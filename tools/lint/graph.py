"""pbslint whole-program symbol graph (the v2 engine substrate).

The per-file engine in ``core.py`` sees one AST at a time; the four
interprocedural rules (guarded-by, lock-order, transitive
no-blocking-in-async, registry-consistency) need facts that only exist
ACROSS files: who calls whom, which locks a callee may acquire, where
an env string is declared vs read.  This module builds that view in two
stages:

1. **Extraction** (``summarize_source``): one AST walk per file distills
   a ``FileSummary`` — module identity, import aliases, classes with
   their attribute/lock declarations and ``# guarded-by:`` annotations,
   and per function: every call, every lock acquisition, and every
   ``self.<attr>`` / annotated-global access, each tagged with the set
   of lock expressions lexically held at that point.  Summaries are
   plain dicts of strings/ints, so they serialize.

2. **Linking** (``Program``): summaries resolve into a call graph
   (``self.m()`` through the class/ancestor method table, ``alias.f()``
   through import aliases, bare ``f()`` through module scope and
   from-imports) and a canonical lock namespace
   (``pkg/mod.py::Class._lock``), plus reverse edges and the
   reachable-acquisition fixpoint the rules consume.

**Cache**: extraction is keyed by each file's sha256 and persisted under
``build/pbslint/graph-cache.json`` (gitignored); an unchanged file costs
one hash, not a parse.  Linking is always recomputed — it is cheap and
depends on the whole file set.

Known, deliberate extraction limits (documented in
docs/static-analysis.md): lambda bodies are opaque (they run in an
unknown context — recording their accesses under the enclosing held-set
would be wrong in both directions); calls through arbitrary objects
(``obj.method()`` where ``obj`` is not ``self``/an alias) do not resolve;
``lock.acquire()`` outside a ``with`` is not an acquisition event.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field

from .core import REPO_ROOT, Violation, iter_py_files

CACHE_VERSION = 6
CACHE_PATH = os.path.join(REPO_ROOT, "build", "pbslint",
                          "graph-cache.json")

# fs mutations the durable-write / ordering rules care about, recorded
# per function as ["fsops"] entries (op, line, argument text)
_FS_OPS = {
    "os.replace", "os.rename", "os.link", "os.unlink", "os.remove",
    "shutil.move",
}
_OPEN_WRITE_RE = re.compile(r"[wax+]")

_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*([\w.\[\]]+)")
_LOCK_ORDER_RE = re.compile(r"#\s*pbslint:\s*lock-order\s+([\w.\-]+)")
_ENV_NAME_RE = re.compile(r"^PBS_PLUS_[A-Z0-9](?:[A-Z0-9_]*[A-Z0-9])?$")

# constructors whose result is a lock for acquisition/ordering purposes;
# value = reentrancy class ("rlock" may self-nest, "lock" may not)
_LOCK_CTORS = {
    "threading.Lock": "lock",
    "threading.RLock": "rlock",
    "threading.Semaphore": "lock",
    "threading.BoundedSemaphore": "lock",
    "threading.Condition": "lock",
    "asyncio.Lock": "lock",
    "asyncio.Semaphore": "lock",
    "asyncio.Condition": "lock",
    "Lock": "lock",
    "RLock": "rlock",
}


def _dotted(node: ast.AST) -> "str | None":
    """``a.b.c`` for a Name/Attribute chain; subscripts collapse to the
    chain of their value (``self._shard_locks[i]`` -> ``self._shard_locks``)
    so a lock picked from a per-shard list canonicalizes to the list
    attribute — ordering discipline is class-level, not instance-level."""
    parts: list[str] = []
    while True:
        if isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        else:
            return None


# -- summary shape (plain dicts: these round-trip through the JSON cache) --
#
# FileSummary.functions[qual] = {
#   "line": int, "is_async": bool, "cls": "Class" | None,
#   "calls":   [[name, line, [held...]], ...],
#   "acquires":[[raw, line, [held_before...], vocab_or_None], ...],
#   "reads":   [[attr, line, [held...]], ...],   # self.<attr> loads
#   "writes":  [[attr, line, [held...]], ...],   # self.<attr> stores
#   "greads"/"gwrites": same for annotated module globals,
#   "blocking":[[prim, line], ...],              # direct blocking calls
#   "fsops":   [[op, line, argtext], ...],       # os.replace/... + open(w)
#   "raises":  [[name, line, has_cause], ...],   # raise X(...) [from e]
# }


@dataclass
class FileSummary:
    path: str                                   # repo-relative posix
    module: str                                 # dotted module name
    imports: dict = field(default_factory=dict)     # alias -> module dotted
    from_imports: dict = field(default_factory=dict)  # alias -> [pkg, name]
    classes: dict = field(default_factory=dict)
    functions: dict = field(default_factory=dict)
    module_guarded: dict = field(default_factory=dict)  # global -> lock expr
    module_locks: dict = field(default_factory=dict)    # global -> lock kind
    env_literals: list = field(default_factory=list)    # [name, line]
    env_registry: list = field(default_factory=list)    # ENV_VARS keys
    env_registry_line: int = 0
    gauges: list = field(default_factory=list)  # [name|None, line, empty?]
    # histogram("name", ...) registrations in server/metrics.py
    hists: list = field(default_factory=list)           # [name|None, line]
    # trace.span/emit/record call sites: [name|None, line, api]
    span_literals: list = field(default_factory=list)
    span_registry: list = field(default_factory=list)   # trace.SPANS keys
    span_registry_line: int = 0
    suppress: dict = field(default_factory=dict)        # line -> [rules]
    file_suppress: list = field(default_factory=list)

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in (
            "path", "module", "imports", "from_imports", "classes",
            "functions", "module_guarded", "module_locks", "env_literals",
            "env_registry", "env_registry_line", "gauges", "hists",
            "span_literals", "span_registry", "span_registry_line",
            "suppress", "file_suppress")}

    @classmethod
    def from_dict(cls, d: dict) -> "FileSummary":
        s = cls(path=d["path"], module=d["module"])
        for k in ("imports", "from_imports", "classes", "functions",
                  "module_guarded", "module_locks", "env_literals",
                  "env_registry", "gauges", "hists", "span_literals",
                  "span_registry", "file_suppress"):
            setattr(s, k, d[k])
        s.env_registry_line = d.get("env_registry_line", 0)
        s.span_registry_line = d.get("span_registry_line", 0)
        # JSON stringifies int keys
        s.suppress = {int(k): v for k, v in d["suppress"].items()}
        return s


def module_name_for(relpath: str) -> str:
    mod = relpath[:-3] if relpath.endswith(".py") else relpath
    parts = mod.replace("\\", "/").split("/")
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class _Extractor(ast.NodeVisitor):
    """One walk: fills a FileSummary.  Maintains class/function stacks
    and the lexically-held lock-expression stack."""

    def __init__(self, summary: FileSummary, lines: list[str]):
        self.s = summary
        self.lines = lines
        self.cls_stack: list[str] = []
        self.func_stack: list[str] = []
        self.held: list[str] = []
        self._docstring_ids: set[int] = set()
        self._registry_span: "tuple[int, int] | None" = None

    # -- helpers -----------------------------------------------------------
    def _fn(self) -> "dict | None":
        if not self.func_stack:
            return None
        return self.s.functions[self.func_stack[-1]]

    def _line_comment(self, lineno: int) -> str:
        # raw text is enough here: guarded-by / lock-order markers live in
        # real comments in this tree; a string literal containing one
        # would only ever ADD an annotation (fail-closed, never unsound)
        if 1 <= lineno <= len(self.lines):
            line = self.lines[lineno - 1]
            i = line.find("#")
            if i >= 0:
                return line[i:]
        return ""

    def _annotation_near(self, regex, lineno: int,
                         end_lineno: "int | None" = None) -> "str | None":
        lines = [lineno]
        # the line above counts only when it is comment-ONLY — a
        # trailing annotation on the previous statement must not bleed
        # onto this one (the suppression scanner's rule, same reason)
        if lineno >= 2 and 1 <= lineno - 1 <= len(self.lines) and \
                re.match(r"^\s*#", self.lines[lineno - 2]):
            lines.append(lineno - 1)
        if end_lineno is not None and end_lineno != lineno:
            lines.append(end_lineno)    # multi-line stmt: trailing comment
        for ln in lines:
            m = regex.search(self._line_comment(ln))
            if m:
                return m.group(1)
        return None

    def _lock_ctor_kind(self, value: ast.AST) -> "str | None":
        for node in ast.walk(value):
            if isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name in _LOCK_CTORS:
                    return _LOCK_CTORS[name]
        return None

    def _mark_docstrings(self, node) -> None:
        body = getattr(node, "body", None)
        if body and isinstance(body[0], ast.Expr) and \
                isinstance(body[0].value, ast.Constant) and \
                isinstance(body[0].value.value, str):
            self._docstring_ids.add(id(body[0].value))

    # -- structure ---------------------------------------------------------
    def visit_Module(self, node: ast.Module) -> None:
        self._mark_docstrings(node)
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.s.imports[a.asname or a.name.split(".")[0]] = a.name

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.level:
            base = self.s.module.split(".")
            # a module's own dotted name counts as a package level for
            # __init__ files only; summaries use source modules, so
            # level=1 strips the module leaf, each extra level one pkg
            base = base[:len(base) - node.level]
            pkg = ".".join(base + ([node.module] if node.module else []))
        else:
            pkg = node.module or ""
        for a in node.names:
            if a.name == "*":
                continue
            self.s.from_imports[a.asname or a.name] = [pkg, a.name]

    def _visit_func(self, node, is_async: bool) -> None:
        self._mark_docstrings(node)
        # qualified name: Class.method for methods, outer.inner for
        # nested functions, plain name at module level
        parts = []
        if self.func_stack:
            parts = [self.func_stack[-1]]
        elif self.cls_stack:
            parts = [self.cls_stack[-1]]
        qual = ".".join(parts + [node.name]) if parts else node.name
        self.s.functions[qual] = {
            "line": node.lineno, "is_async": is_async,
            "cls": self.cls_stack[-1] if self.cls_stack
            and not self.func_stack else None,
            "calls": [], "acquires": [], "reads": [], "writes": [],
            "greads": [], "gwrites": [], "blocking": [],
            "fsops": [], "raises": [],
        }
        if self.cls_stack and not self.func_stack:
            self.s.classes[self.cls_stack[-1]]["methods"].append(node.name)
        self.func_stack.append(qual)
        outer_held = self.held
        self.held = []                  # a new frame holds nothing
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self.held = outer_held
        self.func_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_func(node, False)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_func(node, True)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._mark_docstrings(node)
        if self.func_stack or self.cls_stack:
            # nested/local classes: walk for calls but don't model
            self.generic_visit(node)
            return
        self.s.classes[node.name] = {
            "line": node.lineno,
            "bases": [b for b in (_dotted(x) for x in node.bases) if b],
            "lock_attrs": {}, "guarded": {}, "methods": [],
            "vocab": {},            # lock attr -> lock-order name
        }
        self.cls_stack.append(node.name)
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self.cls_stack.pop()

    def visit_Lambda(self, node: ast.Lambda) -> None:
        return              # opaque: runs in an unknown context

    # -- with / locks ------------------------------------------------------
    def _visit_with(self, node) -> None:
        fn = self._fn()
        acquired: list[str] = []
        for item in node.items:
            raw = _dotted(item.context_expr)
            vocab = self._annotation_near(_LOCK_ORDER_RE, node.lineno)
            if raw is None and vocab is None:
                continue
            if fn is not None:
                fn["acquires"].append(
                    [raw or "", node.lineno, list(self.held), vocab])
            # held entries carry BOTH faces of the acquisition: the raw
            # expression (guarded-by matches structurally against it)
            # and the vocab name when annotated (lock-order identity) —
            # a vocab-named `with` must not stop satisfying guarded-by
            entry = [raw or "", vocab]
            self.held.append(entry)
            acquired.append(entry)
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        for _ in acquired:
            self.held.pop()

    visit_With = _visit_with
    visit_AsyncWith = _visit_with

    # -- assignments (lock decls, guarded-by, registry) --------------------
    def _note_target(self, target: ast.AST, value: "ast.AST | None",
                     lineno: int, end_lineno: "int | None" = None) -> None:
        guard = self._annotation_near(_GUARDED_RE, lineno, end_lineno)
        vocab = self._annotation_near(_LOCK_ORDER_RE, lineno, end_lineno)
        kind = self._lock_ctor_kind(value) if value is not None else None
        if isinstance(target, ast.Attribute) and \
                isinstance(target.value, ast.Name) and \
                target.value.id == "self" and self.cls_stack:
            cls = self.s.classes.get(self.cls_stack[-1])
            if cls is None:
                return
            if kind:
                cls["lock_attrs"][target.attr] = kind
            if guard:
                cls["guarded"][target.attr] = guard
            if vocab:
                cls["vocab"][target.attr] = vocab
        elif isinstance(target, ast.Name) and not self.cls_stack \
                and not self.func_stack:
            if kind:
                self.s.module_locks[target.id] = kind
            if guard:
                self.s.module_guarded[target.id] = guard

    def visit_Assign(self, node: ast.Assign) -> None:
        if not self.func_stack and not self.cls_stack:
            # module level: check for the ENV_VARS registry declaration
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "ENV_VARS" and \
                        isinstance(node.value, ast.Dict):
                    self.s.env_registry = [
                        k.value for k in node.value.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)]
                    self.s.env_registry_line = node.lineno
                    self._registry_span = (
                        node.lineno,
                        node.value.end_lineno or node.lineno)
                if isinstance(t, ast.Name) and t.id == "SPANS" and \
                        isinstance(node.value, ast.Dict) and \
                        self.s.path.endswith("utils/trace.py"):
                    # the span-name registry (registry-consistency)
                    self.s.span_registry = [
                        k.value for k in node.value.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)]
                    self.s.span_registry_line = node.lineno
        for t in node.targets:
            self._note_target(t, node.value, node.lineno, node.end_lineno)
        self._record_stores(node.targets)
        self.visit(node.value)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._note_target(node.target, node.value, node.lineno,
                          node.end_lineno)
        self._record_stores([node.target])
        if node.value is not None:
            self.visit(node.value)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._record_stores([node.target])
        self._record_attr(node.target, "reads")   # += reads too
        self.visit(node.value)

    def _record_stores(self, targets) -> None:
        for t in targets:
            for node in ast.walk(t):
                self._record_attr(node, "writes")

    # -- accesses ----------------------------------------------------------
    def _record_attr(self, node: ast.AST, bucket: str) -> None:
        fn = self._fn()
        if fn is None:
            return
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "self":
            fn[bucket].append([node.attr, node.lineno, list(self.held)])
        elif isinstance(node, ast.Name) and \
                node.id in self.s.module_guarded:
            fn["g" + bucket].append([node.id, node.lineno, list(self.held)])

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if isinstance(node.ctx, ast.Load):
            self._record_attr(node, "reads")
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self._record_attr(node, "reads")

    def _arg_text(self, node: ast.Call) -> str:
        try:
            return ", ".join(ast.unparse(a) for a in node.args)
        except Exception:           # unparse is best-effort display text
            return ""

    def _open_write_mode(self, node: ast.Call) -> bool:
        mode = None
        if len(node.args) >= 2:
            mode = node.args[1]
        for kw in node.keywords:
            if kw.arg == "mode":
                mode = kw.value
        if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
            return bool(_OPEN_WRITE_RE.search(mode.value))
        return False        # default "r" / dynamic mode: not a write

    def visit_Raise(self, node: ast.Raise) -> None:
        fn = self._fn()
        if fn is not None and node.exc is not None:
            exc = node.exc
            if isinstance(exc, ast.Call):
                exc = exc.func
            name = _dotted(exc)
            if name:
                fn["raises"].append(
                    [name, node.lineno, node.cause is not None])
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = self._fn()
        name = _dotted(node.func)
        if name and fn is not None:
            fn["calls"].append([name, node.lineno, list(self.held)])
        if fn is not None:
            if name in _FS_OPS:
                fn["fsops"].append(
                    [name, node.lineno, self._arg_text(node)])
            elif name in ("open", "io.open") and \
                    self._open_write_mode(node):
                fn["fsops"].append(
                    ["open-write", node.lineno, self._arg_text(node)])
        if name == "gauge" and node.args and \
                self.s.path.endswith("server/metrics.py"):
            first = node.args[0]
            lit = first.value if isinstance(first, ast.Constant) and \
                isinstance(first.value, str) else None
            empty = (len(node.args) > 2
                     and isinstance(node.args[2], ast.List)
                     and not node.args[2].elts)
            self.s.gauges.append([lit, node.lineno, empty])
        if name == "histogram" and node.args and \
                self.s.path.endswith("server/metrics.py"):
            first = node.args[0]
            lit = first.value if isinstance(first, ast.Constant) and \
                isinstance(first.value, str) else None
            self.s.hists.append([lit, node.lineno])
        if name is not None and "." in name:
            recv, _, api = name.rpartition(".")
            if api in ("span", "emit", "record", "round_trip") and \
                    recv.lstrip("_") == "trace" and node.args:
                first = node.args[0]
                lit = first.value if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str) else None
                self.s.span_literals.append([lit, node.lineno, api])
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant) -> None:
        if isinstance(node.value, str) and id(node) not in \
                self._docstring_ids and "__" not in node.value and \
                _ENV_NAME_RE.match(node.value):
            span = self._registry_span
            if not (span and span[0] <= node.lineno <= span[1]):
                self.s.env_literals.append([node.value, node.lineno])


def summarize_source(source: str, relpath: str) -> FileSummary:
    tree = ast.parse(source, filename=relpath)
    s = FileSummary(path=relpath, module=module_name_for(relpath))
    ex = _Extractor(s, source.splitlines())
    ex.visit(tree)
    # suppressions piggyback on the core Context scanner so program-rule
    # findings honor the exact same disable syntax as per-file rules
    from .core import Context
    ctx = Context(relpath, source, ast.parse("pass"))
    s.suppress = {ln: sorted(rules)
                  for ln, rules in ctx._line_suppress.items()}
    s.file_suppress = sorted(ctx._file_suppress)
    return s


# -- cache ------------------------------------------------------------------

def rules_fingerprint() -> str:
    """sha256 over the lint engine's own sources (tools/lint/**/*.py).
    A cache entry is only as good as the extractor and the rule set that
    consume it — an edited rule (or protocols.py declaration) must force
    re-analysis even though the ANALYZED files' hashes are unchanged, so
    the fingerprint joins CACHE_VERSION in the cache key."""
    h = hashlib.sha256()
    lint_dir = os.path.dirname(os.path.abspath(__file__))
    for dirpath, dirnames, filenames in os.walk(lint_dir):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            p = os.path.join(dirpath, fn)
            rel = os.path.relpath(p, lint_dir).replace(os.sep, "/")
            h.update(rel.encode("utf-8"))
            h.update(b"\0")
            try:
                with open(p, "rb") as fh:
                    h.update(fh.read())
            except OSError:
                pass
            h.update(b"\0")
    return h.hexdigest()


def _load_cache(path: str = CACHE_PATH,
                rules_sha: "str | None" = None) -> dict:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
        if data.get("version") == CACHE_VERSION and (
                rules_sha is None or data.get("rules") == rules_sha):
            return data.get("files", {})
    except (OSError, ValueError):
        pass
    return {}


def _save_cache(files: dict, path: str = CACHE_PATH,
                rules_sha: "str | None" = None) -> None:
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"version": CACHE_VERSION, "rules": rules_sha,
                       "files": files}, fh)
        os.replace(tmp, path)
    except OSError:
        pass                # cache is an optimization, never a failure


# -- program ----------------------------------------------------------------

class Program:
    """Linked whole-program view handed to every ProgramRule."""

    def __init__(self, summaries: "list[FileSummary]",
                 root: str = REPO_ROOT):
        self.root = root
        self.files: dict[str, FileSummary] = {s.path: s for s in summaries}
        self.by_module: dict[str, FileSummary] = {
            s.module: s for s in summaries}
        # "path::qual" -> function record (+ backrefs)
        self.funcs: dict[str, dict] = {}
        self.func_file: dict[str, FileSummary] = {}
        for s in summaries:
            for qual, fn in s.functions.items():
                fid = f"{s.path}::{qual}"
                self.funcs[fid] = fn
                self.func_file[fid] = s
        self.calls: dict[str, list] = {}        # fid -> [(callee fid, line,
        self.callers: dict[str, list] = {}      #          held)], reverse
        self._link_calls()
        self._stats = {"files": len(summaries),
                       "functions": len(self.funcs),
                       "edges": sum(len(v) for v in self.calls.values())}

    # -- resolution --------------------------------------------------------
    def _class_attr_owner(self, s: FileSummary, cls_name: str,
                          attr: str, key: str) -> "tuple | None":
        """(summary, class name) declaring ``attr`` in ``key`` ('lock_attrs'
        / 'guarded' / 'vocab'), walking project base classes."""
        seen = set()
        stack = [(s, cls_name)]
        while stack:
            cs, cn = stack.pop()
            if (cs.path, cn) in seen:
                continue
            seen.add((cs.path, cn))
            cls = cs.classes.get(cn)
            if cls is None:
                continue
            if attr in cls[key]:
                return cs, cn
            for base in cls["bases"]:
                target = self._resolve_class(cs, base)
                if target is not None:
                    stack.append(target)
        return None

    def _resolve_class(self, s: FileSummary,
                       name: str) -> "tuple[FileSummary, str] | None":
        head, _, tail = name.partition(".")
        if not tail and head in s.classes:
            return s, head
        if head in s.from_imports and not tail:
            pkg, orig = s.from_imports[head]
            target = self.by_module.get(pkg)
            if target is not None and orig in target.classes:
                return target, orig
        if tail and head in s.imports:
            target = self.by_module.get(s.imports[head])
            if target is not None and tail in target.classes:
                return target, tail
        return None

    def _resolve_module_alias(self, s: FileSummary,
                              alias: str) -> "FileSummary | None":
        if alias in s.imports:
            return self.by_module.get(s.imports[alias])
        if alias in s.from_imports:
            pkg, orig = s.from_imports[alias]
            return self.by_module.get(f"{pkg}.{orig}" if pkg else orig)
        return None

    def resolve_call(self, s: FileSummary, caller_qual: str,
                     name: str) -> "str | None":
        """fid of the project function ``name`` refers to at a call site
        inside ``caller_qual``, or None."""
        caller = s.functions.get(caller_qual, {})
        head, _, tail = name.partition(".")
        if head == "self" and tail:
            cls_name = caller.get("cls") or caller_qual.split(".")[0]
            meth = tail.split(".")[0]
            owner = self._find_method(s, cls_name, meth)
            if owner is not None:
                os_, ocn = owner
                return f"{os_.path}::{ocn}.{meth}"
            return None
        if not tail:
            if name in s.functions and s.functions[name]["cls"] is None:
                return f"{s.path}::{name}"
            nested = f"{caller_qual}.{name}"
            if nested in s.functions:
                return f"{s.path}::{nested}"
            if name in s.from_imports:
                pkg, orig = s.from_imports[name]
                target = self.by_module.get(pkg)
                if target is not None and orig in target.functions and \
                        target.functions[orig]["cls"] is None:
                    return f"{target.path}::{orig}"
            return None
        # alias.func or Class.method
        target = self._resolve_module_alias(s, head)
        if target is not None:
            sub = tail.split(".")[0]
            if sub in target.functions and \
                    target.functions[sub]["cls"] is None:
                return f"{target.path}::{sub}"
            return None
        cls = self._resolve_class(s, head)
        if cls is not None:
            cs, cn = cls
            meth = tail.split(".")[0]
            owner = self._find_method(cs, cn, meth)
            if owner is not None:
                os_, ocn = owner
                return f"{os_.path}::{ocn}.{meth}"
        return None

    def _find_method(self, s: FileSummary, cls_name: str,
                     meth: str) -> "tuple[FileSummary, str] | None":
        seen = set()
        stack = [(s, cls_name)]
        while stack:
            cs, cn = stack.pop()
            if (cs.path, cn) in seen:
                continue
            seen.add((cs.path, cn))
            cls = cs.classes.get(cn)
            if cls is None:
                continue
            if meth in cls["methods"]:
                return cs, cn
            for base in cls["bases"]:
                target = self._resolve_class(cs, base)
                if target is not None:
                    stack.append(target)
        return None

    def _link_calls(self) -> None:
        for s in self.files.values():
            for qual, fn in s.functions.items():
                fid = f"{s.path}::{qual}"
                out = []
                for name, line, held in fn["calls"]:
                    callee = self.resolve_call(s, qual, name)
                    if callee is not None:
                        out.append((callee, line, held))
                        self.callers.setdefault(callee, []).append(
                            (fid, line, held))
                if out:
                    self.calls[fid] = out

    # -- lock canonicalization --------------------------------------------
    def canon_lock(self, s: FileSummary, qual: str,
                   raw: str) -> "tuple[str, str] | None":
        """(canonical name, kind) for a lock expression seen inside
        function ``qual`` of file ``s``, or None when unresolvable.
        ``self._x`` resolves through the class's (or ancestors') lock
        declarations; a bare name through module lock globals; a
        declaration-site ``# pbslint: lock-order <name>`` renames."""
        raw = re.sub(r"\[.*\]", "", raw)
        fn = s.functions.get(qual, {})
        head, _, tail = raw.partition(".")
        if head == "self" and tail and "." not in tail:
            cls_name = fn.get("cls") or qual.split(".")[0]
            owner = self._class_attr_owner(s, cls_name, tail, "lock_attrs")
            if owner is None:
                return None
            os_, ocn = owner
            kind = os_.classes[ocn]["lock_attrs"][tail]
            vocab_owner = self._class_attr_owner(s, cls_name, tail, "vocab")
            if vocab_owner is not None:
                vs, vcn = vocab_owner
                return vs.classes[vcn]["vocab"][tail], kind
            return f"{os_.path}::{ocn}.{tail}", kind
        if not tail and head in s.module_locks:
            return f"{s.path}::{head}", s.module_locks[head]
        return None

    def suppressed(self, path: str, rule: str, line: int) -> bool:
        s = self.files.get(path)
        if s is None:
            return False
        if rule in s.file_suppress or "all" in s.file_suppress:
            return True
        names = s.suppress.get(line, ())
        return rule in names or "all" in names

    def report(self, out: "list[Violation]", rule, path: str, line: int,
               message: str) -> None:
        if not self.suppressed(path, rule.name, line):
            out.append(Violation(rule.name, path, line, message))

    @property
    def stats(self) -> dict:
        return dict(self._stats)


def build_program(paths: "list[str]", *, root: str = REPO_ROOT,
                  use_cache: bool = True,
                  cache_path: str = CACHE_PATH) -> "tuple[Program, list]":
    """Summarize every .py under ``paths`` (cache-assisted) and link.
    Returns (program, errors) — errors are unparseable files, reported
    like core parse errors."""
    rules_sha = rules_fingerprint() if use_cache else None
    cached = _load_cache(cache_path, rules_sha) if use_cache else {}
    fresh: dict[str, dict] = {}
    summaries: list[FileSummary] = []
    errors: list[str] = []
    for fp in iter_py_files(paths):
        try:
            with open(fp, "rb") as fh:
                raw = fh.read()
        except OSError as e:
            errors.append(f"{fp}: {e}")
            continue
        ap = os.path.abspath(fp)
        try:
            rel = os.path.relpath(ap, root).replace(os.sep, "/")
        except ValueError:
            rel = ap
        digest = hashlib.sha256(raw).hexdigest()
        ent = cached.get(rel)
        if ent is not None and ent.get("sha256") == digest:
            summaries.append(FileSummary.from_dict(ent["summary"]))
            fresh[rel] = ent
            continue
        try:
            summary = summarize_source(
                raw.decode("utf-8", errors="replace"), rel)
        except SyntaxError as e:
            errors.append(f"{rel}: {e}")
            continue
        summaries.append(summary)
        fresh[rel] = {"sha256": digest, "summary": summary.to_dict()}
    if use_cache:
        # merge-save: a subset run must not evict the full tree's
        # entries; stale paths age out via the size cap below
        merged = dict(cached)
        merged.update(fresh)
        if len(merged) > 4096:
            merged = fresh
        if merged != cached:
            _save_cache(merged, cache_path, rules_sha)
    return Program(summaries, root=root), errors


class ProgramRule:
    """Base class for whole-program rules: one ``analyze`` over the
    linked Program instead of per-node callbacks.  Report through
    ``program.report`` so suppressions apply."""

    name: str = ""
    invariant: str = ""

    def analyze(self, program: Program) -> "list[Violation]":
        raise NotImplementedError
