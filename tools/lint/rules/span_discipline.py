"""span-discipline — spans only through the context-manager API, with
literal, documented names.

Invariant (utils/trace.py, docs/observability.md): every
``trace.span(...)`` call — and every ``trace.round_trip(...)``, the
span of one trip to the device — is a ``with`` context item: a span
object held in a variable and entered by hand has no guaranteed close,
and an unclosed span is exactly the orphan the propagation tests hunt
(``trace.active_spans()``).  ``trace.span/emit/record/round_trip`` names
are STRING LITERALS (a computed name cannot be audited against the closed
``SPANS`` registry) and must appear in the ``docs/observability.md``
span table — the failpoint-discipline contract applied to measurement
points.  Cross-file registry closure (name ∈ SPANS, SPANS ⊆ used,
doc ⟷ registry) is the whole-program ``registry-consistency`` rule's
half; this per-file rule catches the shapes a registry diff cannot:
non-literal names and bare ``span()`` calls.
"""

from __future__ import annotations

import ast
import os
import re

from ..core import REPO_ROOT, Rule

_DOC_PATH = os.path.join(REPO_ROOT, "docs", "observability.md")
_BACKTICKED = re.compile(r"`([A-Za-z0-9_.\-]+)`")
_APIS = ("span", "emit", "record", "round_trip")
_WITH_ONLY = ("span", "round_trip")


class SpanDiscipline(Rule):
    name = "span-discipline"
    invariant = ("trace.span and trace.round_trip are used only as `with` "
                 "context items, and trace.span/emit/record/round_trip "
                 "names are literal and listed in docs/observability.md")

    def __init__(self):
        self._catalog: "set[str] | None" = None
        self._doc_missing = False

    def _load_catalog(self) -> set:
        if self._catalog is None:
            try:
                with open(_DOC_PATH, "r", encoding="utf-8") as f:
                    self._catalog = set(_BACKTICKED.findall(f.read()))
            except OSError:
                self._catalog = set()
                self._doc_missing = True
        return self._catalog

    def begin_file(self, ctx):
        # the tracing module itself defines the API (bare internal
        # calls, registry declaration) — exempt
        return not ctx.path.endswith("utils/trace.py")

    def visit_Call(self, ctx, node: ast.Call) -> None:
        func = node.func
        if not isinstance(func, ast.Attribute) or func.attr not in _APIS:
            return
        recv = func.value
        if not (isinstance(recv, ast.Name)
                and recv.id.lstrip("_") == "trace"):
            return
        if not node.args or not (
                isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            ctx.report(self, node,
                       f"`trace.{func.attr}` must take a string literal "
                       "span name (computed names can't be checked "
                       "against the SPANS registry or the "
                       "docs/observability.md catalog)")
            return
        if func.attr in _WITH_ONLY and id(node) not in ctx.with_ctx_ids:
            ctx.report(self, node,
                       f"`trace.{func.attr}(...)` used outside a `with` "
                       "item — a manually-entered span has no guaranteed "
                       "close and leaks as an orphan; use `with "
                       f"trace.{func.attr}(...):` (one-shot measurements "
                       "go through trace.emit/record)")
            return
        name = node.args[0].value
        catalog = self._load_catalog()
        if self._doc_missing:
            ctx.report(self, node,
                       "docs/observability.md is missing — every span "
                       "name must be cataloged there")
            return
        if name not in catalog:
            ctx.report(self, node,
                       f"span name {name!r} is not documented in "
                       "docs/observability.md — add it to the span "
                       "vocabulary table")
