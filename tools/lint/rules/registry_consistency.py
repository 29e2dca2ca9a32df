"""registry-consistency — config and observability surfaces stay closed.

Invariant, both directions, whole-program:

Env vars: every ``PBS_PLUS_*`` string literal in the product tree
(``pbs_plus_tpu/``; docstrings and the hook/prefix namespaces with
``__`` excluded) must be declared in ``utils/conf.py``'s ``ENV_VARS``
registry and documented in ``docs/configuration.md`` — and every
registry entry must actually be referenced somewhere in the tree and
documented.  An env knob that exists only in code is undiscoverable; one
that exists only in the registry is dead weight lying to operators.

Metrics: every gauge registered in ``server/metrics.py`` must use a
literal, globally-unique ``pbs_plus_*`` name, carry a non-empty sample
source, and appear in the ``docs/metrics.md`` table — and every
``pbs_plus_*`` row in that table must correspond to a registered gauge.
``histogram(...)`` registrations (ISSUE 12) join the same closed set:
literal, unique across gauges+histograms, documented.
Test/bench-only knobs (``PBS_PLUS_FLEET``, ``PBS_PLUS_BENCH*``, ...)
live outside the product tree and are exempt by construction.

Spans: every ``trace.span/emit/record/round_trip`` literal in the
product tree must be a key of ``utils/trace.py``'s ``SPANS`` registry, every
registry key must be used at some call site, and both directions must
agree with the ``docs/observability.md`` span table — the
failpoint-catalog discipline applied to measurement points (the
per-file ``span-discipline`` rule handles non-literal names and bare
``span()`` calls).
"""

from __future__ import annotations

import os
import re

from .. import protocols
from ..graph import Program, ProgramRule

CONF_SUFFIX = "utils/conf.py"
METRICS_SUFFIX = "server/metrics.py"
TRACE_SUFFIX = "utils/trace.py"
PRODUCT_PREFIX = "pbs_plus_tpu/"
ENV_DOC = os.path.join("docs", "configuration.md")
METRICS_DOC = os.path.join("docs", "metrics.md")
SPAN_DOC = os.path.join("docs", "observability.md")
PROTOCOLS_DOC = os.path.join("docs", "protocols.md")
PROTOCOLS_PATH = "tools/lint/protocols.py"

_METRIC_ROW_RE = re.compile(r"^\|\s*`(pbs_plus_[a-z0-9_]+)`")
# span-table rows: backticked lowercase dotted-or-plain names that are
# NOT metric names (`job`, `ingest.sha`, ...) in the first column
_SPAN_ROW_RE = re.compile(r"^\|\s*`([a-z][a-z0-9_.]*)`")
# exact backticked occurrences only: a plain-text substring must not
# count (PBS_PLUS_CHUNKER would otherwise ride on _CHUNKER_BACKEND's row)
_ENV_DOC_RE = re.compile(r"`(PBS_PLUS_[A-Z0-9_]+)`")
# docs/protocols.md catalog rows: kebab names in the first column
# (family keys, ordering names, boundary names — never the CamelCase
# taxonomy classes or dotted runtime event names)
_PROTO_ROW_RE = re.compile(r"^\|\s*`([a-z][a-z0-9-]*)`")


class RegistryConsistency(ProgramRule):
    name = "registry-consistency"
    invariant = ("PBS_PLUS_* env strings are declared in conf.ENV_VARS "
                 "and documented; pbs_plus_* metrics are literal, "
                 "unique, fed, and documented — both directions")

    def _doc_text(self, program: Program, rel: str) -> "str | None":
        try:
            with open(os.path.join(program.root, rel),
                      "r", encoding="utf-8") as fh:
                return fh.read()
        except OSError:
            return None

    def analyze(self, program: Program):
        out = []
        conf = next((s for s in program.files.values()
                     if s.path.endswith(CONF_SUFFIX)
                     and s.path.startswith(PRODUCT_PREFIX)), None)
        if conf is not None:
            self._check_env(program, conf, out)
        metrics = next((s for s in program.files.values()
                        if s.path.endswith(METRICS_SUFFIX)
                        and s.path.startswith(PRODUCT_PREFIX)), None)
        if metrics is not None:
            self._check_metrics(program, metrics, out)
        tracer = next((s for s in program.files.values()
                       if s.path.endswith(TRACE_SUFFIX)
                       and s.path.startswith(PRODUCT_PREFIX)), None)
        if tracer is not None:
            self._check_spans(program, tracer, out)
        if PROTOCOLS_PATH in program.files:
            # protocols↔docs sync runs when the lint engine itself is
            # in scope (the tools/lint leg of verify_lint.sh), so the
            # findings land on a linted file
            self._check_protocols(program, out)
        return out

    # -- protocols ---------------------------------------------------------
    def _check_protocols(self, program: Program, out) -> None:
        """tools/lint/protocols.py ↔ docs/protocols.md, both ways:
        every declared family/ordering/boundary/taxonomy entry is
        documented, every catalog row in the doc is declared."""
        doc = self._doc_text(program, PROTOCOLS_DOC)
        if doc is None:
            program.report(
                out, self, PROTOCOLS_PATH, 1,
                "docs/protocols.md is missing — every declared protocol "
                "must be cataloged there")
            return
        declared = (
            {f["key"] for f in protocols.FAMILIES}
            | {o["name"] for o in protocols.ORDERINGS}
            | {b["name"] for b in protocols.BOUNDARIES})
        for name in sorted(declared):
            if f"`{name}`" not in doc:
                program.report(
                    out, self, PROTOCOLS_PATH, 1,
                    f"protocols.py declares `{name}` but "
                    "docs/protocols.md does not catalog it")
        for decl in protocols.TYPED_ERRORS:
            cls = decl.partition("::")[2]
            if f"`{cls}`" not in doc:
                program.report(
                    out, self, PROTOCOLS_PATH, 1,
                    f"TYPED_ERRORS declares `{cls}` but "
                    "docs/protocols.md does not catalog it")
        doc_rows = set()
        for line in doc.splitlines():
            m = _PROTO_ROW_RE.match(line.strip())
            if m:
                doc_rows.add(m.group(1))
        for name in sorted(doc_rows - declared):
            program.report(
                out, self, PROTOCOLS_PATH, 1,
                f"docs/protocols.md catalogs `{name}` but protocols.py "
                "declares no such family/ordering/boundary")

    # -- env ---------------------------------------------------------------
    def _check_env(self, program: Program, conf, out) -> None:
        registry = set(conf.env_registry)
        reg_line = conf.env_registry_line or 1
        if not registry:
            program.report(
                out, self, conf.path, reg_line,
                "no ENV_VARS registry found in utils/conf.py — declare "
                "every PBS_PLUS_* knob there (docs/configuration.md)")
            return
        doc = self._doc_text(program, ENV_DOC)
        doc_names = set(_ENV_DOC_RE.findall(doc)) if doc is not None \
            else set()
        referenced: set[str] = set()
        for s in program.files.values():
            if not s.path.startswith(PRODUCT_PREFIX):
                continue
            for name, line in s.env_literals:
                referenced.add(name)
                if name not in registry:
                    program.report(
                        out, self, s.path, line,
                        f"env string `{name}` is not declared in "
                        "utils/conf.py ENV_VARS — add it (with a one-"
                        "line description) and document it in "
                        "docs/configuration.md")
                elif doc is not None and name not in doc_names:
                    program.report(
                        out, self, s.path, line,
                        f"env var `{name}` is declared but missing from "
                        "the docs/configuration.md table")
        if doc is None:
            program.report(
                out, self, conf.path, reg_line,
                "docs/configuration.md is missing — the ENV_VARS "
                "registry must be documented there")
        for name in sorted(registry - referenced):
            program.report(
                out, self, conf.path, reg_line,
                f"ENV_VARS declares `{name}` but nothing in the product "
                "tree references it — remove the entry or wire the knob")
        if doc is not None:
            for name in sorted(registry - doc_names):
                program.report(
                    out, self, conf.path, reg_line,
                    f"ENV_VARS entry `{name}` is missing from the "
                    "docs/configuration.md table")

    # -- metrics -----------------------------------------------------------
    def _check_metrics(self, program: Program, metrics, out) -> None:
        doc = self._doc_text(program, METRICS_DOC)
        doc_names = set()
        if doc is not None:
            for line in doc.splitlines():
                m = _METRIC_ROW_RE.match(line.strip())
                if m:
                    doc_names.add(m.group(1))
        seen: dict[str, int] = {}
        for name, line in metrics.hists:
            if name is None:
                program.report(
                    out, self, metrics.path, line,
                    "histogram registered with a non-literal name — "
                    "metric names must be string literals so the "
                    "registry stays greppable and documentable")
                continue
            if not name.startswith("pbs_plus_"):
                program.report(
                    out, self, metrics.path, line,
                    f"metric `{name}` must carry the pbs_plus_ prefix")
            if name in seen:
                program.report(
                    out, self, metrics.path, line,
                    f"metric `{name}` registered twice (first at line "
                    f"{seen[name]}) — names must be unique")
            seen.setdefault(name, line)
            if doc is not None and name not in doc_names:
                program.report(
                    out, self, metrics.path, line,
                    f"metric `{name}` is missing from the "
                    "docs/metrics.md table")
        for name, line, empty in metrics.gauges:
            if name is None:
                program.report(
                    out, self, metrics.path, line,
                    "gauge registered with a non-literal name — metric "
                    "names must be string literals so the registry "
                    "stays greppable and documentable")
                continue
            if not name.startswith("pbs_plus_"):
                program.report(
                    out, self, metrics.path, line,
                    f"metric `{name}` must carry the pbs_plus_ prefix")
            if name in seen:
                program.report(
                    out, self, metrics.path, line,
                    f"metric `{name}` registered twice (first at line "
                    f"{seen[name]}) — names must be unique")
            seen.setdefault(name, line)
            if empty:
                program.report(
                    out, self, metrics.path, line,
                    f"metric `{name}` is fed a literal empty sample "
                    "list — it can never report; wire a source or "
                    "remove it")
            if doc is not None and name not in doc_names:
                program.report(
                    out, self, metrics.path, line,
                    f"metric `{name}` is missing from the "
                    "docs/metrics.md table")
        if doc is None:
            program.report(
                out, self, metrics.path, 1,
                "docs/metrics.md is missing — every registered gauge "
                "must appear in its table")
        else:
            for name in sorted(doc_names - set(seen)):
                program.report(
                    out, self, metrics.path, 1,
                    f"docs/metrics.md documents `{name}` but no such "
                    "gauge is registered in server/metrics.py")

    # -- spans ---------------------------------------------------------------
    def _check_spans(self, program: Program, tracer, out) -> None:
        registry = set(tracer.span_registry)
        reg_line = tracer.span_registry_line or 1
        if not registry:
            program.report(
                out, self, tracer.path, reg_line,
                "no SPANS registry found in utils/trace.py — declare "
                "every span name there (docs/observability.md)")
            return
        doc = self._doc_text(program, SPAN_DOC)
        doc_names: set[str] = set()
        if doc is not None:
            for line in doc.splitlines():
                m = _SPAN_ROW_RE.match(line.strip())
                if m and not m.group(1).startswith("pbs_plus_"):
                    doc_names.add(m.group(1))
        referenced: set[str] = set()
        for s in program.files.values():
            if not s.path.startswith(PRODUCT_PREFIX):
                continue
            for name, line, _api in s.span_literals:
                if name is None:
                    continue        # span-discipline owns non-literals
                referenced.add(name)
                if name not in registry:
                    program.report(
                        out, self, s.path, line,
                        f"span name `{name}` is not declared in "
                        "utils/trace.py SPANS — add it (with its "
                        "histogram feed) and document it in "
                        "docs/observability.md")
        if doc is None:
            program.report(
                out, self, tracer.path, reg_line,
                "docs/observability.md is missing — the SPANS registry "
                "must be documented there")
        for name in sorted(registry - referenced):
            program.report(
                out, self, tracer.path, reg_line,
                f"SPANS declares `{name}` but no trace.span/emit/record "
                "site in the product tree uses it — remove the entry or "
                "instrument the site")
        if doc is not None:
            for name in sorted(registry - doc_names):
                program.report(
                    out, self, tracer.path, reg_line,
                    f"SPANS entry `{name}` is missing from the "
                    "docs/observability.md span table")
            for name in sorted(doc_names - registry):
                program.report(
                    out, self, tracer.path, reg_line,
                    f"docs/observability.md documents span `{name}` but "
                    "utils/trace.py SPANS does not declare it")
