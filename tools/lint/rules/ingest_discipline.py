"""ingest-discipline — batched ingest stages stay on the typed seam.

Invariant (pxar/ingestbackend.py): the write-path stream classes —
``pxar/transfer.py`` and ``pxar/pipeline.py`` — reach the batched
probe/presketch/fingerprint stages only through the declared ingest
backend (``resolve_ingest_backend`` → ``capabilities`` branch).  Two
hazards are flagged:

- **Resurrected duck-typing**: ``getattr(store, "probe_batch", None)``
  / ``"presketch_batch"`` etc. — the silent-attribute-miss pattern the
  typed protocol replaced.  An index-less store must be a *declared*
  no-capability backend, not an AttributeError swallowed into a
  behavior fork.
- **Resurrected per-stage store calls**: ``X.probe_batch(...)`` /
  ``X.presketch_batch(...)`` on anything that is not the resolved
  ingest backend, and direct calls into the batched fingerprint
  kernels (``sha256_chunks`` / ``sha256_chunks_device`` /
  ``sha256_stream_chunks`` / ``sha256_streams_chunks``) — chunk
  fingerprinting flows through the injected ``batch_hasher`` seam,
  never a per-stage kernel dispatch of the stream's own.
- **Undeclared concurrent inserts**: a store-pool fan-out
  (``StoreFanOut(...)``, pxar/storepool.py) in a module that never
  reads the resolved backend's ``capabilities.concurrent_insert`` —
  whether a stream's inserts may run at once is the store's declared
  capability (a remote sink shares one connection, the similarity tier
  depends on the order chunks arrive in).

Receivers whose source text mentions the resolved backend
(``self._ingest`` / a local named ``backend``) are the sanctioned seam.
"""

from __future__ import annotations

import ast

from ..core import Rule
from ._util import call_name

_SCOPES = ("pbs_plus_tpu/pxar/transfer.py",
           "pbs_plus_tpu/pxar/pipeline.py")
_BATCH_ATTRS = frozenset({"probe_batch", "presketch_batch"})
_DUCK_NAMES = frozenset({"probe_batch", "presketch_batch", "presketch",
                         "sketch_batch", "note_dedup_hit"})
_FP_KERNELS = frozenset({"sha256_chunks", "sha256_chunks_device",
                         "sha256_stream_chunks", "sha256_streams_chunks"})
_SEAM_MARKERS = ("ingest", "backend")


class IngestDiscipline(Rule):
    name = "ingest-discipline"
    invariant = ("transfer.py/pipeline.py reach probe/presketch/"
                 "fingerprint only through the declared ingest backend "
                 "— no getattr duck-typing, no resurrected per-stage "
                 "store/kernel calls")

    def begin_file(self, ctx):
        self._fan_outs: list = []
        self._declared = False
        return ctx.path in _SCOPES

    def end_file(self, ctx) -> None:
        if self._declared:
            return
        for node in self._fan_outs:
            ctx.report(self, node,
                       "store-pool fan-out with no read of the resolved "
                       "backend's `capabilities.concurrent_insert`: "
                       "concurrent inserts are a DECLARED capability "
                       "(pxar/ingestbackend.py)")

    def visit_Attribute(self, ctx, node: ast.Attribute) -> None:
        if node.attr == "concurrent_insert":
            try:
                recv = ast.unparse(node.value).lower()
            except Exception:
                recv = ""
            if any(m in recv for m in _SEAM_MARKERS):
                self._declared = True

    def visit_Call(self, ctx, node: ast.Call) -> None:
        func = node.func
        if call_name(node) in ("StoreFanOut", "storepool.StoreFanOut"):
            self._fan_outs.append(node)
            return
        if call_name(node) == "getattr" and len(node.args) >= 2:
            arg = node.args[1]
            if isinstance(arg, ast.Constant) and arg.value in _DUCK_NAMES:
                ctx.report(self, node,
                           f"getattr duck-typing for {arg.value!r}: an "
                           "index-less store is a DECLARED capability "
                           "(ingestbackend.resolve_ingest_backend), not "
                           "a silent attribute miss")
            return
        if isinstance(func, ast.Attribute):
            if func.attr in _BATCH_ATTRS:
                try:
                    recv = ast.unparse(func.value)
                except Exception:
                    recv = ""
                low = recv.lower()
                if not any(m in low for m in _SEAM_MARKERS):
                    ctx.report(self, node,
                               f"`{recv}.{func.attr}(...)` is a "
                               "per-stage store call: batched ingest "
                               "stages go through the resolved ingest "
                               "backend (pxar/ingestbackend.py)")
                return
            if func.attr in _FP_KERNELS:
                ctx.report(self, node,
                           f"direct `{func.attr}` kernel dispatch in a "
                           "stream class: chunk fingerprinting flows "
                           "through the batch_hasher seam")
                return
        if isinstance(func, ast.Name) and func.id in _FP_KERNELS:
            ctx.report(self, node,
                       f"direct `{func.id}` kernel dispatch in a stream "
                       "class: chunk fingerprinting flows through the "
                       "batch_hasher seam")
