"""Typed ingest-backend protocol — declared capabilities, not duck-typing.

The write path's batched stages (dedup-index probe, similarity
presketch) used to reach the store via
``getattr(store, "probe_batch", None)`` — an index-less store was a
*silent attribute miss*, indistinguishable from a typo'd method name or
a store that grew the surface under a different spelling.  This module
replaces that with an explicit seam (ISSUE 13 satellite):

- Stores that implement the batched ingest surface **declare** it via
  ``ingest_capabilities() -> IngestCapabilities`` (``ChunkStore`` in
  pxar/datastore.py answers from its live index/similarity attachments;
  ``PBSChunkSink`` declares the constant no-capability answer).
- ``resolve_ingest_backend(store)`` resolves the declaration ONCE at
  stream open (the ``bind_stream`` discipline) into a small typed
  adapter; writers then branch on ``backend.capabilities`` — no
  ``isinstance`` checks, no per-call attribute probing.
- A store without the declaration (legacy/test doubles) resolves to
  ``InlineIngestBackend``: the *declared* fallback whose capabilities
  are statically empty — per-chunk ``insert`` remains the membership
  oracle, exactly the old index-less behavior, but now spelled out.

pbslint's ``ingest-discipline`` rule keeps transfer.py/pipeline.py on
this seam (docs/static-analysis.md).
"""

from __future__ import annotations

from typing import NamedTuple, Protocol, runtime_checkable


class IngestCapabilities(NamedTuple):
    """What the store can batch for the write path.

    ``probe``     — ``probe_batch`` answers membership authoritatively
                    for a whole digest batch (a dedup index is attached).
    ``presketch`` — ``presketch_batch`` precomputes similarity sketches
                    (+ delta-base candidate shortlists) for a batch's
                    novel chunks (the delta tier is attached).
    ``concurrent_insert`` — ``insert`` may be called from several
                    threads at once for one stream, and what it stores
                    does not depend on the order of those calls: the
                    store stage of a hash batch fans its novel chunks
                    out over helper threads (transfer.py
                    ``_ChunkedStream._store_fanned``).
    """

    probe: bool
    presketch: bool
    concurrent_insert: bool


NO_CAPABILITIES = IngestCapabilities(probe=False, presketch=False,
                                     concurrent_insert=False)


@runtime_checkable
class IngestBackend(Protocol):
    """The batched-stage surface writers consume (transfer.py
    ``_flush_hashes``, pipeline.py's batch committer)."""

    @property
    def capabilities(self) -> IngestCapabilities: ...

    def probe_batch(self, digests: "list[bytes]") -> "list[bool] | None": ...

    def presketch_batch(self, digests: "list[bytes]", chunks: "list",
                        known: "list[bool] | None") -> int: ...


class StoreIngestBackend:
    """Adapter over a store that declares ``ingest_capabilities()``.

    ``capabilities`` re-asks the store on every read: index and
    similarity attachments can change after store construction (the
    server's per-job chunker-override store shares the primary's
    similarity index via the ``similarity`` setter), and the answer is
    two attribute checks."""

    __slots__ = ("_store",)

    def __init__(self, store):
        self._store = store

    @property
    def capabilities(self) -> IngestCapabilities:
        return self._store.ingest_capabilities()

    def probe_batch(self, digests: "list[bytes]") -> "list[bool] | None":
        return self._store.probe_batch(digests)

    def presketch_batch(self, digests: "list[bytes]", chunks: "list",
                        known: "list[bool] | None") -> int:
        return self._store.presketch_batch(digests, chunks, known)


class InlineIngestBackend:
    """The declared index-less fallback: no batched stage exists, so
    every capability is statically False and the batched entry points
    refuse loudly (writers must branch on ``capabilities`` first —
    reaching a method anyway is a caller bug, not a silent no-op)."""

    __slots__ = ("_store",)

    capabilities = NO_CAPABILITIES

    def __init__(self, store):
        self._store = store

    def probe_batch(self, digests):
        raise TypeError(
            f"{type(self._store).__name__} declares no batched probe "
            "capability — branch on backend.capabilities.probe")

    def presketch_batch(self, digests, chunks, known):
        raise TypeError(
            f"{type(self._store).__name__} declares no presketch "
            "capability — branch on backend.capabilities.presketch")


def resolve_ingest_backend(store) -> IngestBackend:
    """Resolve a store's declared ingest capabilities into a typed
    backend (one declaration lookup, at stream open)."""
    decl = getattr(store, "ingest_capabilities", None)
    if callable(decl):
        return StoreIngestBackend(store)
    return InlineIngestBackend(store)
