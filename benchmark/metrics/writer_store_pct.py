"""Share of the backup writer threads' lives spent storing chunks:
100 x sum ``writer_store_s`` / sum ``writer_life_s`` over the job
records of the window's jobs.  The state brackets the insert loop of a
hash batch, the block of the ``ingest.store`` span in
``_ChunkedStream._flush_hashes``: compression, the chunk file's write
and the index insert of every new chunk, the touch of every known one —
on the writer's thread (ROADMAP S8 2).
Layer: stream writer.  Source: the job's ``backup.pump`` span."""

from benchmark.harness.jobclocks import share_pct


def read(window):
    return share_pct(window, "writer_store_s", "writer_life_s")
