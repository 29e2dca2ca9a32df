"""Mean duration of the ``ingest.sha`` spans that began inside the
window: how long a stream writer waits for a batch of digests.
Layer: stream writer.  Source: the program's own spans."""


def read(window):
    spans = window.sha_spans
    return 1000.0 * sum(spans) / len(spans) if spans else None
