"""ingest_ms: mean host time of ingest and validation per window batch
(BatchStats.ingest_s)."""


def read(run):
    b = run.batches
    return 1e3 * sum(x["ingest_s"] for x in b) / len(b) if b else None
