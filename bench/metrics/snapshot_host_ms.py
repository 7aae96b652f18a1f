"""snapshot_host_ms: mean host time of snapshot maintenance per window batch
(BatchStats.snapshot.host_s)."""


def read(run):
    b = run.batches
    return 1e3 * sum(x["snapshot_host_s"] for x in b) / len(b) if b else None
