"""sweeps_per_batch: mean DF-P sweeps per window batch (BatchStats.iters)."""


def read(run):
    b = run.batches
    return sum(x["iters"] for x in b) / len(b) if b else None
