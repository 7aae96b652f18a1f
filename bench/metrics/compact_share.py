"""compact_share: percent of window batches the session ran on its compact
engine (BatchStats.engine)."""


def read(run):
    b = run.batches
    return 100.0 * sum(x["engine"] == "compact" for x in b) / len(b) \
        if b else None
