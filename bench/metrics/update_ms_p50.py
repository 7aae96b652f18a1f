"""update_ms_p50: median wall time of the window's batches, each from the
call into StreamSession.apply until its ranks are ready."""
import statistics


def read(run):
    walls = [b["wall_s"] for b in run.batches]
    return statistics.median(walls) * 1e3 if walls else None
