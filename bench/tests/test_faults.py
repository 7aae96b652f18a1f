"""A run with the timed path broken underneath has to come out not correct.

Each test drives the whole harness at the configuration's rehearsal size on
the CPU (``rehearse=True`` skips the look for a chip), with one fault
planted in the session, and checks the verdict. One sound run per cell
checks the other side.
"""
import pytest

from bench.harness import run_cell

from repro.stream.session import StreamSession

STREAM_CELLS = ["rmat.dfp_random"]
ALL_CELLS = STREAM_CELLS + ["rmat.static"]


def _run(cell):
    return run_cell(cell, seed=2**32 + 5, seconds=1.0, trace=False,
                    rehearse=True, log=lambda s: None)


def _half(batch):
    """The batch with the second half of each list left out (rounded
    up, so that a one-edge batch loses its edge)."""
    def h(a):
        return a[:a.size // 2]
    return type(batch)(h(batch.del_src), h(batch.del_dst),
                       h(batch.ins_src), h(batch.ins_dst))


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    c = res["checks"]["l1_gap_max"]
    assert c["value"] <= c["limit"]
    assert list(res)[-1] == "checks"
    assert set(res["window_programs"]) == {"lowered", "compiled",
                                           "compile_s"}


@pytest.mark.parametrize("cell", STREAM_CELLS)
def test_state_left_unchanged_is_caught(cell, monkeypatch):
    orig = StreamSession.apply

    def stale(self, batch):
        before = self.ranks
        orig(self, batch)
        self.ranks = before
        return before
    monkeypatch.setattr(StreamSession, "apply", stale)
    assert _run(cell)["correct"] is False


@pytest.mark.parametrize("cell", STREAM_CELLS)
def test_half_batch_left_out_is_caught(cell, monkeypatch):
    orig = StreamSession.apply
    monkeypatch.setattr(StreamSession, "apply",
                        lambda self, batch: orig(self, _half(batch)))
    assert _run(cell)["correct"] is False


@pytest.mark.parametrize("cell", STREAM_CELLS)
def test_altered_answer_is_caught(cell, monkeypatch):
    orig = StreamSession.apply

    def altered(self, batch):
        self.ranks = orig(self, batch).at[0].multiply(2.0)
        return self.ranks
    monkeypatch.setattr(StreamSession, "apply", altered)
    assert _run(cell)["correct"] is False


def test_static_altered_answer_is_caught(monkeypatch):
    orig = StreamSession.recompute
    monkeypatch.setattr(StreamSession, "recompute",
                        lambda self: orig(self).at[0].multiply(2.0))
    assert _run("rmat.static")["correct"] is False


def test_static_half_the_sweeps_is_caught(monkeypatch):
    orig = StreamSession.recompute

    def half_sweeps(self):
        orig(self)
        iters = self.history[-1].iters
        params = self.params
        self.params = params._replace(max_iter=max(iters // 2, 1))
        try:
            return orig(self)
        finally:
            self.params = params
    monkeypatch.setattr(StreamSession, "recompute", half_sweeps)
    assert _run("rmat.static")["correct"] is False
