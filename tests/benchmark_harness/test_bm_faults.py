"""The comparison that decides ``correct`` catches the faults each cell
can have: the timed path is broken underneath a run at the tiny size
and the run must come out not correct. The device stamp is left out,
everything else is the harness's run."""

import bm_helpers
import pytest


@pytest.fixture(autouse=True)
def _precision_restored(monkeypatch):
    monkeypatch.setenv("SWIFTLY_PRECISION", "highest")
    yield
    monkeypatch.undo()
    _clear_traced_programs()


def _state_unchanged(mp):
    """The backward's fold returns its accumulator unchanged."""
    from swiftly_tpu.parallel import StreamedBackward

    orig = StreamedBackward.add_subgrid_group

    def add(self, cols, group):
        orig(self, cols, group * 0)

    mp.setattr(StreamedBackward, "add_subgrid_group", add)


def _half_batch_to_backward(mp):
    """Half of each column group never reaches the backward."""
    from swiftly_tpu.parallel import StreamedBackward

    orig = StreamedBackward.add_subgrid_group

    def add(self, cols, group):
        keep = max(1, len(cols) // 2)
        orig(self, cols[:keep], group[:keep])

    mp.setattr(StreamedBackward, "add_subgrid_group", add)


def _wrap_groups(mp, change):
    from swiftly_tpu.parallel import StreamedForward

    orig = StreamedForward.stream_column_groups

    def stream(self, cover, spill=None):
        for per_col, group in orig(self, cover, spill=spill):
            yield per_col, change(group)

    mp.setattr(StreamedForward, "stream_column_groups", stream)


def _answer_altered(mp):
    """Every subgrid comes out 1e-4 too large where it is produced."""
    _wrap_groups(mp, lambda g: g * (1 + 1e-4))


def _half_batch_forward(mp):
    """The second half of each column group's subgrids is left out."""
    def drop(g):
        n = g.shape[0] // 2
        return g.at[n:].set(0) if n else g * 0

    _wrap_groups(mp, drop)


def _stale_groups(mp):
    """The stream hands out its first group again and again: the state
    of the stream never moves on (one column a group, so a cover has
    several)."""
    from swiftly_tpu.parallel import StreamedForward

    mp.setattr(StreamedForward, "_auto_col_group", lambda self, n: 1)
    first = {}

    def stale(g):
        first.setdefault(g.shape, g)
        return first[g.shape]

    _wrap_groups(mp, stale)


def _clear_traced_programs():
    """Forget the stage programs traced so far, so a patched body is
    traced anew, and is not handed to a later test."""
    from swiftly_tpu.parallel import streamed

    for name in dir(streamed):
        clear = getattr(getattr(streamed, name), "cache_clear", None)
        if clear is not None:
            clear()


def _exchange_left_out(mp):
    """The psum across chips is skipped: each chip keeps its partial."""
    from swiftly_tpu.parallel import streamed

    _clear_traced_programs()
    mp.setattr(streamed, "_collective_sum", lambda x, *a, **k: x)


CASES = [
    ("roundtrip-32k", _state_unchanged),
    ("roundtrip-32k", _half_batch_to_backward),
    ("roundtrip-32k", _answer_altered),
    ("forward-32k", _answer_altered),
    ("forward-32k", _half_batch_forward),
    ("forward-32k", _stale_groups),
    ("forward-64k-mesh4", _answer_altered),
    ("forward-64k-mesh4", _half_batch_forward),
    ("forward-64k-mesh4", _stale_groups),
    ("forward-64k-mesh4", _exchange_left_out),
]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__.strip('_')}"
                              for w, f in CASES])
def test_fault_makes_the_run_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    r = bm_helpers.run_tiny(workload, seed=2**31 + 101)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0
