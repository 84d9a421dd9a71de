"""The program's own stage spans on the profiler's clock, and the two
readers of the host stream that use them (`staging_idle_frac`,
`upload_idle_frac`): a recorded profile of the tiny round trip on the
facet-slab plan with every ``obs`` system off, and the readers on
synthetic traces."""

from types import SimpleNamespace

import bm_helpers
import pytest

from benchmark import drive, harness, trace
from benchmark.trace import Op, Span

READERS = harness.resolve(harness.load_spec(), "roundtrip-32k")["readers"]

# the stages of one round-trip pass on the facet-slab plan
PASS_SPANS = {"fwd.slab_upload", "fwd.drain", "fwd.slab_step",
              "fwd.sampled_facet_pass", "fwd.column_group",
              "bwd.sampled_fold"}


@pytest.fixture
def slab_pass(monkeypatch):
    """One tiny round trip set up and warmed on the facet-slab plan,
    with the metrics registry, span tracer and flight recorder off: a
    callable that runs one pass."""
    from swiftly_tpu.obs import metrics, recorder
    from swiftly_tpu.obs import trace as otrace
    from swiftly_tpu.parallel import StreamedForward

    for system in (metrics, otrace, recorder):
        system.disable()
        system.reset()
    monkeypatch.setattr(StreamedForward, "_facet_stack_fits",
                        lambda self: False)
    monkeypatch.setenv("SWIFTLY_PRECISION", "highest")
    res = bm_helpers.tiny_cell("roundtrip-32k")
    harness.configure(res["config"])
    op = drive.RoundTrip(res["config"], 1)
    op.load(2**31 + 5)
    op.build()
    op.warm()
    return lambda: op.one_pass(drive.Tracer(None, op.devices))


def test_program_spans_reach_a_recorded_profile(slab_pass, tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        slab_pass()
    finally:
        jax.profiler.stop_trace()
    _, host = trace.read_xplane(trace.find_profile(str(tmp_path)))
    names = {s.name for s in host}
    assert PASS_SPANS <= names, sorted(names)
    assert names & {"fwd.slab_wait", "fwd.slab_stage"}, sorted(names)


def test_the_same_pass_without_a_session_records_nothing(slab_pass,
                                                        monkeypatch):
    from swiftly_tpu.obs import metrics, recorder
    from swiftly_tpu.obs import trace as otrace

    made = []
    monkeypatch.setattr(otrace, "_ProfilerSpan",
                        lambda *args: made.append(args))
    slab_pass()
    assert made == []
    assert metrics.export()["stages"] == {}
    assert otrace.get_tracer().counts() == (0, 0)
    assert recorder.get_recorder().events() == []


def _reading(devices, host, lo=0.0, hi=10.0):
    return SimpleNamespace(devices=devices, host=host, lo=lo, hi=hi)


# device 0 idles in (2, 4), (6, 7) and (9, 10) of the span [0, 10)
BUSY = [Op(0, 2, "fusion.1", ""), Op(4, 6, "fusion.2", ""),
        Op(7, 9, "fusion.3", "")]
HOST = [
    Span(0, 10, "bench/traced_span"),
    Span(0, 10, "fwd.column_group"),  # holds them all: no reader's span
    Span(1.5, 3, "fwd.slab_wait"),    # idle (2, 3) inside ...
    Span(2.5, 3.5, "fwd.slab_stage"),  # ... and (3, 3.5): 1.5 s staging
    Span(5, 6.5, "fwd.slab_upload"),  # idle (6, 6.5) inside
    Span(9.5, 12, "fwd.drain"),       # partly past the span: (9.5, 10)
    Span(3.5, 4, "bench/bwd_add"),    # idle, but not the stream's
]


def test_idle_is_counted_inside_the_readers_spans_only():
    r = _reading({0: BUSY}, HOST)
    staging = READERS["staging_idle_frac"](r)
    upload = READERS["upload_idle_frac"](r)
    assert staging == pytest.approx(15.0)
    assert upload == pytest.approx(10.0)
    idle = trace.length(trace.gaps(BUSY, 0, 10))
    assert staging + upload <= 100 * idle / 10


def test_readers_average_over_the_chips():
    always_busy = [Op(0, 10, "fusion.9", "")]
    r = _reading({0: BUSY, 1: always_busy}, HOST)
    assert READERS["staging_idle_frac"](r) == pytest.approx(7.5)
    assert READERS["upload_idle_frac"](r) == pytest.approx(5.0)


def test_readers_find_nothing_without_a_device_plane_or_their_spans():
    for name in ("staging_idle_frac", "upload_idle_frac"):
        assert READERS[name](_reading({}, HOST)) is None
        # a program that opens no stream span: the resident plan, or a
        # program from before the spans reached the profile
        bench_only = [s for s in HOST if s.name.startswith("bench/")]
        assert READERS[name](_reading({0: BUSY}, bench_only)) is None
        # the spans lie wholly outside the traced span
        late = [Span(20, 30, s) for s in ("fwd.slab_wait", "fwd.drain")]
        assert READERS[name](_reading({0: BUSY}, late)) is None
