"""The trace reduction: busy union, idle share, scope matching, nesting,
exposed collective time and gap labels, on synthetic traces, and the
xplane reader on a small recorded one."""

import bm_helpers  # noqa: F401  (puts the repo on sys.path)
import pytest

from benchmark import trace
from benchmark.trace import Op, Span


def test_union_merges_overlaps_and_touching():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]


def test_busy_and_idle_share_are_clipped_to_the_span():
    ops = [Op(-1, 1, "a", ""), Op(2, 3, "b", ""), Op(2.5, 6, "c", ""),
           Op(9, 12, "d", "")]
    assert trace.length(trace.busy(ops, 0, 10)) == pytest.approx(6.0)
    assert trace.gaps(ops, 0, 10) == [(1, 2), (6, 9)]


def test_scope_matches_whole_path_elements_only():
    sampled = Op(0, 1, "fusion.1", "swiftly/fwd.sampled_facet_pass")
    plain = Op(1, 2, "fusion.2", "swiftly/fwd.facet_pass")
    assert trace.in_scopes(sampled, ["fwd.sampled_facet_pass"])
    assert not trace.in_scopes(sampled, ["fwd.facet_pass"])
    assert trace.in_scopes(plain, ["fwd.facet_pass"])
    assert trace.stage_of(sampled) == "fwd.sampled_facet_pass"
    assert trace.stage_of(Op(0, 1, "copy.1", "")) == ""


def test_scope_seconds_counts_nested_ops_once_and_skips_collectives():
    s = "swiftly/fwd.column_pass"
    ops = [Op(0, 10, "while.3", s), Op(1, 3, "fusion.1", s),
           Op(4, 9, "fusion.2", s), Op(10, 12, "psum.1", s),
           Op(12, 13, "fusion.7", "swiftly/bwd.sampled_fold")]
    assert trace.scope_seconds(ops, ["fwd.column_pass"], 0, 20) == 10
    assert trace.scope_seconds(ops, ["bwd.sampled_fold"], 0, 20) == 1


def test_self_seconds_and_top_ops_subtract_nested_time():
    s = "swiftly/bwd.sampled_fold"
    ops = [Op(0, 10, "while.3", s), Op(1, 3, "fusion.1", s),
           Op(4, 9, "fusion.12", s), Op(11, 12, "copy.4", "")]
    own = {o.name: t for o, t in trace.self_seconds(ops, 0, 20)}
    assert own == {"while.3": 3, "fusion.1": 2, "fusion.12": 5, "copy.4": 1}
    top = trace.top_ops({0: ops, 1: ops}, 0, 20)
    assert top[0] == ["bwd.sampled_fold:fusion", 7.0]
    assert ["-:copy", 1.0] in top


def test_exposed_collective_counts_only_time_with_nothing_else():
    s = "swiftly/fwd.column_pass"
    ops = [
        Op(0, 10, "while.3", s),          # a loop holding a collective
        Op(1, 3, "fusion.1", s),
        Op(3, 5, "psum.1", s),            # exposed 2 s ...
        Op(3.5, 4, "fusion.9", s),        # ... its own body op
        Op(6, 9, "fusion.2", s),
        Op(12, 13, "all-reduce.1", s),    # half hidden by fusion.5
        Op(12.5, 14, "fusion.5", s),
    ]
    assert trace.exposed_collective(ops, 0, 20) == pytest.approx(2.5)
    assert trace.is_collective(Op(0, 1, "all-reduce-done.2", ""))
    assert not trace.is_collective(Op(0, 1, "fusion.3", "swiftly/psum"))


def test_gap_labels_prefer_the_innermost_covering_span():
    host = [Span(0, 10, "bench/fwd_group"), Span(2, 4, "bench/sync"),
            Span(6, 7, "np.asarray")]
    assert trace.label_gap((2.5, 3.5), host) == "bench/sync"
    assert trace.label_gap((5, 6), host) == "bench/fwd_group"
    assert trace.label_gap((11, 12), host) == "host-idle"
    ops = {0: [Op(0, 2, "a", ""), Op(5, 6, "b", ""), Op(8, 10, "c", "")]}
    gaps = trace.idle_gaps(ops, host, 0, 10)
    # (2, 5): 3 s under fwd_group, only 2 s under sync
    assert gaps == [["bench/fwd_group", 3], ["bench/fwd_group", 2]]


def test_op_name_is_the_instruction_name():
    text = ("%fusion.43 = (f32[1,11264,768]{1,2,0}) fusion(f32[11264,768] "
            "%fusion.46), kind=kOutput, calls=%fused_computation.118")
    assert trace.op_name(text) == "fusion.43"


def _pb(*fields):
    """A serialized protobuf message of ``(number, value)`` fields: an
    int as a varint, bytes or str as length-delimited."""
    def varint(v):
        out = bytearray()
        while True:
            out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
            v >>= 7
            if not v:
                return bytes(out)

    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def _instr(name, op_name=None, calls=()):
    fields = [(1, name)]
    if op_name is not None:
        fields.append((7, _pb((2, op_name))))
    fields += [(38, c) for c in calls]
    return _pb(*fields)


def _hlo(*computations):
    """An ``xla.HloProto`` of ``(id, [instruction])`` computations."""
    comps = [_pb((5, cid), *[(2, i) for i in instrs])
             for cid, instrs in computations]
    return _pb((1, _pb((1, "jit_f"), *[(3, c) for c in comps])))


def test_instruction_stages_credit_each_instruction_its_own_scope():
    hlo = _hlo(
        (7, [_instr("add.0", "jit(f)/swiftly/bwd.x/add")]),
        (9, [_instr("fusion.1", calls=[7]),
             _instr("dot.3", "jit(f)/swiftly/fwd.y/swiftly/fwd.z/dot"),
             _instr("copy.2")]),
    )
    stages = trace.instruction_stages(hlo)
    assert stages["fusion.1"] == "swiftly/bwd.x"   # from what it calls
    assert stages["dot.3"] == "swiftly/fwd.z"      # the innermost scope
    assert stages["copy.2"] == ""                  # two stages: no guess
    one = trace.instruction_stages(_hlo(
        (1, [_instr("dot.1", "jit(g)/swiftly/fwd.y/dot"), _instr("copy.2")])))
    assert one == {"dot.1": "swiftly/fwd.y", "copy.2": "swiftly/fwd.y"}


def test_readers_credit_device_time_by_operation():
    """Two stages in one program read apart: the facet pass, the column
    step of the facet-slab plan and the fold each get their own time."""
    from types import SimpleNamespace

    from benchmark import counts, harness
    from benchmark.reading import Reading

    ops = [Op(0, 1, "fusion.1", "swiftly/fwd.sampled_facet_pass"),
           Op(1, 3, "colpass_pallas.2", "swiftly/fwd.slab_step"),
           Op(3, 4, "fusion.3", "swiftly/bwd.column_pass"),
           Op(4, 7, "fusion.4", "swiftly/bwd.sampled_fold"),
           Op(7, 8, "copy.5", "")]
    config = dict(N=1024, yB_size=352, yN_size=512, xA_size=448,
                  xM_size=512, W=11.0)
    op = SimpleNamespace(devices=[SimpleNamespace(id=0)],
                         facet_configs=[None] * 9, col_offs=[0, 1, 2],
                         per_column=3)
    peak = harness.peak_of("TPU v5 lite")
    reading = Reading({0: ops}, [Span(0, 8, "bench/traced_span")], config,
                      peak, op, {"fwd_columns": 3, "bwd_columns": 3}, 1, 2)
    g = counts.geometry(config, 9, 3, 3)

    def share(stages, seconds):
        flops = sum(counts.STAGES[s](g)[0] * 3 for s in stages)
        nbytes = sum(counts.STAGES[s](g)[1] * 3 for s in stages)
        return 100 * counts.least_seconds(flops, nbytes, peak)[0] / seconds

    readers = harness.resolve(harness.load_spec(), "roundtrip-32k")["readers"]
    assert readers["facet_pass_roofline"](reading) == pytest.approx(
        share(["fwd_facet_pass"], 1))
    assert readers["colpass_roofline"](reading) == pytest.approx(
        share(["fwd_column_pass", "bwd_column_pass"], 3))
    assert readers["fold_roofline"](reading) == pytest.approx(
        share(["bwd_fold"], 3))
    assert reading.busy_s() == 8


def test_reads_a_recorded_trace(tmp_path):
    """A small trace recorded here: the host spans come back on one
    clock, and the metadata plane maps each instruction of a program to
    its own scope."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def two_stage_program(x):
        with jax.named_scope("swiftly/fwd.test_stage"):
            y = jnp.cos(x) @ x
        with jax.named_scope("swiftly/bwd.test_stage"):
            return jnp.sin(y) @ y

    x = jnp.ones((64, 64), jnp.float32)
    two_stage_program(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/traced_span"):
        with jax.profiler.TraceAnnotation("bench/fwd_group"):
            two_stage_program(x + 1).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_profile(str(tmp_path))
    devices, host = trace.read_xplane(path)
    lo, hi = trace.span_of(host, "bench/traced_span")
    inner = trace.span_of(host, "bench/fwd_group")
    assert lo <= inner[0] <= inner[1] <= hi
    xp = trace._xplane_pb2()
    space = xp.XSpace()
    space.ParseFromString(open(path, "rb").read())
    program = next(v for k, v in trace.program_scopes(space).items()
                   if k.startswith("jit_two_stage_program("))
    assert {"swiftly/fwd.test_stage", "swiftly/bwd.test_stage"} <= set(
        program.values())
    assert devices == {}  # the CPU has no TPU plane
