"""Reduction of a profiler trace to device busy time, stage time and
idle gaps.

`read_xplane` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a plain record: for each device, the operations that ran on it (start
and end in seconds, the operation's name, the ``named_scope`` path the
program gave it), and the host's annotated spans on the same clock.
Everything after that works on the plain record, so the tests check it
on synthetic traces.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict, namedtuple

Op = namedtuple("Op", "start end name scope")
Span = namedtuple("Span", "start end name")

# what XLA names the cross-device reductions and exchanges
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|ppermute",
    re.IGNORECASE,
)


def is_collective(op):
    return bool(COLLECTIVE.search(op.name))


def clip(intervals, lo, hi):
    """The intervals cut to ``[lo, hi)``, empty ones dropped."""
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, minus):
    """``intervals`` minus the points of ``minus`` (both unions)."""
    out = []
    for a, b in intervals:
        cur = a
        for c, d in minus:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def busy(ops, lo, hi):
    """Union of the intervals in which any operation ran, in
    ``[lo, hi)``."""
    return union(clip([(o.start, o.end) for o in ops], lo, hi))


def gaps(ops, lo, hi):
    """The idle intervals of ``[lo, hi)``."""
    return subtract([(lo, hi)], busy(ops, lo, hi))


def nesting(ops):
    """``[(op, has_children, under_collective)]``: whether each operation
    holds others (a loop holds its body's on the same line) and whether
    it runs inside a collective."""
    out, stack = [], []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        # pop what ended before o, and what o overruns (not nested)
        while stack and (stack[-1][0].end <= o.start
                         or stack[-1][0].end < o.end):
            out.append(tuple(stack.pop()))
        under = bool(stack) and (stack[-1][2] or is_collective(stack[-1][0]))
        if stack:
            stack[-1][1] = True
        stack.append([o, False, under])
    out.extend(tuple(x) for x in stack)
    return out


def exposed_collective(ops, lo, hi):
    """Seconds of ``[lo, hi)`` in which a collective ran on the device
    and no other operation did. An operation nested in a collective is
    part of it; a loop that holds a collective is not other work."""
    coll, other = [], []
    for o, parent, under in nesting(ops):
        if is_collective(o) or under:
            coll.append((o.start, o.end))
        elif not parent:
            other.append((o.start, o.end))
    return length(subtract(union(clip(coll, lo, hi)),
                           union(clip(other, lo, hi))))


def in_scopes(op, scopes):
    """Whether the operation's scope path names one of ``scopes`` (as a
    whole path element, so ``fwd.facet_pass`` does not match
    ``fwd.sampled_facet_pass``)."""
    parts = set(re.split(r"[/:]", op.scope))
    return any(s in parts for s in scopes)


def scope_seconds(ops, scopes, lo, hi):
    """Device seconds of the non-collective operations under ``scopes``
    in ``[lo, hi)``."""
    return length(union(clip(
        [(o.start, o.end) for o in ops
         if in_scopes(o, scopes) and not is_collective(o)], lo, hi)))


def stage_of(op):
    """The ``swiftly/<stage>`` element of an operation's scope path, or
    '' where it has none."""
    m = re.search(r"swiftly/([A-Za-z0-9_.]+)", op.scope)
    return m.group(1) if m else ""


def self_seconds(ops, lo, hi):
    """``[(op, seconds)]``: each operation's time in ``[lo, hi)`` less
    that of the operations nested in it (a loop holds its body's)."""
    out, stack = [], []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and (stack[-1][0].end <= o.start
                         or stack[-1][0].end < o.end):
            out.append(tuple(stack.pop()))
        own = length(clip([(o.start, o.end)], lo, hi))
        if stack:
            stack[-1][1] -= own
        stack.append([o, own])
    out.extend(tuple(x) for x in stack)
    return out


def top_ops(devices, lo, hi, n=10):
    """``[[name, seconds], ...]``: the operations that took most device
    time of their own, averaged over the devices, named
    ``<stage>:<instruction>`` with the instruction's number dropped."""
    total = defaultdict(float)
    for ops in devices.values():
        for o, s in self_seconds(ops, lo, hi):
            if s > 0:
                base = re.sub(r"\.\d+$", "", o.name)
                total[f"{stage_of(o) or '-'}:{base}"] += s / len(devices)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def label_gap(gap, host):
    """What the host was doing in an idle gap: the host span that
    overlaps it most, the innermost on a tie."""
    a, b = gap
    best, key = "host-idle", None
    for s in host:
        ov = min(b, s.end) - max(a, s.start)
        if ov <= 0:
            continue
        k = (ov, -(s.end - s.start))
        if key is None or k > key:
            best, key = s.name, k
    return best


def idle_gaps(devices, host, lo, hi, n=10):
    """``[[label, seconds], ...]``: the longest idle gaps of the first
    device, each labelled by what the host was doing."""
    if not devices:
        return []
    first = devices[min(devices)]
    found = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[label_gap(g, host), g[1] - g[0]] for g in found]


def find_profile(directory):
    """The newest ``.xplane.pb`` under ``directory``."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def _xplane_pb2():
    """The XSpace protobuf module of the installed TensorFlow profiler
    protos, loaded from its file alone (importing the ``tensorflow``
    package would start TensorFlow)."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("the trace reader needs tensorflow's xplane_pb2")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("_bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


_STAGE = re.compile(r"swiftly/([A-Za-z0-9_.]+)")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one serialized
    protobuf message: varints as ints, everything else as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not read here")
        yield num, value


def _ids(value):
    """A repeated integer field's values, packed or one at a time."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _stage(op_name_text):
    """The innermost ``swiftly/<stage>`` of an ``op_name``, or ''."""
    found = _STAGE.findall(op_name_text)
    return found[-1] if found else ""


def instruction_stages(hlo_proto):
    """``{instruction name: stage}`` for every instruction of one
    program, from its serialized ``xla.HloProto``.

    An instruction takes the innermost ``swiftly/<stage>`` of its own
    ``op_name``; one without (a fusion whose root carries none, a copy
    XLA added) takes the stage most of the instructions it calls carry;
    failing that, the program's stage where the program has one stage
    only, and '' otherwise. Field numbers are those of ``xla/hlo.proto``:
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2, id 5; HloInstructionProto.name 1,
    metadata 7, called_computation_ids 38; OpMetadata.op_name 2."""
    buf = memoryview(hlo_proto)
    module = next((v for n, v in _fields(buf) if n == 1), b"")
    instrs, by_comp = [], defaultdict(list)
    for n, comp in _fields(module):
        if n != 3:
            continue
        cid, members = None, []
        for cn, cv in _fields(comp):
            if cn == 5:
                cid = cv
            elif cn == 2:
                name, stage, calls = "", "", []
                for fn_, fv in _fields(cv):
                    if fn_ == 1:
                        name = bytes(fv).decode()
                    elif fn_ == 7:
                        for mn, mv in _fields(fv):
                            if mn == 2:
                                stage = _stage(bytes(mv).decode())
                    elif fn_ == 38:
                        calls += _ids(fv)
                members.append((name, stage, calls))
        instrs += members
        by_comp[cid] += [stage for _, stage, _ in members if stage]
    stages = {stage for _, stage, _ in instrs if stage}
    only = next(iter(stages)) if len(stages) == 1 else ""
    out = {}
    for name, stage, calls in instrs:
        if not stage:
            inner = [s for c in calls for s in by_comp.get(c, ())]
            stage = max(set(inner), key=inner.count) if inner else only
        out[name] = f"swiftly/{stage}" if stage else ""
    return out


def program_scopes(space):
    """``{program name: {instruction name: "swiftly/<stage>"}}`` from the
    HLO of every program in the profile's metadata plane."""
    out = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for em in plane.event_metadata.values():
            table = {}
            for st in em.stats:
                if st.bytes_value:
                    table.update(instruction_stages(st.bytes_value))
            out[em.name] = table
    return out


def op_name(text):
    """The instruction name of an operation event (``%fusion.43 = ...``
    gives ``fusion.43``)."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def read_xplane(path, device_line="XLA Ops", module_line="XLA Modules"):
    """``(devices, host)``: ``{device_id: [Op]}`` for every TPU plane of
    the profile, each operation with its instruction name and the scope
    that instruction carries in the HLO of the program it ran in, and
    every named host span; in seconds on the profile's one clock."""
    xp = _xplane_pb2()
    space = xp.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    scopes = program_scopes(space)
    devices, host = {}, []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}

        def events(line):
            t0 = line.timestamp_ns * 1e-9
            for ev in line.events:
                a = t0 + ev.offset_ps * 1e-12
                yield a, a + ev.duration_ps * 1e-12, names.get(
                    ev.metadata_id, "")

        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            modules = sorted(events(lines[module_line])) if (
                module_line in lines) else []
            starts = [a for a, _, _ in modules]
            ops = []
            for a, b, name in (events(lines[device_line])
                               if device_line in lines else ()):
                i = bisect.bisect_right(starts, a) - 1
                instr, scope = op_name(name), ""
                if i >= 0 and a < modules[i][1]:
                    scope = scopes.get(modules[i][2], {}).get(instr, "")
                ops.append(Op(a, b, instr, scope))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for a, b, name in events(line):
                    if b > a and not name.startswith("$"):
                        host.append(Span(a, b, name))
    return devices, host


def span_of(host, name):
    """``(start, end)`` of the host span ``name`` (the last one)."""
    found = [s for s in host if s.name == name]
    if not found:
        raise ValueError(f"no host span {name!r} in the trace")
    s = found[-1]
    return s.start, s.end
