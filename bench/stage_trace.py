"""Where a traced sweep unit's device time goes, stage by stage, and what
the event loop's iteration counts say.

The program names its event loop's stages with ``jax.named_scope``
(``engine.next_event``, ``engine.finalize``, ``engine.admit``,
``engine.faults``, ``engine.dispatch``, ``engine.map``,
``engine.start``), each heuristic's loop with ``sweep.<NAME>``, and its
host work with ``TraceAnnotation`` spans (``sweep.build``,
``sweep.trace.<NAME>``). A scope lands in the ``op_name`` metadata of
every HLO op traced inside it. On a TPU the profiler keeps it as the
``tf_op`` stat of each op's *event metadata* on the device plane, which
``jax.profiler.ProfileData`` does not show, so :func:`op_names` reads it
from the ``.xplane.pb`` itself. A fusion carries its root's ``op_name``,
so a fusion that spans two stages counts toward one.

Device time is counted over *leaf* ops only: events that contain no
other event on their line. A ``while`` event spans its whole loop, body
included, so counting it beside its body's fusions would count the same
time twice.

A program without the scopes, spans or the ``steps`` output reads
nothing here: each function then returns None, never 0.
"""
from __future__ import annotations

import dataclasses
import functools

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OP_NAME_STAT = "tf_op"
SCOPE = "engine."
BUILD_SPAN = "sweep.build"
TRACE_SPAN = "sweep.trace."
# the name the program gives the sweep's jitted function (runner.run_all)
SWEEP_PROGRAM = "run_all"


@dataclasses.dataclass(eq=False)  # hashed by identity, for the cache below
class Profile:
    # device plane -> [(op name, start, end, op_name metadata or "")]
    ops: dict
    # device plane -> [(program name, start, end)]
    modules: dict
    # every host event: [(name, start, end)]
    host: list


# The fields of ``tsl/profiler/protobuf/xplane.proto`` read here, by their
# numbers there; the parser skips every other field. A map is a repeated
# entry message (key 1, value 2) on the wire.
_XPLANE = (
    ("XStat", (("metadata_id", 1, "int64"), ("str_value", 5, "string"),
               ("ref_value", 7, "uint64"))),
    ("XStatMetadata", (("name", 2, "string"),)),
    ("XEventMetadata", (("name", 2, "string"), ("stats", 5, "XStat*"))),
    ("EventMetadataEntry", (("value", 2, "XEventMetadata"),)),
    ("StatMetadataEntry", (("key", 1, "int64"),
                           ("value", 2, "XStatMetadata"))),
    ("XPlane", (("name", 2, "string"),
                ("event_metadata", 4, "EventMetadataEntry*"),
                ("stat_metadata", 5, "StatMetadataEntry*"))),
    ("XSpace", (("planes", 1, "XPlane*"),)),
)


@functools.cache
def xspace_class():
    """A message class that parses an ``.xplane.pb`` into its planes' event
    and stat metadata, which ``jax.profiler.ProfileData`` does not show."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    for name, fields in _XPLANE:
        m = fdp.message_type.add(name=name)
        for fname, number, kind in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if kind.endswith("*")
                            else F.LABEL_OPTIONAL)
            kind = kind.rstrip("*")
            if kind in ("int64", "uint64", "string"):
                f.type = getattr(F, f"TYPE_{kind.upper()}")
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_names(path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    """device plane -> {op event name: its ``tf_op`` (``op_name``) stat},
    read from the planes' event metadata."""
    with open(path, "rb") as f:
        space = xspace_class().FromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(device_prefix):
            continue
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        names = out.setdefault(plane.name, {})
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if stat_name.get(st.metadata_id) == OP_NAME_STAT:
                    names[entry.value.name] = (
                        st.str_value or stat_name.get(st.ref_value, ""))
    return out


def load(path: str, device_prefix: str = DEVICE_PREFIX,
         ops_line: str = OPS_LINE, modules_line: str = MODULES_LINE
         ) -> Profile:
    """Ops with their ``op_name``, programs and host spans of an
    ``.xplane.pb``: events read with ``jax.profiler.ProfileData``, op names
    with :func:`op_names`. Times are nanoseconds on the profiler's
    clock."""
    from jax.profiler import ProfileData

    named = op_names(path, device_prefix)
    ops, modules, host = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(device_prefix):
            names = named.get(plane.name, {})
            o = ops.setdefault(plane.name, [])
            m = modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == ops_line:
                    o.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                              names.get(e.name, "")) for e in line.events)
                elif line.name == modules_line:
                    m.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events)
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events)
    return Profile(ops=ops, modules=modules, host=host)


def leaves(events) -> list:
    """The events of one line that contain no other event. Events on a
    line nest, so after sorting by (start, longest first) an event holds
    another exactly when it holds the next one. Neighbours whose clock
    stamps overlap by a little, without one holding the other, are both
    leaves."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= ev[2] or nxt[2] > ev[2]]


def stage_of(op_name: str):
    """The innermost ``engine.*`` scope of an ``op_name``, without its
    prefix, or None outside every stage."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE):
            return part[len(SCOPE):].split(":")[0]  # tf_op is "name:type"
    return None


@functools.lru_cache(maxsize=4)  # one reduction for all stage readers
def stage_seconds(p: Profile, lo: float, hi: float, program: str):
    """stage -> device seconds of the leaf ops that ran in [lo, hi] inside
    a program whose name contains ``program``, averaged over devices. Key
    None holds the leaf ops under no stage scope. None where no leaf op
    there carries a stage (a program without the scopes)."""
    acc = {}
    for d, evs in p.ops.items():
        spans = [(s, e) for n, s, e in p.modules.get(d, [])
                 if program in n and s < hi and e > lo]
        for _, s, e, op in leaves(evs):
            mid = (s + e) / 2
            if lo <= mid < hi and any(a <= mid < b for a, b in spans):
                k = stage_of(op)
                acc[k] = acc.get(k, 0) + (e - s)
    if not any(k is not None for k in acc):
        return None
    nd = max(len(p.ops), 1)
    return {k: ns / nd / 1e9 for k, ns in acc.items()}


def host_spans(p: Profile, prefix: str, lo: float, hi: float) -> list:
    """Host spans whose name starts with ``prefix`` and that start in
    [lo, hi], as [(name, start, end)]."""
    return [ev for ev in p.host if ev[0].startswith(prefix)
            and lo <= ev[1] < hi]


def steps(outputs) -> list:
    """Per window unit, the ``(H, B)`` loop iterations per lane from the
    program's outputs (``[(traces, metrics dict)]``); None where the
    program does not return them."""
    if not outputs or "steps" not in outputs[0][1]:
        return None
    return [m["steps"] for _, m in outputs]


def loop_iters(outputs):
    """Iterations the device ran per unit: the sum over heuristics of the
    slowest lane's count (a vmapped loop runs until its last lane ends),
    as a mean over the window's units."""
    per = steps(outputs)
    if per is None:
        return None
    return sum(int(s.max(-1).sum()) for s in per) / len(per)


def idle_lane_share(outputs):
    """Share of lane iterations in which a lane had already ended and the
    vmapped loop ran on for the others, over the window's units."""
    per = steps(outputs)
    if per is None:
        return None
    ran = sum(int(s.max(-1).sum()) * s.shape[-1] for s in per)
    used = sum(int(s.sum()) for s in per)
    return 1.0 - used / ran if ran else None


# What the per-layer readers (``bench/metrics/``) read. Besides the fields
# ``bench/metrics/__init__.py`` lists, they look for ``profile`` (a
# :class:`Profile` of the traced unit), ``window`` ((start, end) of its
# ``bench_unit`` span) and ``outputs`` (the window's units as host arrays,
# ``[(traces, metrics dict)]``); a reading without them reads nothing.

def _traced(r):
    p, w = getattr(r, "profile", None), getattr(r, "window", None)
    if p is None or w is None or not (r.traced or {}).get("whole"):
        return None
    return p, w


def read_stages(r):
    """stage -> device seconds in the traced unit (key None: unscoped),
    or None."""
    t = _traced(r)
    return None if t is None else stage_seconds(t[0], *t[1], SWEEP_PROGRAM)


def read_stage(r, stage: str):
    s = read_stages(r)
    return None if s is None else s.get(stage, 0.0)


def read_spans(r, prefix: str):
    """Host spans of the traced unit whose name starts with ``prefix``, or
    None where the program writes no ``sweep.build`` span there."""
    t = _traced(r)
    if t is None:
        return None
    p, (lo, hi) = t
    if not host_spans(p, BUILD_SPAN, lo, hi):
        return None
    return host_spans(p, prefix, lo, hi)
