"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` only. A device is a plane whose
name starts with ``/device:TPU:`` (on the CPU, in the tests, any prefix
the caller names); on it, the ``XLA Ops`` line holds one event per
operation run and the ``XLA Modules`` line one event per program run.
Host planes hold the harness's ``TraceAnnotation`` spans, on the same
clock. All times are nanoseconds from the start of the trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DROPPED = "Trace Buffers Dropped"


@dataclasses.dataclass
class Trace:
    # device plane name -> line name -> [(event name, start, end)]
    devices: dict
    # every host event: [(name, start, end)]
    host: list


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler.trace`` directory."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, device_prefix: str = DEVICE_PREFIX) -> Trace:
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        is_dev = plane.name.startswith(device_prefix)
        is_host = plane.name.startswith("/host:")
        if not (is_dev or is_host):
            continue
        lines = {line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events] for line in plane.lines}
        if is_dev:
            devices[plane.name] = lines
        if is_host:
            host.extend(ev for evs in lines.values() for ev in evs)
    return Trace(devices=devices, host=host)


def merge(intervals, lo: float, hi: float) -> list:
    """The union of ``(start, end)`` intervals clipped to ``[lo, hi]``, as
    sorted disjoint intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def short_name(name: str) -> str:
    """An operation's name without its HLO text (``%while.5 = ...``)."""
    return name.split(" = ", 1)[0]


def line_events(trace: Trace, device: str, line: str) -> list:
    """Events of the device's lines whose name starts with ``line``."""
    return [ev for name, evs in trace.devices[device].items()
            if name.startswith(line) for ev in evs]


def busy(trace: Trace, lo: float, hi: float, line: str = OPS_LINE) -> dict:
    """device -> merged intervals in which an operation ran, in [lo, hi]."""
    return {d: merge([(s, e) for _, s, e in line_events(trace, d, line)],
                     lo, hi)
            for d in trace.devices}


def module_time(trace: Trace, match: str, lo: float, hi: float,
                line: str = MODULES_LINE) -> tuple:
    """(device -> ns of the programs whose name contains ``match``, in
    [lo, hi]; the sorted set of matched program names)."""
    names, out = set(), {}
    for d in trace.devices:
        evs = [(n, s, e) for n, s, e in line_events(trace, d, line)
               if match in n]
        names.update(n for n, _, _ in evs)
        out[d] = total(merge([(s, e) for _, s, e in evs], lo, hi))
    return out, sorted(names)


def dropped_from(trace: Trace, lo: float, hi: float):
    """Where, in [lo, hi], a device first dropped trace buffers (the TPU's
    ``Trace Buffers Dropped`` event), or None: after it the trace misses
    operations."""
    starts = [s for lines in trace.devices.values()
              for evs in lines.values() for n, s, e in evs
              if n == DROPPED and s < hi and e > lo]
    return max(min(starts), lo) if starts else None


def host_span(trace: Trace, name: str) -> tuple:
    """(start, end) of the first host event called ``name``."""
    spans = sorted((s, e) for n, s, e in trace.host if n == name)
    if not spans:
        raise KeyError(f"no host span {name!r} in the trace")
    return spans[0]


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10,
            line: str = OPS_LINE) -> list:
    """The ``k`` operation names with the most device time in [lo, hi],
    as [name, seconds averaged over the devices]."""
    acc = {}
    for d in trace.devices:
        for n, s, e in line_events(trace, d, line):
            dt = min(e, hi) - max(s, lo)
            if dt > 0:
                acc[short_name(n)] = acc.get(short_name(n), 0.0) + dt
    nd = max(len(trace.devices), 1)
    top = sorted(acc.items(), key=lambda x: -x[1])[:k]
    return [[n, ns / nd / 1e9] for n, ns in top]


def idle_gaps(merged, lo: float, hi: float, spans, k: int = 10) -> list:
    """The ``k`` longest idle gaps of one device in [lo, hi], each named by
    the host span (``spans``: [(name, start, end)]) that covers most of
    it, as [name, seconds]."""
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        cover = {}
        for n, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[n] = cover.get(n, 0.0) + ov
        name = max(cover, key=cover.get) if cover else "host_other"
        out.append([name, (e - s) / 1e9])
    return out
