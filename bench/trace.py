"""Reduction from a profiler trace (``.xplane.pb``) to per-layer numbers.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with the lines
``XLA Modules`` (one event per program run, with a ``run_id`` stat) and
``XLA Ops`` (one event per operation), and a host plane (``/host:CPU``)
whose lines hold the host's TraceMe spans, among them the harness's own
(``SPAN_STEP`` around every timed step, ``SPAN_BACKUP`` around the
backup flush) and the runtime's launch events, which carry the ``run_id``
of the program they launch.  All times are in the profiler's clock, in
nanoseconds.

The traced window runs from the start of the first ``SPAN_STEP`` to the
end of the last one.  Nothing here looks at devices or topology when it is
imported.
"""

from __future__ import annotations

import bisect
import gzip
import re
from dataclasses import dataclass, field
from pathlib import Path

SPAN_STEP = "bench.step"
SPAN_BACKUP = "bench.backup_flush"
# host events that launch a program on a device, with its run id; only
# those on the main thread, which runs the harness and the flush (the
# runtime's own queue thread enqueues deferred launches at any time)
LAUNCH = "DoEnqueueProgram"
MAIN_LINE = "main/"
# the host line that holds Python-level spans (ours, ``PjitFunction(..)``)
PYTHON_LINE = "python"

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def profile_options():
    """No Python-call tracing (it would swamp the host plane and slow the
    host); host TraceMe spans at the default level, and no HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


@dataclass
class Device:
    index: int
    ops: list = field(default_factory=list)       # (start, end, short name)
    modules: list = field(default_factory=list)   # (start, end, name, run_id)


@dataclass
class Trace:
    devices: dict                                  # index -> Device
    host: list                                     # (start, end, name, run_id, line)

    def spans(self, name: str) -> list:
        return sorted((s, e) for s, e, n, _, _ in self.host if n == name)

    def window(self) -> tuple[int, int] | None:
        steps = self.spans(SPAN_STEP)
        if not steps:
            return None
        return steps[0][0], max(e for _, e in steps)


def op_name(full: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return full.split(" = ", 1)[0].lstrip("%")


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str | Path) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device(int(m.group(1))))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 op_name(ev.name)) for ev in line.events]
                elif line.name == "XLA Modules":
                    dev.modules += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name, _stat(ev, "run_id"))
                                    for ev in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                          _stat(ev, "run_id"), line.name)
                         for ev in line.events]
    return Trace(devices, host)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end, ...)`` intervals clipped to [lo, hi],
    as sorted disjoint (start, end) pairs."""
    out: list[list[float]] = []
    for iv in sorted((max(i[0], lo), min(i[1], hi)) for i in intervals):
        if iv[1] <= iv[0]:
            continue
        if out and iv[0] <= out[-1][1]:
            out[-1][1] = max(out[-1][1], iv[1])
        else:
            out.append(list(iv))
    return [(a, b) for a, b in out]


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran on ``dev``."""
    return sum(b - a for a, b in union(dev.ops, lo, hi))


def span_device_ns(trace: Trace, span: str, dev: Device) -> float:
    """Device time of the program runs that the host launched inside the
    spans named ``span``: launch events in a span give run ids, and the
    device's module events with those run ids give the time."""
    spans = trace.spans(span)
    if not spans:
        return 0.0
    starts = [s for s, _ in spans]
    run_ids = set()
    for s, e, name, rid, line in trace.host:
        if name != LAUNCH or rid is None or not line.startswith(MAIN_LINE):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1] and e <= spans[i][1]:
            run_ids.add(rid)
    return float(sum(e - s for s, e, _, rid in dev.modules if rid in run_ids))



def top_ops(dev: Device, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` operations that took most device time in [lo, hi]."""
    total: dict[str, float] = {}
    for s, e, name in dev.ops:
        if e > lo and s < hi:
            total[name] = total.get(name, 0.0) + min(e, hi) - max(s, lo)
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(trace: Trace, dev: Device, lo: float, hi: float,
              n: int = 10) -> list:
    """Idle time of ``dev`` in [lo, hi], summed by what the host was doing:
    each gap goes to the shortest Python-level host span that covers its
    middle (other than the step span itself), or to ``runtime`` where none
    does."""
    busy = union(dev.ops, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted((s, e, name) for s, e, name, _, line in trace.host
                  if line == PYTHON_LINE and name != SPAN_STEP)
    total: dict[str, float] = {}
    starts = [h[0] for h in host]
    longest = max((e - s for s, e, _ in host), default=0)
    for a, b in gaps:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid)
        label, width = "runtime", None
        for s, e, name in reversed(host[max(0, i - 4096):i]):
            if s < mid - longest:
                break
            if e >= mid and (width is None or e - s < width):
                label, width = name, e - s
        total[label] = total.get(label, 0.0) + (b - a)
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]
