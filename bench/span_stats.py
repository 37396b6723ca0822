"""The stats the program puts on its own host spans (a
``jax.profiler.TraceAnnotation(name, key=value)`` lands as stat ``key`` on
its event), which ``trace.load`` does not keep: read again from the traced
file."""

from __future__ import annotations

from pathlib import Path

from bench import trace as tr


def read(path: str | Path, names) -> dict:
    """Name -> sorted (start, end, stats) of the host events with that
    name, for each of ``names``."""
    from jax.profiler import ProfileData
    names = set(names)
    out: dict = {n: [] for n in names}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out[ev.name].append((ev.start_ns,
                                         ev.start_ns + ev.duration_ns,
                                         dict(ev.stats)))
    return {n: sorted(v, key=lambda x: x[:2]) for n, v in out.items()}


def for_run(run, name: str) -> list | None:
    """The spans ``name`` of the run's traced file, found as
    ``harness.measure`` finds it (the newest ``.xplane.pb`` under
    ``harness.TRACE_DIR``); None unless that file's ``SPAN_STEP`` window is
    the run's own ``(lo, hi)``."""
    from bench import harness
    files = sorted(harness.TRACE_DIR.glob("**/*.xplane.pb"))
    if not files:
        return None
    spans = read(files[-1], (tr.SPAN_STEP, name))
    steps = spans[tr.SPAN_STEP]
    if not steps or (steps[0][0], max(e for _, e, _ in steps)) != (run.lo,
                                                                   run.hi):
        return None
    return spans[name]
