"""Device idle time while the host dispatches the step, per traced step
in steady state, in ms, on chip 0, with the device's events moved onto
the host clock (``bench/clock.py``): for each of the program's
``train.dispatch`` spans but the window's first, the idle from its start
to the start of the step program it launched, over the spans read.  That
program is the first module run after the span's start named
``jit_<f>``, where ``PjitFunction(<f>)`` is the call inside the span, on
the span's own thread.  The first traced dispatch is left out: it carries
the window's start (a memory defragmentation after set-up, tens of ms on
a TPU v5e), which is no cost of a dispatch.  Nothing to read where the
program has no such span."""

import bisect

from bench import clock, trace as tr

SPAN = "train.dispatch"
CALL = "PjitFunction("


def read(run):
    dispatches = sorted((s, e, line) for s, e, name, _, line
                        in run.trace.host
                        if name == SPAN and run.lo <= s and e <= run.hi)[1:]
    if not run.steps or not run.devices or not dispatches:
        return None
    dev = clock.aligned(run.trace, run.devices[0])
    if dev is None:
        return None
    calls = [(s, e, line, name[len(CALL):-1]) for s, e, name, _, line
             in run.trace.host if name.startswith(CALL)]
    modules = sorted(dev.modules)
    starts = [m[0] for m in modules]
    idle, found = 0.0, 0
    for s, e, line in dispatches:
        fn = next((f for cs, ce, cl, f in calls
                   if cl == line and s <= cs and ce <= e), None)
        if fn is None:
            continue
        start = next((m[0] for m in modules[bisect.bisect_left(starts, s):]
                      if m[2].split("(", 1)[0] == f"jit_{fn}"), None)
        if start is None:
            continue
        busy = tr.union([o for o in dev.ops if o[0] < start and o[1] > s],
                        s, start)
        idle += start - s - sum(b - a for a, b in busy)
        found += 1
    return idle / found / 1e6 if found else None
