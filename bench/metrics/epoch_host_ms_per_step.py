"""The host's own time inside the program's ``ownership.epoch`` spans per
traced step, in ms: the colour bump and the state's epoch hooks (among
them the backup flush's dispatches), as ``StateMutRef.drop`` runs them,
less the time the runtime held the host waiting for room to enqueue a
program.  That wait is the self time of each ``ExecutePrepare`` on the
main thread inside the spans (its time outside the events nested in it):
once the launch queue is full, the next launch waits there for the
device, and the epoch would otherwise read the device's step time.
Nothing to read where the program has no such span."""

import bisect

from bench import trace as tr

SPAN = "ownership.epoch"
PREPARE = "CommonPjRtLoadedExecutable::ExecutePrepare"


def waited_ns(trace: tr.Trace, lo: float, hi: float) -> float:
    """Self time of the ``ExecutePrepare`` events on the main thread that
    lie inside [lo, hi]."""
    main = sorted((s, e, n) for s, e, n, _, line in trace.host
                  if line.startswith(tr.MAIN_LINE) and lo <= s and e <= hi)
    starts = [s for s, _, _ in main]
    out = 0.0
    for i, (s, e, n) in enumerate(main):
        if n != PREPARE:
            continue
        inner = [m for m in main[i + 1:bisect.bisect_left(starts, e)]
                 if m[1] <= e]
        out += e - s - sum(b - a for a, b in tr.union(inner, s, e))
    return out


def read(run):
    spans = [(s, e) for s, e in run.trace.spans(SPAN)
             if run.lo <= s and e <= run.hi]
    if not run.steps or not spans:
        return None
    own = sum(e - s - waited_ns(run.trace, s, e) for s, e in spans)
    return own / run.steps / 1e6
