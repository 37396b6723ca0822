"""The device's clock against the host's, worked out from a trace alone.

Each program run has a device module event and host events with the same
``run_id``: ``DoEnqueueProgram``, where the host enqueued it, and
``CompleteCallbacks``, where the host learnt that it had finished.  On a
TPU v5e trace the module comes out before the host event that enqueued
it (1.17 - 1.25 ms on one recorded trace, 0.34 ms at least on another):
the device's events are early against the host clock.  A program cannot
start before it is enqueued, nor end after the host saw it end, so the
shift to add to the device's times lies between
max(enqueue start - module start) and min(completion start - module end).
The alignment here takes the lower bound: the smallest shift that puts
every run at or after its enqueue.
"""

from __future__ import annotations

from bench import trace as tr

DONE = "CompleteCallbacks"


def _first(trace: tr.Trace, name: str) -> dict:
    """Run id -> start of the first host event ``name`` that carries it,
    on whichever thread."""
    out: dict = {}
    for s, _, n, rid, _ in trace.host:
        if n == name and rid is not None:
            out[rid] = min(s, out.get(rid, s))
    return out


def enqueues(trace: tr.Trace) -> dict:
    """Run id -> start of the first host event that enqueued it."""
    return _first(trace, tr.LAUNCH)


def device_offset_ns(trace: tr.Trace, dev: tr.Device) -> float | None:
    """The shift to add to ``dev``'s times: max(enqueue start - module
    start) over its runs.  None where no run can be matched."""
    starts = enqueues(trace)
    gaps = [starts[rid] - s for s, _, _, rid in dev.modules if rid in starts]
    return max(gaps) if gaps else None


def step_bounds_ns(trace: tr.Trace, dev: tr.Device) -> list[tuple]:
    """For each ``SPAN_STEP``, in order, the (lowest, highest) shift that
    the runs enqueued inside it allow: one shift for the whole window
    holds where it lies inside every step's pair.  A step whose runs all
    queue behind earlier work gives a loose lower bound (as low as minus
    the queue's depth); an upper bound is None where no run of the step
    has a completion event."""
    starts, done = enqueues(trace), _first(trace, DONE)
    out = []
    for lo, hi in trace.spans(tr.SPAN_STEP):
        runs = [(s, e, rid) for s, e, _, rid in dev.modules
                if rid in starts and lo <= starts[rid] < hi]
        if runs:
            out.append((max(starts[r] - s for s, _, r in runs),
                        min((done[r] - e for _, e, r in runs if r in done),
                            default=None)))
    return out


def aligned(trace: tr.Trace, dev: tr.Device) -> tr.Device | None:
    """``dev`` with its operations and modules moved onto the host clock,
    or None where the offset cannot be worked out."""
    off = device_offset_ns(trace, dev)
    if off is None:
        return None
    return tr.Device(dev.index,
                     ops=[(s + off, e + off, n) for s, e, n in dev.ops],
                     modules=[(s + off, e + off, n, rid)
                              for s, e, n, rid in dev.modules])
