"""Device time of the backup flush per traced step, in ms, on chip 0: the
programs that the host launched inside the program's own ``replica.flush``
spans (``ReplicaSlot._flush``), matched by run id.  Nothing to read where
the program has no such span."""

from bench import trace as tr

SPAN = "replica.flush"


def read(run):
    if not run.steps or not run.devices or not run.trace.spans(SPAN):
        return None
    ns = tr.span_device_ns(run.trace, SPAN, run.devices[0])
    return ns / run.steps / 1e6 if ns else None
