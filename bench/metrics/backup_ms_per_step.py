"""Device time of the backup flush per traced step, in ms, on chip 0: the
programs that the host launched inside the harness's backup span
(``SPAN_BACKUP``, opened and closed around ``ReplicaSlot._flush`` by hooks
at the front and back of the state's epoch hooks).  Nothing to read where
the path has no backup."""

from bench import trace as tr


def read(run):
    if not run.steps or not run.trace.spans(tr.SPAN_BACKUP):
        return None
    ns = tr.span_device_ns(run.trace, tr.SPAN_BACKUP, run.devices[0])
    return ns / run.steps / 1e6 if ns else None
