"""The backup flush's share of chip 0's HBM bandwidth, in %: 2 x the bytes
that the ``replica.flush`` spans say they copied (a copy reads and writes
every byte), over the device time of the programs launched inside them
(``replica_flush_ms_per_step``'s time), over ``peaks.json``
``hbm_bytes_per_s``.  The bytes are the whole state's, so the share is
one chip's only where one chip holds the state.  Nothing to read where the
program has no such span."""

from bench import span_stats, trace as tr

SPAN = "replica.flush"


def read(run):
    if not run.devices:
        return None
    flushes = span_stats.for_run(run, SPAN)
    if not flushes:
        return None
    nbytes = sum(st["nbytes"] for _, _, st in flushes)
    ns = tr.span_device_ns(run.trace, SPAN, run.devices[0])
    if not ns:
        return None
    return 100.0 * 2 * nbytes / (ns / 1e9) / run.peak["hbm_bytes_per_s"]
