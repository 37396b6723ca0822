"""Share of the traced window in which no operation ran on a chip, in %:
1 - (union of the chip's operation intervals) / window, the mean over the
chips used; each chip's own share goes to standard error."""

import sys

from bench import trace as tr


def read(run):
    if run.window_s <= 0 or not run.devices:
        return None
    width = run.hi - run.lo
    shares = [100.0 * (1 - tr.busy_ns(d, run.lo, run.hi) / width)
              for d in run.devices]
    for d, s in zip(run.devices, shares):
        print(f"device_idle_pct chip {d.index}: {s!r}", file=sys.stderr)
    return sum(shares) / len(shares)
