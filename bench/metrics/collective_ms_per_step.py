"""Device time of the step's collective operations per traced step, in ms,
the mean over the chips used.

An operation counts where it is a collective or runs one alone, as the
TPU compiler schedules them: a collective instruction (``all-gather``,
``all-reduce``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, or the ``-start``/``-done`` halves of one), the
``async-collective-start``/``-done`` fusions that launch and await an
asynchronous one, and a fusion that calls a computation named after a
collective (``calls=%all-reduce-scatter``).  A compute fusion that carries
an asynchronous collective's progress inside it
(``calls=%async_collective_fusion``) does not count: its time is the
compute's, and the transfer it hides costs the step nothing.  So this is
the time the chip spends on collectives rather than on compute.

The short names ``trace.load`` keeps do not say what a fusion calls, so
the operations are read again, with their HLO text, from the run's own
traced file (found as ``span_stats.for_run`` finds it).  Each chip's share
goes to standard error.  Nothing to read where the program runs no
collective."""

from __future__ import annotations

import re
import sys

from bench import trace as tr

KINDS = "all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
# in ``%name = <shape> opcode(operands), calls=%computation, ...``
OPCODE = re.compile(rf"\s(?:{KINDS})(?:-start|-done)?\(")
NAME = re.compile(rf"^%?(?:async-collective|{KINDS})[\w.-]*\s=")
CALLS = re.compile(rf"calls=%(?:{KINDS})")


def is_collective(text: str) -> bool:
    """Whether the ``XLA Ops`` event named ``text`` (an HLO instruction)
    is a collective or runs one alone."""
    head = text.split(", metadata=", 1)[0]
    return bool(NAME.match(head) or OPCODE.search(head) or CALLS.search(head))


def collective_ops(path) -> tuple[tuple | None, dict]:
    """The ``SPAN_STEP`` window of the trace at ``path``, and chip index ->
    (start, end) of every collective operation on its ``XLA Ops`` line."""
    from jax.profiler import ProfileData
    steps, ops = [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                ops.setdefault(int(m.group(1)), []).extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if is_collective(ev.name))
            elif plane.name.startswith("/host:CPU"):
                steps += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events if ev.name == tr.SPAN_STEP]
    window = (min(steps)[0], max(e for _, e in steps)) if steps else None
    return window, ops


def read(run):
    """From the run's traced file: the newest under the harness's trace
    directory, if its ``SPAN_STEP`` window is the run's."""
    from bench import harness
    files = sorted(harness.TRACE_DIR.glob("**/*.xplane.pb"))
    if not run.steps or not run.devices or not files:
        return None
    window, ops = collective_ops(files[-1])
    if window != (run.lo, run.hi):
        return None
    per_chip = [sum(b - a for a, b in tr.union(ops.get(d.index, []),
                                                run.lo, run.hi))
                for d in run.devices]
    if not any(per_chip):
        return None
    for d, ns in zip(run.devices, per_chip):
        print(f"collective_ms_per_step chip {d.index}: "
              f"{ns / run.steps / 1e6!r}", file=sys.stderr)
    return sum(per_chip) / len(per_chip) / run.steps / 1e6
