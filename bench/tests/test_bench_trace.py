"""The trace reduction, on a trace recorded on a TPU v5e chip
(``record_fixture.py``: the qwen3 smoke configuration through
``TrainState.step`` with its backup, three traced steps) and on
hand-made intervals."""

from pathlib import Path

import pytest

from bench import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "train_1chip.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_trace():
    return tr.load(FIXTURE)


def test_window_and_steps(chip_trace):
    assert sorted(chip_trace.devices) == [0]
    steps = chip_trace.spans(tr.SPAN_STEP)
    assert len(steps) == 3
    lo, hi = chip_trace.window()
    assert (lo, hi) == (steps[0][0], steps[-1][1])
    assert (hi - lo) / 1e6 == pytest.approx(41.159278)
    dev = chip_trace.devices[0]
    # every program run of the window lies inside it, on the same clock
    assert all(lo <= s and e <= hi for s, e, _, _ in dev.modules)


def test_busy_and_idle(chip_trace):
    lo, hi = chip_trace.window()
    dev = chip_trace.devices[0]
    busy = tr.busy_ns(dev, lo, hi)
    assert busy == pytest.approx(277461)
    assert busy <= sum(e - s for s, e, _ in dev.ops)


def test_backup_span_device_time(chip_trace):
    dev = chip_trace.devices[0]
    assert len(chip_trace.spans(tr.SPAN_BACKUP)) == 3
    copies = sum(e - s for s, e, n, _ in dev.modules
                 if n.startswith("jit_copy"))
    # exactly the copies the flush launched: not the step, whose deferred
    # launch falls inside the span on the runtime's own thread
    assert tr.span_device_ns(chip_trace, tr.SPAN_BACKUP, dev) == copies
    assert copies == pytest.approx(103238)


def test_step_program_ops_and_gaps(chip_trace):
    lo, hi = chip_trace.window()
    dev = chip_trace.devices[0]
    ops = tr.top_ops(dev, lo, hi)
    assert len(ops) == 10 and ops[0][0] == "copy.1"
    assert ops == sorted(ops, key=lambda x: -x[1])
    gaps = tr.idle_gaps(chip_trace, dev, lo, hi)
    assert sum(s for _, s in gaps) <= (hi - lo - tr.busy_ns(dev, lo, hi)) / 1e9 + 1e-12
    assert {name for name, _ in gaps} >= {"PjitFunction(copy)",
                                          tr.SPAN_BACKUP}


def test_union_by_hand():
    dev = tr.Device(0, ops=[(0, 10, "fusion.1"), (5, 20, "all-reduce.3"),
                            (30, 40, "all-gather-start.1"),
                            (50, 60, "copy.2")])
    assert tr.union(dev.ops, 0, 100) == [(0, 20), (30, 40), (50, 60)]
    assert tr.busy_ns(dev, 8, 55) == 12 + 10 + 5
    assert tr.op_name("%fusion.12 = bf16[2]{0} fusion(%a)") == "fusion.12"
