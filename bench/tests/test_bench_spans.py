"""The device clock's alignment and the readers of the program's own spans,
on two traces recorded on a TPU v5e chip by ``record_fixture.py``:
``train_1chip`` (before the program had spans) and ``train_1chip_spans``
(with ``train.dispatch``, ``ownership.epoch`` and ``replica.flush``)."""

import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import clock, harness, span_stats, trace as tr
from bench.registry import ROOT, Registry

FIXTURES = Path(__file__).parent / "fixtures"
PLAIN = FIXTURES / "train_1chip.xplane.pb.gz"
SPANS = FIXTURES / "train_1chip_spans.xplane.pb.gz"
PEAK = {"hbm_bytes_per_s": 819e9}
NEW = ("replica_flush_ms_per_step", "epoch_host_ms_per_step",
       "dispatch_idle_ms_per_step", "backup_hbm_pct")


@pytest.fixture(scope="module", params=[PLAIN, SPANS], ids=["plain", "spans"])
def chip_trace(request):
    return tr.load(request.param)


def _run(path):
    """What a reader gets from the harness for the trace at ``path``."""
    return harness.TracedRun(path, SimpleNamespace(chips=1,
                                                   flops_per_step=1.0), PEAK)


@pytest.fixture
def spans_run(tmp_path, monkeypatch):
    """The spans fixture as the newest trace of a run, where
    ``span_stats.for_run`` looks for it."""
    pb = tmp_path / "plugins" / "profile" / "t" / "host.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(gzip.decompress(SPANS.read_bytes()))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return _run(pb)


def test_offset_on_the_plain_fixture():
    trace = tr.load(PLAIN)
    assert clock.device_offset_ns(trace, trace.devices[0]) == 1_252_821


def test_no_run_starts_before_its_enqueue_once_aligned(chip_trace):
    dev = chip_trace.devices[0]
    starts = clock.enqueues(chip_trace)
    assert {rid for *_, rid in dev.modules} <= set(starts)
    early = [starts[rid] - s for s, _, _, rid in dev.modules]
    assert max(early) > 0                      # unaligned, runs come early
    moved = clock.aligned(chip_trace, dev)
    late = [s - starts[rid] for s, _, _, rid in moved.modules]
    assert min(late) == 0                      # the tightest run touches
    assert len(moved.ops) == len(dev.ops)
    assert tr.busy_ns(moved, float("-inf"), float("inf")) == tr.busy_ns(
        dev, float("-inf"), float("inf"))


def test_one_offset_fits_every_step(chip_trace):
    """Each step's runs bound the shift from both sides (enqueue before
    start, end before the host's completion event); the window's one
    offset lies inside every step's bounds, so no step needs its own.
    (A lower bound is loose where a step's runs all queue behind earlier
    work, as on a full-size trace, so the lower bounds' spread is no
    measure of drift.)"""
    dev = chip_trace.devices[0]
    off = clock.device_offset_ns(chip_trace, dev)
    bounds = clock.step_bounds_ns(chip_trace, dev)
    assert len(bounds) == 3
    assert max(lo for lo, _ in bounds) == off
    assert all(lo <= off <= hi for lo, hi in bounds)


def test_aligned_gaps_on_the_plain_fixture():
    """The harness's idle gaps, named against the aligned device: the
    inter-copy gaps move from the flush span to the copies' own calls,
    and none is left inside the step's dispatch."""
    trace = tr.load(PLAIN)
    lo, hi = trace.window()
    gaps = dict(tr.idle_gaps(trace, clock.aligned(trace, trace.devices[0]),
                             lo, hi))
    assert gaps[tr.SPAN_BACKUP] == pytest.approx(0.002598943)
    assert gaps["runtime"] == pytest.approx(0.001667089)
    assert "PjitFunction(train_step)" not in gaps


def test_new_readers_find_nothing_without_program_spans(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    reg, run = Registry(ROOT), _run(PLAIN)
    assert {m: reg.reader(m)(run) for m in NEW} == dict.fromkeys(NEW)


def test_offset_on_the_spans_fixture():
    trace = tr.load(SPANS)
    assert clock.device_offset_ns(trace, trace.devices[0]) == 1_303_090


def test_program_spans_nest(spans_run):
    trace = spans_run.trace
    steps = trace.spans(tr.SPAN_STEP)
    dispatch, epoch, flush, harness_flush = (
        trace.spans(n) for n in ("train.dispatch", "ownership.epoch",
                                 "replica.flush", tr.SPAN_BACKUP))
    assert len(steps) == len(dispatch) == len(epoch) == len(flush) == 3
    for st, d, ep, fl, hf in zip(steps, dispatch, epoch, flush,
                                 harness_flush):
        assert st[0] <= d[0] and d[1] <= ep[0] and ep[1] <= st[1]
        assert ep[0] <= hf[0] <= fl[0] and fl[1] <= hf[1] <= ep[1]
    # the stats the program writes: the flush's bytes, nothing else
    assert [set(st) for _, _, st in span_stats.for_run(
        spans_run, "replica.flush")] == [{"nbytes"}] * 3
    assert [st for _, _, st in span_stats.for_run(
        spans_run, "ownership.epoch")] == [{}] * 3


def test_readers_on_the_spans_fixture(spans_run):
    reg = Registry(ROOT)
    value = {m: reg.reader(m)(spans_run) for m in NEW + ("backup_ms_per_step",)}
    # the same launches as the harness's own span around the flush
    assert value["replica_flush_ms_per_step"] == value["backup_ms_per_step"]
    assert value["replica_flush_ms_per_step"] == pytest.approx(0.034219)
    assert value["epoch_host_ms_per_step"] == pytest.approx(11.788414667)
    # steps 2 and 3: the first traced dispatch is left out
    assert value["dispatch_idle_ms_per_step"] == pytest.approx(0.6858345)
    # 2 x 3 x nbytes over the flush's device time, over 819 GB/s
    flushes = span_stats.for_run(spans_run, "replica.flush")
    nbytes = [st["nbytes"] for _, _, st in flushes]
    assert len(set(nbytes)) == 1
    assert value["backup_hbm_pct"] == pytest.approx(
        100 * 2 * sum(nbytes) / (3 * 0.034219e-3) / 819e9)
    assert value["backup_hbm_pct"] == pytest.approx(25.777823)


def test_span_stats_only_for_the_runs_own_trace(spans_run):
    assert span_stats.for_run(spans_run, "replica.flush")
    other = SimpleNamespace(lo=spans_run.lo, hi=spans_run.hi + 1)
    assert span_stats.for_run(other, "replica.flush") is None


def test_epoch_host_leaves_out_the_wait_to_enqueue():
    """A launch whose ``ExecutePrepare`` waits 80 units for room in the
    queue before its own work: the epoch keeps only the host's work."""
    from bench.metrics import epoch_host_ms_per_step as eh
    main, py = "main/1", "python3"
    host = [(0, 100, "ownership.epoch", None, py),
            (5, 95, eh.PREPARE, None, main),
            (85, 86, "Acquire semaphore", None, main),
            (86, 94, "AllocateRawBuffer", None, main),
            (88, 90, "MemoryAllocation", None, main),
            (96, 99, eh.PREPARE, None, main),
            (97, 98, "Handle inputs", None, main),
            (0, 100, tr.SPAN_STEP, None, py)]
    trace = tr.Trace({}, host)
    assert eh.waited_ns(trace, 0, 100) == (90 - 9) + (3 - 1)
    run = SimpleNamespace(trace=trace, lo=0, hi=100, steps=1)
    assert eh.read(run) == (100 - 83) / 1e6
