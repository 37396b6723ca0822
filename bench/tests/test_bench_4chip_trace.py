"""``collective_ms_per_step`` and ``sharded_step_mfu`` on a four-chip
trace recorded on a TPU v5e host by ``record_fixture_4chip.py`` (the
qwen3-1.7b smoke configuration through ``TrainState.step`` on a (data 2,
model 2) mesh, three traced steps), checked against the compiled HLO of
the step that the trace ran."""

import gzip
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, trace as tr
from bench.registry import ROOT, Registry, _module

FIXTURES = Path(__file__).parent / "fixtures"
TRACE = FIXTURES / "train_4chip.xplane.pb.gz"
HLO = FIXTURES / "train_4chip.hlo.txt.gz"
ONE_CHIP = FIXTURES / "train_1chip_spans.xplane.pb.gz"
PEAK = {"bf16_flop_per_s": 197e12}
FLOPS = 1e9                      # a stand-in step count for the MFU's scale
# the reader on this fixture, as first read (each chip's share: 0.21497,
# 0.21452, 0.21451, 0.21379 ms)
COLLECTIVE_MS = 0.21444641666666667
KINDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
         "all-to-all")


def _reader(name):
    return Registry(ROOT).reader(name)


def _traced(tmp_path, monkeypatch, fixture, chips):
    """What a reader gets from the harness for ``fixture``, laid out where
    the harness's traced run would have written it."""
    pb = tmp_path / "plugins" / "profile" / "t" / "host.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(gzip.decompress(fixture.read_bytes()))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return harness.TracedRun(pb, SimpleNamespace(chips=chips,
                                                 flops_per_step=FLOPS), PEAK)


@pytest.fixture
def run(tmp_path, monkeypatch):
    return _traced(tmp_path, monkeypatch, TRACE, 4)


def _scheduled_collectives(hlo: str) -> set:
    """The instructions of the compiled step that the chip runs as ops
    (those of computations no fusion calls) and that are a collective or
    call a computation holding one, leaving out the compute fusions that
    carry one inside (``async_collective_fusion``)."""
    bodies, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) .*\{$", line)
        if head:
            comp = bodies.setdefault(head.group(1), [])
        elif comp is not None and " = " in line:
            comp.append(line.split(", metadata=", 1)[0])
    called = {c for lines in bodies.values() for l in lines
              for c in re.findall(r"calls=%([\w.-]+)", l)}
    opcode = re.compile(r"\s(" + "|".join(KINDS) + r")(-start|-done)?\(")
    holds = {c for c, lines in bodies.items()
             if any(opcode.search(l) for l in lines)}
    out = set()
    for c, lines in bodies.items():
        if c in called:
            continue
        for l in lines:
            name = re.match(r"^\s*(?:ROOT\s+)?%([\w.-]+)", l).group(1)
            calls = re.findall(r"calls=%([\w.-]+)", l)
            if opcode.search(l) or any(
                    x in holds and not x.startswith("async_collective_fusion")
                    for x in calls):
                out.add(name)
    return out


def test_reader_counts_what_the_hlo_runs_as_collectives(run):
    """The name rule of the reader, against the program's structure:
    the ops it counts in the trace are exactly the scheduled collectives
    of the compiled step that ran in the window."""
    from jax.profiler import ProfileData
    is_collective = _module(ROOT / "bench" / "metrics"
                            / "collective_ms_per_step.py").is_collective
    path = next(harness.TRACE_DIR.glob("**/*.xplane.pb"))
    seen, counted = set(), set()
    for plane in ProfileData.from_file(str(path)).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if run.lo <= ev.start_ns < run.hi:
                    seen.add(tr.op_name(ev.name))
                    if is_collective(ev.name):
                        counted.add(tr.op_name(ev.name))
    want = _scheduled_collectives(gzip.decompress(HLO.read_bytes()).decode())
    assert counted and counted == want & seen
    assert want <= seen


def test_collective_time_per_step(run):
    assert sorted(d.index for d in run.devices) == [0, 1, 2, 3]
    assert run.steps == 3
    value = _reader("collective_ms_per_step")(run)
    busy_ms = run.busy_s() * 1e3 / run.steps
    assert 0 < value < busy_ms
    assert value == pytest.approx(COLLECTIVE_MS, rel=1e-9)


def test_sharded_mfu_reads_every_chip(run):
    value = _reader("sharded_step_mfu")(run)
    want = 100 * FLOPS * 3 / run.window_s / (4 * PEAK["bf16_flop_per_s"])
    assert value == pytest.approx(want) and 0 < value < 100


def test_nothing_to_read_without_collectives(tmp_path, monkeypatch):
    """A one-chip trace runs no collective; and a trace that is not the
    run's own is not read."""
    one = _traced(tmp_path / "one", monkeypatch, ONE_CHIP, 1)
    assert _reader("collective_ms_per_step")(one) is None
    other = _traced(tmp_path / "four", monkeypatch, TRACE, 4)
    other.lo -= 1
    assert _reader("collective_ms_per_step")(other) is None

