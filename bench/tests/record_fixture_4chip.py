"""Record the small four-chip trace that ``test_bench_4chip_trace.py``
reduces.

    python bench/tests/record_fixture_4chip.py <out_dir>

Runs on the four TPU chips of one host: the qwen3-1.7b smoke configuration
through ``TrainState.step`` on a (data 2, model 2) mesh, with no backup
slot (the step donates), three traced steps, each in a ``bench.step``
span.  Writes ``<out_dir>/train_4chip.xplane.pb``,
``<out_dir>/train_4chip.hlo.txt`` (the compiled step the trace runs) and
``<out_dir>/summary.json`` (per device, the opcodes of the ``XLA Ops``
events with their count and time, and the names of those that
``collective_ms_per_step`` counts), then prints the summary.  Gzip the
``.xplane.pb`` and the ``.hlo.txt`` into ``bench/tests/fixtures/``, and
set the expected numbers in the test from the summary.
"""

from __future__ import annotations

import collections
import glob
import json
import re
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# ``%name = <shape> opcode(operands)...``
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*.*?\s([a-z][\w-]*)\(")


def opcodes(text: str) -> dict:
    """Instruction name -> opcode, for every line of HLO text (or op
    event name) that holds one."""
    out = {}
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def summarize(path: str) -> dict:
    from jax.profiler import ProfileData

    from bench.metrics.collective_ms_per_step import is_collective
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {}
        for line in plane.lines:
            if "XLA Ops" not in line.name:
                continue
            kinds = collections.Counter()
            ns = collections.Counter()
            names = set()
            for ev in line.events:
                for name, op in opcodes(ev.name).items():
                    kinds[op] += 1
                    ns[op] += ev.duration_ns
                    if is_collective(ev.name):
                        names.add(name)
            lines[line.name] = {"opcodes": {k: [kinds[k], ns[k]]
                                            for k in sorted(kinds)},
                                "collectives": sorted(names)}
        out[plane.name] = lines
    return out


def main() -> None:
    out_dir = Path(sys.argv[1])
    out_dir.mkdir(parents=True, exist_ok=True)
    import jax
    import numpy as np

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        raise SystemExit("record_fixture_4chip: four TPU chips needed")
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.train import OptConfig, TrainState, shard_batch

    from bench.trace import SPAN_STEP, profile_options
    cfg = configs.smoke("qwen3-1.7b")
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    ts = TrainState(cfg, OptConfig(lr=1e-3, warmup=5),
                    init_params(cfg, jax.random.PRNGKey(0)), mesh=mesh)
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab, (4, 64), dtype=np.int32),
             "labels": rng.integers(0, cfg.vocab, (4, 64), dtype=np.int32)}
    for _ in range(2):
        float(ts.step(batch)["loss"])
    params, opt_state = ts.state.read()
    hlo = ts._owned.donating.lower(params, opt_state, shard_batch(mesh, batch))
    hlo = hlo.compile().as_text()
    trace_dir = out_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir),
                             profiler_options=profile_options())
    for _ in range(3):
        with jax.profiler.StepTraceAnnotation(SPAN_STEP):
            float(ts.step(batch)["loss"])
    jax.profiler.stop_trace()
    [pb] = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    shutil.copy(pb, out_dir / "train_4chip.xplane.pb")
    (out_dir / "train_4chip.hlo.txt").write_text(hlo)
    summary = {"device_kind": devices[0].device_kind,
               "devices": summarize(pb)}
    (out_dir / "summary.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary)[:20000])


if __name__ == "__main__":
    main()
