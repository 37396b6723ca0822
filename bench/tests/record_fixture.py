"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python bench/tests/record_fixture.py <out_dir>

Runs on one TPU chip: the qwen3 smoke configuration through
``TrainState.step`` with a ``ReplicaSlot`` attached and the harness's own
backup-flush span hooks, three traced steps.  Writes ``<out_dir>/
train_1chip.xplane.pb`` and ``<out_dir>/summary.json`` (every plane, its
lines, and per line the event names and stats that occur), then prints the
summary.  Gzip the ``.xplane.pb`` into ``bench/tests/fixtures/`` and set the
expected numbers in the test from the summary.
"""

from __future__ import annotations

import collections
import glob
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def summarize(path: str) -> dict:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            names = collections.Counter()
            stats = set()
            n = 0
            for ev in line.events:
                n += 1
                names[ev.name] += 1
                for k, _ in ev.stats:
                    stats.add(k)
            lines.append({"line": line.name, "events": n,
                          "top": names.most_common(25),
                          "stats": sorted(stats)})
        out.append({"plane": plane.name, "lines": lines,
                    "stats": sorted(k for k, _ in plane.stats)})
    return {"planes": out}


def main() -> None:
    out_dir = Path(sys.argv[1])
    out_dir.mkdir(parents=True, exist_ok=True)
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture: no TPU")
    from repro import configs
    from repro.models import init_params
    from repro.train import OptConfig, TrainState

    from bench.trace import SPAN_BACKUP
    cfg = configs.smoke("qwen3-0.6b")
    ts = TrainState(cfg, OptConfig(lr=1e-3, warmup=5),
                    init_params(cfg, jax.random.PRNGKey(0)))
    ts.replicate()
    spans = []

    def begin(*_):
        spans.append(jax.profiler.TraceAnnotation(SPAN_BACKUP))
        spans[-1].__enter__()

    def end(*_):
        spans.pop().__exit__(None, None, None)

    ts.state.on_epoch.insert(0, begin)
    ts.state.on_epoch.append(end)
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab, (4, 64), dtype=np.int32),
             "labels": rng.integers(0, cfg.vocab, (4, 64), dtype=np.int32)}
    for _ in range(2):
        float(ts.step(jax.tree.map(jnp.asarray, batch))["loss"])
    trace_dir = out_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    from bench.trace import profile_options
    jax.profiler.start_trace(str(trace_dir),
                             profiler_options=profile_options())
    for _ in range(3):
        with jax.profiler.StepTraceAnnotation("bench.step"):
            float(ts.step(jax.tree.map(jnp.asarray, batch))["loss"])
    jax.profiler.stop_trace()
    [pb] = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    shutil.copy(pb, out_dir / "train_1chip.xplane.pb")
    summary = summarize(pb)
    summary["device_kind"] = jax.devices()[0].device_kind
    (out_dir / "summary.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary)[:20000])


if __name__ == "__main__":
    main()
