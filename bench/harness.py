"""One run of one training cell: set-up, the measured window, the check.

Set-up (counted in ``setup_s``, from process start to the first timed
step): the traffic pool from the seed, the weights made on the device(s)
from the seed in one jitted call, the program's state, and steps 1-3
through the window's own call on the pool's first three batches, with the
readings the check needs taken between them (compiles happen there, or
load from the persistent cache).  The window then runs steps on the rest
of the pool in turn until ``seconds`` have passed; a step runs from the
host batch to its loss on the host.  After the window: peak memory, the
cell's exact numbers, the program's state freed, then the plain reference
on the same three batches and the comparison (``check``).
"""

from __future__ import annotations

import gc
import math
import shutil
import statistics
import sys
import time

import numpy as np

from bench import check, flops, trace as tr
from bench.registry import ROOT, Registry

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = ROOT / ".bench_trace"


class CompileClock:
    """Sums the seconds JAX spends in backend compiles (a persistent-cache
    hit counts its retrieval time instead)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def weights_key(seed: int):
    """A JAX key from any whole-number seed (also above 32 bits)."""
    import jax
    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.key(int(word) & 0x7FFFFFFF)


class Cell:
    """What a cell is made of, found by name."""

    def __init__(self, reg: Registry, name: str):
        self.name = name
        self.spec = reg.cell(name)
        self.cfg = reg.config(self.spec["config"])
        self.traffic = reg.traffic(self.spec["traffic"])
        self.gen = reg.generator(self.traffic)
        self.ref = reg.reference(self.cfg)
        self.entry = reg.entry(self.spec["entry"])
        self.opt = self.spec["optimizer"]
        self.chips = self.spec["chips"]
        self.batch_shape = (self.traffic["batch"], self.traffic["seq"])
        self.limits = self.spec["limits"]
        self.flops_per_step = flops.train_flops_per_step(
            self.cfg, *self.batch_shape)

    def pool(self, seed: int) -> list[dict]:
        return self.gen.pool(self.traffic, self.cfg["vocab_size"], seed)

    def program_config(self):
        from repro.models.config import ModelConfig
        return ModelConfig(**self.ref.program_fields(self.cfg))

    def opt_config(self):
        from repro.train import OptConfig
        return OptConfig(name="adamw", **self.opt)

    def init(self, key):
        """The seeded weights, traced with ``key`` an argument, so that one
        compiled program serves every seed."""
        return self.ref.init_weights(self.cfg, key)

    def runner(self, seed: int, devices):
        return self.entry.Runner(self.program_config(), self.opt_config(),
                                 self.init, weights_key(seed),
                                 {**self.spec, "batch_shape": self.batch_shape},
                                 devices)


def _norms(ref, tree):
    import jax
    return dict(zip(ref.leaf_names(tree),
                    (float(x) for x in jax.jit(ref.leaf_norms)(tree))))


def program_readings(cell: Cell, runner, pool: list[dict], seed: int) -> dict:
    """Steps 1-3 through the runner on ``pool[0..2]``, with the readings
    ``check`` compares: each loss; every leaf's gradient norm at step 1
    before the clip, worked out from the first moment after one step
    (mu = (1 - b1) g_clipped) and the step's global norm; every leaf's
    change after the three steps, against the seeded weights made anew."""
    import jax
    opt = cell.opt
    losses = [runner.step(pool[0])]
    scale = max(1.0, runner.grad_norm() / opt["clip_norm"]) / (1 - opt["b1"])
    grad = {n: v * scale for n, v in _norms(cell.ref, runner.first_moment()).items()}
    losses += [runner.step(pool[1]), runner.step(pool[2])]
    start = jax.jit(cell.init)(weights_key(seed))
    delta = jax.jit(lambda p, q: jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"), p, q))
    out = {"loss": losses, "grad": grad,
           "delta": _norms(cell.ref, delta(runner.params(), start))}
    del start
    return out


def reference_readings(cell: Cell, seed: int, pool,
                       variant: str = "f32") -> dict:
    return cell.ref.train_readings(cell.cfg, cell.opt, weights_key(seed),
                                   pool[:3], variant)


def measure(runner, pool: list[dict], first: int, seconds: float,
            trace_steps: int = 0) -> dict:
    """Steps on ``pool[first:]`` in turn until ``seconds`` have passed.
    With ``trace_steps``, the profiler records the first that many."""
    import jax
    times, losses = [], []
    i, traced = first, None
    if trace_steps:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=tr.profile_options())
    t0 = time.perf_counter()
    t_end = deadline = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= deadline and times:
            break
        with jax.profiler.StepTraceAnnotation(tr.SPAN_STEP, step_num=i):
            losses.append(runner.step(pool[i % len(pool)]))
        i += 1
        t_end = time.perf_counter()
        times.append(t_end - now)
        if trace_steps and len(times) == trace_steps:
            jax.profiler.stop_trace()
            traced = sorted(TRACE_DIR.glob("**/*.xplane.pb"))[-1]
    if trace_steps and traced is None:
        jax.profiler.stop_trace()
        traced = sorted(TRACE_DIR.glob("**/*.xplane.pb"))[-1]
    return {"t0": t0, "t_end": t_end, "times": times, "losses": losses,
            "trace": traced}


class TracedRun:
    """What a per-layer metric's reader gets: the trace, its window on the
    profiler's clock, the steps in it, the chips used and their peaks."""

    def __init__(self, path, cell: Cell, peak: dict):
        self.trace = tr.load(path)
        self.lo, self.hi = self.trace.window()
        self.window_s = (self.hi - self.lo) / 1e9
        self.steps = len(self.trace.spans(tr.SPAN_STEP))
        self.devices = [self.trace.devices[i]
                        for i in sorted(self.trace.devices)][:cell.chips]
        self.chips = cell.chips
        self.flops_per_step = cell.flops_per_step
        self.peak = peak

    def busy_s(self) -> float:
        return statistics.fmean(tr.busy_ns(d, self.lo, self.hi)
                                for d in self.devices) / 1e9


def _p90(values: list[float]) -> float:
    return float(np.percentile(np.asarray(values), 90))


def run(reg: Registry, name: str, seed: int, seconds: float, trace: bool,
        t_start: float, devices, peak: dict | None = None) -> dict:
    """One run; returns the result line's object.  ``peak`` stands in for
    the chip's entry in ``peaks.json`` (tests on the CPU)."""
    cell = Cell(reg, name)
    clock = CompileClock()
    kind = devices[0].device_kind
    peak = peak or flops.peak(kind)

    marks = [("start", time.perf_counter() - t_start)]
    pool = cell.pool(seed)
    marks.append(("traffic", time.perf_counter() - t_start))
    runner = cell.runner(seed, devices)
    marks.append(("weights and state", time.perf_counter() - t_start))
    prog = program_readings(cell, runner, pool, seed)
    compiles_before = clock.count
    setup_s = time.perf_counter() - t_start
    marks.append(("steps 1-3", setup_s))
    steps = measure(runner, pool, 3, seconds,
                    cell.spec["trace_steps"] if trace else 0)
    compiles_in_window = clock.count - compiles_before
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    exact = runner.exact_numbers()
    runner.close()
    del runner
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed, pool)
    t_ref = time.perf_counter() - t_ref
    numbers = {**check.gaps(prog, ref), **exact}
    attempted = len(steps["losses"])
    failed = sum(not math.isfinite(l) for l in steps["losses"])
    correct, checks = check.judge(numbers, cell.limits)
    correct = correct and failed == 0 and attempted > 0

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    window_s = steps["t_end"] - steps["t0"]
    tokens = cell.batch_shape[0] * cell.batch_shape[1]
    e2e = {
        "train_tokens_per_s": tokens * attempted / window_s,
        "step_ms.p90": _p90(steps["times"]) * 1e3,
        "setup_s": setup_s,
    }
    metrics, breakdown = {}, None
    if trace:
        run_ = TracedRun(steps["trace"], cell, peak)
        device["busy_s"] = run_.busy_s()
        device["window_s"] = run_.window_s
        for m in reg.metrics(name, "per_layer"):
            value = reg.reader(m["name"])(run_)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        d0 = run_.devices[0]
        breakdown = {"device_ops": tr.top_ops(d0, run_.lo, run_.hi),
                     "idle_gaps": tr.idle_gaps(run_.trace, d0, run_.lo,
                                               run_.hi)}
    else:
        for m in reg.metrics(name, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    log = sys.stderr
    print("set-up: " + ", ".join(f"{k} at {v:.2f} s" for k, v in marks)
          + f"; {compiles_before} compiles took {clock.seconds:.2f} s",
          file=log)
    print(f"window: {attempted} steps in {window_s:.3f} s, "
          f"{compiles_in_window} compiles inside it; losses "
          f"{steps['losses'][0]:.4f} -> {steps['losses'][-1]:.4f}", file=log)
    print(f"program losses {prog['loss']} reference {ref['loss']} "
          f"({t_ref:.1f} s)", file=log)
    print(f"grad gap set by {numbers['grad_gap_leaf']}, update gap by "
          f"{numbers['update_gap_leaf']}", file=log)
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=log)
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
