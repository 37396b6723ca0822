"""Bring-up check: train and serve qwen3-0.6b at its published widths on
one TPU chip, through the launchers a user calls.

    python chip_smoke.py               # one chip: train steps, then requests
    python chip_smoke.py --four-chips  # (data 2, model 2) TrainState vs 1 chip

Everything runs in this one process, which holds the chip(s).  It turns
on the compile cache the launchers' ``main`` would (``enable_compile_cache``)
and runs their ``parse_args`` + ``run`` with the arguments below.  The script
exits non-zero when JAX finds no TPU, when it is run outside a checkout of
the repository, or when any check fails.  Its last line on stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the lines before it are bring-up observations (compile seconds, step
seconds taken after the result reached the host, losses, peak HBM bytes),
not a benchmark.

Checks, one chip:
  * train (``repro.launch.train --full``): every loss finite, the last
    below the first on the Markov data;
  * serve (``repro.launch.serve --full``): every request completes with
    ``max_new`` token ids in ``[0, vocab)``;
  * the first decode step's bf16 logits agree with a float32,
    ``precision="highest"`` run of ``decode_step`` on the same inputs
    within ``LOGIT_RMS_TOL`` / ``LOGIT_MAX_TOL``, and every first token the
    engine emitted is within ``LOGIT_MAX_TOL`` of the reference's best.
Checks, ``--four-chips``:
  * the parameters span all four chips, and one chip holds about a
    quarter of their bytes;
  * three sharded train steps give the one-chip losses on the same
    batches within ``LOSS_RTOL`` (bf16 reductions in another order).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
# the launcher's default peak lr (3e-3) is for the smoke config; at full
# width it overshoots once warm-up ends and the loss climbs
TRAIN_ARGV = ["--arch", ARCH, "--full", "--steps", "8", "--batch", "8",
              "--seq", "256", "--lr", "1e-3"]
SERVE_ARGV = ["--arch", ARCH, "--full", "--requests", "8", "--slots", "4",
              "--max-new", "8"]

# bf16 weights and activations through every layer vs a float32 run of
# the same (bf16-valued) weights: RMS error relative to the reference's RMS,
# and the largest error relative to the reference's largest |logit|.
LOGIT_RMS_TOL = 5e-2
LOGIT_MAX_TOL = 1e-1
# sharded vs one-chip loss, relative
LOSS_RTOL = 1e-2

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Sums the seconds JAX spends in backend compiles (a persistent-cache
    hit counts its retrieval time instead)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def steady(step_s: list[float]) -> dict:
    """The first step compiles; the rest are steady state."""
    rest = step_s[1:] or step_s
    return {"first_s": step_s[0], "median_s": statistics.median(rest),
            "min_s": min(rest)}


def train_phase(argv: list[str], clock: CompileClock) -> dict:
    from repro.launch import train
    out = train.run(train.parse_args(argv))
    losses = out["losses"]
    check(all(math.isfinite(l) for l in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return {"compile_s": clock.lap(), "first_loss": losses[0],
            "last_loss": losses[-1], "steps": len(losses),
            **steady(out["step_s"])}


def first_step_logits(engine, requests):
    """bf16 logits of the engine's first decode step, and those of a
    float32 ``precision="highest"`` run on the same inputs.  The first
    tick admits the first ``slots`` requests into slots 0.. in order and
    feeds each its last prompt token against an empty cache."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode_step, init_cache

    cfg = engine.cfg
    params = engine.weights.read()
    tokens = jnp.asarray([[r.prompt[-1]] for r in requests[:engine.slots]],
                         jnp.int32)

    def logits(c, p):
        cache = init_cache(c, engine.slots, engine.max_len)
        return jax.jit(functools.partial(decode_step, c))(
            p, cache, tokens)[0][:, -1].astype(jnp.float32)

    got = logits(cfg, params)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        want = logits(cfg32, jax.tree.map(
            lambda x: x.astype(jnp.float32), params))
    return got, want


def serve_phase(argv: list[str], clock: CompileClock) -> dict:
    import numpy as np

    from repro.launch import serve
    out = serve.run(serve.parse_args(argv))
    engine, reqs = out["engine"], out["requests"]
    vocab = engine.cfg.vocab
    for r in reqs:
        check(r.done and len(r.generated) == r.max_new,
              f"request {r.rid} incomplete: {len(r.generated)} tokens")
        check(all(0 <= t < vocab for t in r.generated),
              f"request {r.rid} token outside [0, {vocab})")
    serve_compile_s = clock.lap()

    got, want = (np.asarray(x) for x in first_step_logits(engine, reqs))
    err = got - want
    rms = float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2)))
    worst = float(np.abs(err).max() / np.abs(want).max())
    check(rms <= LOGIT_RMS_TOL, f"logit RMS error {rms} > {LOGIT_RMS_TOL}")
    check(worst <= LOGIT_MAX_TOL, f"logit max error {worst} > {LOGIT_MAX_TOL}")
    margin = LOGIT_MAX_TOL * float(np.abs(want).max())
    for slot, r in enumerate(reqs[:engine.slots]):
        tok = r.generated[0]
        check(want[slot, tok] >= want[slot].max() - margin,
              f"slot {slot}: engine token {tok} is not the reference's best")
    return {"compile_s": serve_compile_s, "requests": len(reqs),
            "tokens": sum(len(r.generated) for r in reqs),
            "logit_rel_rms_err": rms, "logit_rel_max_err": worst,
            **steady(out["step_s"])}


def four_chip_phase(cfg, batch: int = 8, seq: int = 256,
                    steps: int = 3) -> dict:
    """A ``TrainState`` on a (data 2, model 2) mesh, which places the state
    and shards its step, against the one-chip ``TrainState`` on the same
    batches."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.train import OptConfig, TrainState, synthetic_batches

    opt = OptConfig(lr=3e-3, warmup=5, decay_steps=2 * steps)
    data = synthetic_batches(cfg.vocab, batch, seq)
    batches = [next(data) for _ in range(steps)]

    ts = TrainState(cfg, opt, init_params(cfg, jax.random.PRNGKey(0)))
    one = [float(ts.step(jax.tree.map(jnp.asarray, b))["loss"])
           for b in batches]
    del ts

    mesh = make_mesh((2, 2), ("data", "model"))
    ts = TrainState(cfg, opt, init_params(cfg, jax.random.PRNGKey(0)),
                    mesh=mesh)
    leaves = jax.tree.leaves(ts.params())
    held = set().union(*(l.sharding.device_set for l in leaves))
    check(len(held) == 4, f"parameters on {len(held)} devices, not 4")
    total = sum(l.nbytes for l in leaves)
    dev0 = jax.devices()[0]
    on_dev0 = sum(s.data.nbytes for l in leaves
                  for s in l.addressable_shards if s.device == dev0)
    check(on_dev0 < 0.3 * total,
          f"device 0 holds {on_dev0 / total:.2f} of the parameters")
    sharded = [float(ts.step(b)["loss"]) for b in batches]
    for a, b in zip(one, sharded):
        check(math.isfinite(b) and abs(a - b) <= LOSS_RTOL * abs(a),
              f"sharded losses {sharded} vs one-chip {one}")
    return {"one_chip_losses": one, "sharded_losses": sharded,
            "param_share_on_device0": on_dev0 / total}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips "
                    "and the one-chip run it is compared with")
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"chip_smoke: run it from a checkout of the "
                         f"repository ({e})")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                         f"{devices[0].platform}); nothing was run")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SystemExit(f"chip_smoke: {need} TPU chips needed, "
                         f"{len(devices)} found")
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device {device} compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        from repro import configs
        report = {"four_chips": four_chip_phase(configs.get(ARCH))}
    else:
        report = {"train": train_phase(TRAIN_ARGV, clock)}
        report["train"]["peak_bytes_in_use"] = \
            devices[0].memory_stats()["peak_bytes_in_use"]
        report["serve"] = serve_phase(SERVE_ARGV, clock)
    report["compile_s"] = clock.lap() + sum(
        r.get("compile_s", 0.0) for r in report.values())
    report["wall_s"] = time.perf_counter() - t0
    mem = devices[0].memory_stats()
    report["peak_bytes_in_use"] = mem["peak_bytes_in_use"]
    report["bytes_limit"] = mem.get("bytes_limit")
    print(json.dumps(report), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
