"""Serving driver: batched decode with the ownership-paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        [--full] [--requests 12] [--slots 4] [--max-new 16] [--refresh-every 8]

Demonstrates the paper's coherence protocol in the serving path:
  * shared prompt prefixes are immutably-borrowed pages (refcounted);
  * each decode step appends under a mutable borrow (color bump);
  * weight refresh is a colored-cache fetch: a writer (simulated online
    trainer) bumps the weights' color and every replica refetches lazily —
    zero invalidation messages.

The default is the reduced smoke config; ``--full`` serves the published
widths.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full", action="store_true",
                    help="the published widths, not the smoke config")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="bump weight color every N engine steps "
                    "(simulated online trainer)")
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> dict:
    """Serve every request; returns the engine, the requests in submission
    order and the wall seconds of each engine step (each ends when the
    step's tokens reach the host, so the first one includes the compile)."""
    from repro import configs
    from repro.core.jaxstate import OwnedState
    from repro.models import init_params
    from repro.serve import ServeEngine

    cfg = configs.get(args.arch) if args.full else configs.smoke(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    weights = OwnedState("weights", params)
    engine = ServeEngine(cfg, weights, slots=args.slots, max_len=256)

    rng = np.random.default_rng(0)
    shared_prefix = list(rng.integers(0, cfg.vocab, size=cfg.attn_chunk))
    reqs = []
    for i in range(args.requests):
        # half the requests share a prompt prefix (page-level sharing)
        prompt = shared_prefix + list(rng.integers(0, cfg.vocab, size=8)) \
            if i % 2 == 0 else list(rng.integers(0, cfg.vocab, size=12))
        reqs.append(engine.submit(prompt, max_new=args.max_new))

    step_s = []
    while engine.queue or engine.active:
        t0 = time.perf_counter()
        engine.step()
        step_s.append(time.perf_counter() - t0)
        if args.refresh_every and len(step_s) % args.refresh_every == 0:
            with weights.borrow_mut() as ref:      # online weight update
                ref.set(ref.deref_mut())
        if len(step_s) > 10_000:
            raise RuntimeError("engine did not drain")

    done = sum(1 for r in reqs if r.done)
    st = engine.stats()
    print(f"served {done}/{len(reqs)} requests in {st['steps']} steps")
    print(f"kv pages: {st['kv']}")
    print(f"weight refreshes: {st['weight_refreshes']} "
          f"(hits {st['weight_hits']}) — zero invalidation messages")
    if done != len(reqs):
        raise RuntimeError(f"only {done}/{len(reqs)} requests completed")
    return {"engine": engine, "requests": reqs, "stats": st,
            "step_s": step_s}


def main(argv=None) -> dict:
    from repro.launch.compile_cache import enable_compile_cache
    args = parse_args(argv)
    enable_compile_cache()
    return run(args)


if __name__ == "__main__":
    main()
