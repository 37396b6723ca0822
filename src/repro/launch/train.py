"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        [--full] [--steps 50] [--batch 8] [--seq 256] [--ckpt-dir DIR] \
        [--mesh DxM]    (train sharded over a (data D, model M) mesh)
        [--fail-at N]   (inject a failure: restore from the epoch backup)

Runs the real loop: synthetic data -> ownership-wrapped train state ->
jitted step (color bump per epoch; the backup slot keeps each epoch's
arrays, so the step does not donate them) -> epoch-batched
checkpointing -> optional failure injection + recovery.  The default is
the reduced smoke config; ``--full`` trains the published widths.  With
``--mesh``, the weights are made on the mesh, sharded as ``dist.sharding``
lays them out (the dense projections over every axis, on d_model), and
the state stays there.
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full", action="store_true",
                    help="the published widths, not the smoke config")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="DxM: shard over a (data D, model M) mesh")
    ap.add_argument("--lr", type=float, default=3e-3)
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> dict:
    """Train; returns the per-step losses and wall seconds.  Each step's
    time ends when its loss reaches the host, so the first one includes
    the compile."""
    from repro import configs
    from repro.checkpoint import CheckpointManager
    from repro.dist.sharding import train_shardings
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.train import OptConfig, TrainState, synthetic_batches

    cfg = configs.get(args.arch) if args.full else configs.smoke(args.arch)
    key = jax.random.PRNGKey(0)
    mesh = None
    if args.mesh:
        mesh = make_mesh(tuple(int(n) for n in args.mesh.split("x")),
                         ("data", "model"))
        init = functools.partial(init_params, cfg)
        shardings, _, _ = train_shardings(mesh, jax.eval_shape(init, key))
        params = jax.jit(init, out_shardings=shardings)(key)
    else:
        params = init_params(cfg, key)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"batch={args.batch}x{args.seq} mesh={args.mesh or 'none'}")

    opt = OptConfig(lr=args.lr, warmup=5, decay_steps=args.steps * 2)
    ts = TrainState(cfg, opt, params, mesh=mesh,
                    microbatches=args.microbatches)
    ts.replicate()                                # §4.2.3 backup slot
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, ts.state,
                                every_n_epochs=args.ckpt_every)

    data = synthetic_batches(cfg.vocab, args.batch, args.seq,
                             prefix_len=cfg.prefix_len, d_model=cfg.d_model)
    losses, step_s = [], []
    for step in range(1, args.steps + 1):
        batch = jax.tree.map(jax.numpy.asarray, next(data))
        t0 = time.perf_counter()
        m = ts.step(batch)
        losses.append(float(m["loss"]))
        step_s.append(time.perf_counter() - t0)
        if step % 5 == 0 or step == 1:
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"color {ts.color} {step_s[-1]*1e3:.0f} ms")
        if args.fail_at and step == args.fail_at:
            print(f"!! injecting failure at step {step}; promoting backup")
            ts.restore_from_backup()

    print(f"first loss {losses[0]:.4f} -> last {losses[-1]:.4f} "
          f"({'improved' if losses[-1] < losses[0] else 'NO IMPROVEMENT'})")
    if mgr and mgr.latest():
        print(f"checkpoints: {len(mgr.saved)}, latest color {mgr.latest()[0]}")
    return {"arch": cfg.name, "losses": losses, "step_s": step_s}


def main(argv=None) -> dict:
    from repro.launch.compile_cache import enable_compile_cache
    args = parse_args(argv)
    enable_compile_cache()
    return run(args)


if __name__ == "__main__":
    main()
