"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--multi-pod] [--mesh 2x4] [--smoke] [--out artifacts/dryrun]

For each cell this lowers the *real* train_step (params + optimizer update,
donated) or serve_step (one token against a seq_len cache), compiles it for
the 16x16 (or 2x16x16) mesh, and records:
  * compiled.memory_analysis()  -> per-device bytes (proves it fits)
  * compiled.cost_analysis()    -> HLO flops / bytes for the roofline
  * collective bytes by op kind -> parsed from the partitioned HLO

The production meshes are stood in for by 512 host devices
(``DRYRUN_XLA_FLAGS`` overrides the XLA flags); ``main`` sets them before
JAX starts its backend, and importing this module changes nothing.
"""

import argparse
import json
import os
import re
import time
from pathlib import Path


def _dtype_bytes(name: str) -> float:
    return {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8, "c64": 8, "c128": 16}.get(name, 4)


_SHAPE_RE = re.compile(r"(pred|[us]\d+|bf16|f16|f32|f64|c64|c128)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?P<rtype>[^=]*?)\s*"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start|-done)?\(")


def _group_size(line: str, default: int = 2) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)     # iota v2
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)      # explicit
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, while_mult: int = 1) -> dict:
    """Per-device *wire* bytes per collective kind, from partitioned HLO.

    Result-type bytes R, group size G, ring algorithms:
      all-reduce: 2(G-1)/G x R   all-gather: (G-1)/G x R_out
      reduce-scatter: (G-1) x R_out   all-to-all: (G-1)/G x R
      collective-permute: R
    Ops inside while bodies (scan over layers) are multiplied by
    ``while_mult`` (the scan trip count) — the body appears once in text
    but executes every step.
    """
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m or m.group("start") == "-done":
            continue
        kind = m.group("kind")
        shapes = _SHAPE_RE.findall(m.group("rtype"))
        if not shapes:
            shapes = _SHAPE_RE.findall(line.split("(")[0])
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _dtype_bytes(dt)
        G = _group_size(line)
        ring = {"all-reduce": 2.0 * (G - 1) / G,
                "all-gather": (G - 1) / G,
                "reduce-scatter": float(G - 1),
                "all-to-all": (G - 1) / G,
                "collective-permute": 1.0}[kind]
        mult = while_mult if "/while/" in line or "while" in line.split(
            "metadata", 1)[-1] else 1
        out[kind] += nbytes * ring * mult
    return {k: int(v) for k, v in out.items()}


def layer_trips(cfg, microbatches: int = 1) -> int:
    """How often one step runs its layer scan's body: the ``while_mult``
    of ``collective_bytes``."""
    period = cfg.attn_every or 1
    n_steps = (cfg.n_layers // period) if cfg.scan_layers else 1
    return max(1, n_steps * max(1, microbatches))


# §Perf-confirmed per-cell optimization policy (EXPERIMENTS §4): the
# paper-faithful rules stay the default; --optimized applies these.
SMALL_DENSE = {"qwen3_0_6b", "starcoder2_3b", "gemma_7b", "musicgen_medium",
               "rwkv6_3b", "pixtral_12b"}


def optimized_overrides(arch: str, shape: str) -> dict:
    from repro.configs import SHAPES
    mode = SHAPES[shape][2]
    ov = {}
    if mode == "decode":
        ov["serve_weights_tp_only"] = True
        if shape != "long_500k":
            ov["decode_shard_s"] = True
    elif mode == "train" and arch in SMALL_DENSE:
        ov["dp_only"] = True
    if arch in ("qwen3_moe_235b", "arctic_480b") and mode != "decode":
        ov["moe_a2a"] = True
    return ov


def input_specs(cfg, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    from repro.configs import SHAPES
    from repro.models import build_batch_spec
    seq, batch, mode = SHAPES[shape_name]
    return build_batch_spec(cfg, batch, seq, mode=mode), (seq, batch, mode)


def _cost_of(lowered_or_compiled) -> dict:
    try:
        ca = lowered_or_compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception:                                   # pragma: no cover
        return {}


def sharded_train_step(cfg, opt, mesh, params_abs, batch_abs, *,
                       microbatches: int = 1):
    """The train step as a ``TrainState`` on ``mesh`` compiles it
    (donating variant), with the inputs' shardings as well: shardings
    from ``train_shardings``, the jit from ``jit_train_step``, the remat
    policy from ``compile_fitting``.

    Returns (``Fitted``: policy, saved bytes, jitted step and its compiled
    program; abstract optimizer state; (param, opt_state, batch)
    NamedSharding trees).
    """
    import functools

    import jax

    from repro.dist.sharding import train_shardings
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import (compile_fitting, jit_train_step,
                                        make_train_step)

    opt_abs = jax.eval_shape(functools.partial(init_opt_state, opt),
                             params_abs)
    shardings = train_shardings(mesh, params_abs, opt_abs, batch_abs)
    fitted = compile_fitting(
        lambda policy: jit_train_step(
            make_train_step(cfg, opt, mesh, microbatches, policy), shardings),
        (params_abs, opt_abs, batch_abs), cfg, mesh, microbatches)
    return fitted, opt_abs, shardings


def lower_cell(arch: str, shape_name: str, mesh, *, opt_name: str | None = None,
               smoke: bool = False, compile_: bool = True,
               microbatches: int = 1, verbose: bool = True,
               calibrate: bool = True, overrides: dict | None = None) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro import configs
    from repro.dist.sharding import (batch_specs, cache_specs, param_specs,
                                     set_mesh)
    from repro.models import init_cache, init_params
    from repro.serve.serve_step import make_serve_step
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import make_train_step

    import dataclasses

    cfg = configs.smoke(arch) if smoke else configs.get(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    from repro.dist.sharding import set_rule_flags
    set_rule_flags(ulysses=cfg.ulysses,
                   serve_weights=cfg.serve_weights_tp_only,
                   dp_only=cfg.dp_only)
    batch_abs, (seq, batch, mode) = input_specs(cfg, shape_name)
    if smoke:
        seq, batch = min(seq, 256), min(batch, max(8, 1))
        from repro.models import build_batch_spec
        batch_abs = build_batch_spec(cfg, batch, seq, mode=mode)

    set_mesh(mesh)
    ns = lambda spec: NamedSharding(mesh, spec)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
    opt = OptConfig(name=opt_name or
                    ("adafactor" if arch == "arctic_480b" else "adamw"))

    remat_policy = None

    def build(cfg2):
        """Lower one variant; returns (lowered, abstract param tree).  A
        train step compiles here already, to choose its remat policy."""
        nonlocal remat_policy
        params_abs = jax.eval_shape(functools.partial(init_params, cfg2),
                                    jax.random.PRNGKey(0))
        if mode == "train":
            fitted, opt_abs, _ = sharded_train_step(
                cfg2, opt, mesh, params_abs, batch_abs,
                microbatches=microbatches)
            remat_policy = fitted.policy
            return fitted.jitted.lower(params_abs, opt_abs,
                                       batch_abs), params_abs
        p_shard = jax.tree.map(ns, param_specs(mesh, params_abs))
        b_shard = jax.tree.map(ns, batch_specs(mesh, batch_abs))
        if mode == "prefill":
            from repro.serve.serve_step import make_prefill
            fn = make_prefill(cfg2, mesh=mesh)
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
            return jitted.lower(params_abs, batch_abs), params_abs
        cache_abs = jax.eval_shape(
            functools.partial(init_cache, cfg2, batch, seq))
        c_shard = jax.tree.map(ns, cache_specs(mesh, cache_abs),
                               is_leaf=is_spec)
        fn = make_serve_step(cfg2, mesh=mesh)
        jitted = jax.jit(fn,
                         in_shardings=(p_shard, c_shard, b_shard["tokens"]),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,))
        return jitted.lower(params_abs, cache_abs,
                            batch_abs["tokens"]), params_abs

    t0 = time.time()
    lowered, params_abs = build(cfg)
    rec = {"arch": arch, "shape": shape_name, "mode": mode,
           "mesh": dict(mesh.shape), "seq": seq, "batch": batch,
           "params": int(sum(int(jnp.prod(jnp.array(l.shape)))
                             for l in jax.tree.leaves(params_abs))),
           "active_params": cfg.active_param_count(),
           "lower_s": round(time.time() - t0, 2)}
    if not compile_:
        set_mesh(None)
        return rec

    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:                              # pragma: no cover
        rec["memory"] = {"error": str(e)}
    rec["cost_raw"] = {k: v for k, v in _cost_of(compiled).items()
                       if "flops" in k or "bytes accessed" == k}

    # XLA's cost analysis counts a while body ONCE, independent of trip
    # count.  True FLOPs are extrapolated from two *unrolled, single-device*
    # variants (1 and 2 layer-groups; einsum attention -- identical T^2 math
    # to the chunked path; python-loop experts/chunks): F(L) = base+slope*L.
    period = cfg.attn_every or 1
    n_steps = (cfg.n_layers // period) if cfg.scan_layers else 1
    n_dev = mesh.size
    if calibrate and n_steps > 2 and not smoke:
        def build_cal(n_layers):
            cfg2 = dataclasses.replace(
                cfg, n_layers=n_layers, scan_layers=False,
                attn_chunk=max(cfg.attn_chunk, seq + 1),
                unroll_chunks=True, unroll_experts=True)
            set_mesh(None)
            p_abs = jax.eval_shape(functools.partial(init_params, cfg2),
                                   jax.random.PRNGKey(0))
            if mode == "train":
                o_abs = jax.eval_shape(
                    functools.partial(init_opt_state, opt), p_abs)
                fn = make_train_step(cfg2, opt, mesh=None,
                                     remat_policy=remat_policy)
                return jax.jit(fn).lower(p_abs, o_abs, batch_abs)
            if mode == "prefill":
                from repro.serve.serve_step import make_prefill
                fn = make_prefill(cfg2, mesh=None)
                return jax.jit(fn).lower(p_abs, batch_abs)
            c_abs = jax.eval_shape(
                functools.partial(init_cache, cfg2, batch, seq))
            fn = make_serve_step(cfg2, mesh=None)
            return jax.jit(fn).lower(p_abs, c_abs, batch_abs["tokens"])

        c1 = _cost_of(build_cal(period).compile())
        c2 = _cost_of(build_cal(2 * period).compile())
        set_mesh(mesh)
        cal = {}
        for k in ("flops", "bytes accessed"):
            if k in c1 and k in c2:
                slope = (c2[k] - c1[k]) / period
                total = c1[k] - slope * period + slope * cfg.n_layers
                cal[k.replace(" ", "_")] = max(total / n_dev,
                                               rec["cost_raw"].get(k, 0.0))
        rec["cost"] = cal
        rec["cost"]["calibrated"] = True
    else:
        rec["cost"] = {
            "flops": rec["cost_raw"].get("flops", 0.0),
            "bytes_accessed": rec["cost_raw"].get("bytes accessed", 0.0),
            "calibrated": False}

    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(
        hlo, while_mult=layer_trips(cfg, microbatches))
    rec["hlo_lines"] = hlo.count("\n")
    set_mesh(None)
    if verbose:
        flops = rec.get("cost", {}).get("flops", 0)
        print(f"  [{arch} x {shape_name}] lower {rec['lower_s']}s "
              f"compile {rec['compile_s']}s flops/dev {flops:.3e} "
              f"coll {sum(rec['collectives'].values())/1e6:.1f}MB")
    return rec


def main():
    os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS")
                               or "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run single-pod AND multi-pod")
    ap.add_argument("--mesh", default=None,
                    help="override mesh, e.g. 2x4 (axes data,model) or "
                    "2x2x2 (pod,data,model)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf-confirmed per-cell flags; artifacts"
                    " are tagged _opt")
    args = ap.parse_args()

    from repro import configs
    from repro.launch.mesh import make_mesh, make_production_mesh

    cells = configs.cells()
    if args.arch:
        key = configs.ALIASES.get(args.arch,
                                  args.arch.replace("-", "_").replace(".", "_"))
        cells = [c for c in cells if c[0] == key]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    if not cells:
        raise SystemExit("no cells selected")

    meshes = []
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        meshes.append(("custom", make_mesh(dims, axes)))
    elif args.both:
        meshes = [("pod1", make_production_mesh()),
                  ("pod2", make_production_mesh(multi_pod=True))]
    elif args.multi_pod:
        meshes.append(("pod2", make_production_mesh(multi_pod=True)))
    else:
        meshes.append(("pod1", make_production_mesh()))

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = []
    for mesh_name, mesh in meshes:
        for arch, shape in cells:
            tag = f"{arch}_{shape}_{mesh_name}" \
                + ("_opt" if args.optimized else "") \
                + ("_smoke" if args.smoke else "")
            if args.skip_existing and (outdir / f"{tag}.json").exists():
                print(f"== {tag} (cached)")
                continue
            print(f"== {tag} (mesh {dict(mesh.shape)})")
            try:
                ov = optimized_overrides(arch, shape) if args.optimized \
                    else None
                rec = lower_cell(arch, shape, mesh, smoke=args.smoke,
                                 microbatches=args.microbatches,
                                 overrides=ov)
                print(json.dumps({k: rec[k] for k in
                                  ("memory", "cost", "collectives")
                                  if k in rec}, indent=None)[:400])
                (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
            except Exception as e:
                import traceback
                traceback.print_exc()
                failures.append((tag, repr(e)))
    if failures:
        print(f"\nFAILED {len(failures)} cells:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        raise SystemExit(1)
    print(f"\nALL {len(cells) * len(meshes)} cells OK")


if __name__ == "__main__":
    main()
