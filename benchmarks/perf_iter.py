"""§Perf iteration driver: lower one cell with config overrides, print the
three roofline terms and the delta vs the stored baseline artifact.

    PYTHONPATH=src python -m benchmarks.perf_iter --arch granite-34b \
        --shape decode_32k --set decode_shard_s=true [--save tag]

Like ``repro.launch.dryrun``, ``main`` stands 512 host devices in for the
production mesh (``DRYRUN_XLA_FLAGS`` overrides) before JAX starts.
"""

import argparse
import json
import os
from pathlib import Path


def parse_val(v: str):
    if v.lower() in ("true", "false"):
        return v.lower() == "true"
    try:
        return int(v)
    except ValueError:
        try:
            return float(v)
        except ValueError:
            return v


def main():
    os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS")
                               or "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (repeatable)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--save", default=None,
                    help="store artifact as artifacts/perf/<arch>_<shape>_<tag>.json")
    args = ap.parse_args()

    from benchmarks.roofline import analyze, ARTIFACTS
    from repro import configs
    from repro.launch.dryrun import lower_cell
    from repro.launch.mesh import make_production_mesh

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = parse_val(v)

    key = configs.ALIASES.get(args.arch,
                              args.arch.replace("-", "_").replace(".", "_"))
    mesh = make_production_mesh()
    rec = lower_cell(key, args.shape, mesh, overrides=overrides or None,
                     microbatches=args.microbatches)
    a = analyze(rec)
    print(json.dumps({k: (round(v, 6) if isinstance(v, float) else v)
                      for k, v in a.items()}, indent=1))
    base_file = ARTIFACTS / f"{key}_{args.shape}_pod1.json"
    if base_file.exists():
        b = analyze(json.loads(base_file.read_text()))
        for term in ("compute_s", "memory_s", "collective_s", "mem_gb"):
            if b[term]:
                print(f"  {term:13s} {b[term]:10.4f} -> {a[term]:10.4f} "
                      f"({a[term]/b[term]:.3f}x)")
        print(f"  roofline      {b['roofline_frac']:.4f} -> "
              f"{a['roofline_frac']:.4f}")
    if args.save:
        out = Path("artifacts/perf")
        out.mkdir(parents=True, exist_ok=True)
        rec["overrides"] = overrides
        (out / f"{key}_{args.shape}_{args.save}.json").write_text(
            json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
