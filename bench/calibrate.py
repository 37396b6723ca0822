"""The readings that the limits of a cell's check are set from.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--variants fp8,half] [--out FILE]

In one process, for each seed: the program's readings, taken exactly as a
run's set-up takes them (steps 1-3 through the window's own call), against
the float32 reference; then each variant of the reference put in the
program's place (``fp8``: the control; ``half``: a fault),
against the same reference.  Prints one JSON line per seed and writes
them all to ``--out``.  It needs the cell's chips, as a run does.

A state left unchanged by the step needs no run: its change and its first
moment are zero, so ``update_gap`` and ``grad_gap`` read 1.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="fp8,half")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import check, harness
    from bench.registry import Registry
    from bench.run import chips
    from repro.launch.compile_cache import enable_compile_cache

    reg = Registry(ROOT)
    cell = harness.Cell(reg, args.workload)
    devices = chips(cell.chips)
    enable_compile_cache()
    variants = [v for v in args.variants.split(",") if v]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        pool = cell.pool(seed)
        runner = cell.runner(seed, devices)
        prog = harness.program_readings(cell, runner, pool, seed)
        exact = runner.exact_numbers()
        runner.close()
        del runner
        gc.collect()
        ref = harness.reference_readings(cell, seed, pool)
        row = {"seed": seed, "program": {**check.gaps(prog, ref), **exact},
               "loss": {"program": prog["loss"], "reference": ref["loss"]}}
        for v in variants:
            got = harness.reference_readings(cell, seed, pool, v)
            row[v] = check.gaps(got, ref)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
