"""The benchmark's command: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Every run is a new process.  It needs as many TPU chips as the cell asks
for and exits non-zero, printing no result, where JAX finds fewer or none.
Its last line on stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; then ``checks``, each compared number with its limit, which
also close standard error.  JAX's compile cache is kept in the checkout
(``repro.launch.compile_cache``), unless ``JAX_COMPILATION_CACHE_DIR``
names another directory.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips(n: int):
    """The first ``n`` TPU chips, or exit: no result without them."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: no accelerator ({e})")
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX sees "
                         f"{devices[0].platform}); nothing was run")
    if len(devices) < n:
        raise SystemExit(f"bench: {n} TPU chips needed, {len(devices)} found")
    return devices[:n]


def main(argv=None) -> None:
    args = parse_args(argv)
    try:
        from bench.registry import Registry
        reg = Registry(ROOT)
        from repro.launch.compile_cache import enable_compile_cache
    except (ImportError, FileNotFoundError) as e:
        raise SystemExit(f"bench: not a checkout of the repository ({e})")
    cell = reg.cell(args.workload)
    devices = chips(cell["chips"])
    import jax
    enable_compile_cache()
    # every program goes to the cache, however quick its compile, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness
    out = harness.run(reg, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, devices)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
