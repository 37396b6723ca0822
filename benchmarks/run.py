"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run [--fast|--quick]``.

Prints ``name,us_per_call,derived`` CSV — one section per paper table/figure
and the protocol micro-benchmarks.

``--quick`` is the CI smoke mode: it runs only the protocol micro-benchmarks
and the batched-I/O-plane app sweep and writes a ``BENCH_protocol.json``
summary (round trips, makespan, doorbell stats, and the open-loop serving
SLO columns — p50/p99 tail latency + goodput) so successive PRs leave a
comparable perf trajectory.
"""

from __future__ import annotations

import json
import sys


def _app_stats(r) -> dict:
    return {
        "makespan_us": round(r.makespan_us, 2),
        "round_trips": r.net["round_trips"],
        "bytes_moved": r.net["bytes_moved"],
        "doorbell_batches": r.net["doorbell_batches"],
        "batched_verbs": r.net["batched_verbs"],
        "async_writebacks": r.net["async_writebacks"],
        "fences": r.net["fences"],
        "fenced_verbs": r.net["fenced_verbs"],
        "ooo_completions": r.net["ooo_completions"],
        "qp_switches": r.net["qp_switches"],
        "speculative_fetches": r.net["speculative_fetches"],
        "late_fences": r.net["late_fences"],
        "wasted_prefetches": r.net["wasted_prefetches"],
    }


def quick(out_path: str = "BENCH_protocol.json") -> dict:
    from benchmarks import protocol_micro
    from repro.apps.dataframe import run_dataframe
    from repro.apps.gemm import run_gemm
    from repro.apps.kvstore import run_kvstore
    from repro.apps.socialnet import run_socialnet

    rows = protocol_micro.all_rows()
    summary: dict = {
        "micro": {name: {"us": round(us, 3), "derived": derived}
                  for name, us, derived in rows},
        "apps": {},
        # Multi-QP / out-of-order completion plane trajectory: makespan plus
        # the deterministic fence/ooo counters, pinned by the gate.
        "qp_sweep": protocol_micro.qp_sweep_summary(),
        # Adaptive deref coalescer vs the best static quantum budget, per
        # request mix (makespan gated within tolerance, counters exactly).
        "coalesce_sweep": protocol_micro.coalesce_summary(),
        # Crash-recovery trajectory: fail-over makespan vs (cluster size,
        # lost working set), counters pinned exactly; the SLO pair gates
        # that working-set scaling dominates cluster-size scaling.
        "recovery": protocol_micro.recovery_summary(),
        "recovery_slo": protocol_micro.recovery_slo(),
        # Serving SLO trajectory: open-loop (Poisson/bursty) tail latency
        # and goodput over the DSM-backed ServeFleet — p50/p99 higher-is-
        # worse, goodput lower-is-worse, protocol counters pinned exactly.
        "serve": protocol_micro.serve_summary(),
        # Lock-contention trajectory (spin vs delegation vs reader leases
        # at 2/8/64 servers under zipf skew): makespan within tolerance,
        # synchronization counters pinned exactly.  Delegation must keep
        # beating spin at 8+ servers (spin_over_delegate, derived).
        "lock_sweep": protocol_micro.lock_sweep_summary(),
        # Placement trajectory (static spread/packed layouts vs telemetry-
        # driven live owner migration on the zipf-skewed apps at 2-64
        # servers): makespan within tolerance, placement counters pinned
        # exactly.  Each auto row's auto_beats_static bool (strict win on
        # makespan AND round trips at 8+ servers, identical digests) is
        # gated and must not flip false.
        "placement_sweep": protocol_micro.placement_summary(),
        # Runtime-sanitizer wall-clock overhead (docs/analysis.md).  Never
        # gated — wall-clock is runner-dependent; the span_identical bools
        # document the observation-only contract (identical simulated
        # trajectory with the sanitizer on).
        "sanitize_overhead": protocol_micro.sanitize_overhead_summary(),
        "prefetch": {},
    }
    for app, fn, kw in (
        ("socialnet", run_socialnet, dict(n_requests=120)),
        ("dataframe", run_dataframe, dict(n_columns=4, chunks_per_column=8,
                                          n_ops=4, use_tbox=True)),
    ):
        entry = {}
        # "batched"/"unbatched" keep the PR-1 manual choreography planes;
        # "auto" is the runtime coalescer with zero app choreography.
        for mode, mkw in (("batched", dict(batch_io=True, coalesce="manual")),
                          ("unbatched", dict(batch_io=False,
                                             coalesce="manual")),
                          ("auto", dict(batch_io=True, coalesce="auto"))):
            entry[mode] = _app_stats(fn(4, "drust", **mkw, **kw))
        entry["rtt_ratio"] = round(
            entry["unbatched"]["round_trips"]
            / max(1, entry["batched"]["round_trips"]), 2)
        summary["apps"][app] = entry
    # Speculative-prefetch trajectory: the deferred-fence/wasted counters
    # are fully deterministic — the gate pins them exactly.
    for name, r in (
        ("gemm_prefetch", run_gemm(4, "drust", n=256, tile=64,
                                   prefetch=True)),
        ("kvstore_window8", run_kvstore(4, "drust", n_keys=256, n_ops=600,
                                        prefetch_window=8)),
    ):
        summary["prefetch"][name] = {
            "makespan_us": round(r.makespan_us, 2),
            "round_trips": r.net["round_trips"],
            "speculative_fetches": r.net["speculative_fetches"],
            "late_fences": r.net["late_fences"],
            "wasted_prefetches": r.net["wasted_prefetches"],
        }
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
    return summary


def main() -> None:
    if "--quick" in sys.argv:
        summary = quick()
        print("name,us_per_call,derived")
        for name, meta in summary["micro"].items():
            print(f"{name},{meta['us']:.2f},{meta['derived']}")
        for app, entry in summary["apps"].items():
            print(f"quick_{app}_rtt_ratio,0.00,{entry['rtt_ratio']}")
        for name, meta in summary["qp_sweep"].items():
            print(f"quick_qp_{name},{meta['makespan_us']:.2f},"
                  f"{meta['ooo_completions']}")
        for name, meta in summary["coalesce_sweep"].items():
            print(f"quick_coalesce_{name},{meta['makespan_us']:.2f},"
                  f"{meta['auto_over_best']}")
        for name, meta in summary["prefetch"].items():
            print(f"quick_prefetch_{name},{meta['makespan_us']:.2f},"
                  f"{meta['speculative_fetches']}")
        for name, meta in summary["recovery"].items():
            print(f"quick_recovery_{name},{meta['makespan_us']:.2f},"
                  f"{meta['restored_bytes']}")
        for name, meta in summary["lock_sweep"].items():
            print(f"quick_lock_{name},{meta['makespan_us']:.2f},"
                  f"{meta['round_trips']}")
        for name, meta in summary["serve"].items():
            print(f"quick_serve_{name}_p99,{meta['p99_us']:.2f},"
                  f"{meta['goodput_tok_s']}")
        for name, meta in summary["placement_sweep"].items():
            print(f"quick_placement_{name},{meta['makespan_us']:.2f},"
                  f"{meta['round_trips']}")
        slo = summary["recovery_slo"]
        print(f"quick_recovery_slo_ok,0.00,{slo['slo_ok']}")
        print("wrote BENCH_protocol.json", file=sys.stderr)
        return

    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")

    from benchmarks import paper_figs
    for name, us, derived in paper_figs.all_rows(fast=fast):
        print(f"{name},{us:.2f},{derived}")

    from benchmarks import protocol_micro
    for name, us, derived in protocol_micro.all_rows():
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
