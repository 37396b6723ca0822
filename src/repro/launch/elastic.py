"""Elastic scaling: checkpoint reshard AND live protocol-runtime rescale.

    PYTHONPATH=src python -m repro.launch.elastic --arch qwen3-0.6b \
        --from-mesh 2x4 --to-mesh 4x2
    PYTHONPATH=src python -m repro.launch.elastic --protocol

Two paths to the same DSM promise (the global address space stays fixed
while the membership changes, DESIGN §2.2):

* **checkpoint reshard** (default) — checkpoints store logical
  (path -> global shape) leaves, the PGAS view, so restoring onto any mesh
  is just re-partitioning.
* **live protocol rescale** (``--protocol``) — no checkpoint round trip:
  a server *crashes* under a live drust runtime (shrink), the controller's
  probe loop declares it and the ``RecoveryManager`` fails it over
  (quiesce / re-home / restripe — flushed data stays readable at its
  original addresses), then the cluster *grows* with ``add_server`` and
  keeps allocating on the new member.
"""

import argparse
import os
import tempfile

import jax
import numpy as np


def run(arch: str = "qwen3-0.6b", from_mesh=(2, 4), to_mesh=(4, 2),
        verbose: bool = True) -> bool:
    from repro import configs
    from repro.checkpoint import restore, save
    from repro.dist.sharding import param_specs
    from repro.launch.mesh import make_mesh
    from repro.models import init_params

    cfg = configs.smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))

    mesh_a = make_mesh(from_mesh, ("data", "model"))
    specs_a = param_specs(mesh_a, params)
    sharded_a = jax.tree.map(
        lambda x, s: jax.device_put(x, jax.sharding.NamedSharding(mesh_a, s)),
        params, specs_a)

    with tempfile.TemporaryDirectory() as d:
        save(f"{d}/ck", sharded_a, color=3)

        mesh_b = make_mesh(to_mesh, ("data", "model"))
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        specs_b = param_specs(mesh_b, like)
        restored, manifest = restore(f"{d}/ck", like, mesh=mesh_b,
                                     specs=specs_b)

    ok = manifest["color"] == 3
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        ok &= bool(np.allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=1e-6))
    if verbose:
        print(f"elastic reshard {from_mesh} -> {to_mesh}: "
              f"{'OK' if ok else 'MISMATCH'} (epoch color {manifest['color']})")
    return ok


def run_protocol(n_servers: int = 4, verbose: bool = True) -> bool:
    """Live rescale of a running protocol cluster: crash server ``n-1``,
    probe-detect + fail over, verify flushed data survives at its original
    addresses, then grow by one server and allocate on it."""
    from repro.core import Cluster, ServerLostError

    cl = Cluster(n_servers, "drust", replicate=True, qps_per_thread=2,
                 ooo=True, coalesce="auto")
    ths = [cl.main_thread(s) for s in range(n_servers)]
    victim = n_servers - 1

    # Populate every server, mutate, and flush the epoch (train-step edge).
    boxes = []
    for s, th in enumerate(ths):
        for i in range(8):
            b = cl.backend.alloc(th, 256, i + 100 * s, server=s)
            cl.backend.write(th, b, i + 1000 * s)
            boxes.append((s, i, b))
    cl.replicator.flush_epoch()
    dirty = cl.backend.alloc(ths[victim], 256, "dirty", server=victim)
    cl.backend.write(ths[victim], dirty, "unflushed")    # will be lost

    # Shrink: crash + probe loop until declared, recovery runs.
    cl.recovery.crash(victim)
    probe_th = ths[0]
    declared: list = []
    while not declared:
        declared = cl.controller.probe_failures(probe_th)
    report = cl.recovery.reports[-1]
    ok = declared == [victim] and report.server == victim
    ok &= report.rehomed_boxes >= 8 and report.lost_writes >= 1

    # Flushed data is readable at its original addresses, served by the
    # promoted backup; the unflushed write reverted to its flushed epoch.
    for s, i, b in boxes:
        ok &= cl.backend.read(ths[0], b) == i + 1000 * s
    try:
        cl.backend.read(ths[0], dirty)
        got_lost = True          # restored from replica map?  It never flushed
    except ServerLostError:
        got_lost = False
    ok &= not got_lost

    # Grow: a fresh server joins and takes allocations + traffic.
    s_new = cl.add_server()
    th_new = cl.main_thread(s_new)
    nb = cl.backend.alloc(th_new, 256, "fresh", server=s_new)
    ok &= cl.backend.read(ths[0], nb) == "fresh"
    ok &= s_new == n_servers and len(cl.sim.alive_servers()) == n_servers

    if verbose:
        print(f"elastic protocol rescale {n_servers}->"
              f"{n_servers - 1}->{n_servers}: {'OK' if ok else 'MISMATCH'} "
              f"(rehomed {report.rehomed_boxes}, orphans "
              f"{report.orphaned_cids}, makespan "
              f"{report.makespan_us:.1f}us)")
    return ok


def main():
    # the reshard meshes need 8 devices: host devices stand in for them
    # (set before JAX starts its backend; an XLA_FLAGS already given wins)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--from-mesh", default="2x4")
    ap.add_argument("--to-mesh", default="4x2")
    ap.add_argument("--protocol", action="store_true",
                    help="live protocol-runtime rescale (crash + fail-over "
                         "+ grow) instead of a checkpoint reshard")
    ap.add_argument("--servers", type=int, default=4)
    a = ap.parse_args()
    if a.protocol:
        assert run_protocol(a.servers)
        return
    parse = lambda s: tuple(int(x) for x in s.split("x"))
    assert run(a.arch, parse(a.from_mesh), parse(a.to_mesh))


if __name__ == "__main__":
    main()
