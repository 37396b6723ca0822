"""Elastic scaling (mesh-to-mesh checkpoint restore, live protocol
rescale) and straggler mitigation (controller drains slow servers)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import Cluster


def test_straggler_detection_and_mitigation():
    cl = Cluster(4, backend="drust")
    ths = []
    for s in range(4):
        for _ in range(2):
            th = cl.main_thread(0)
            th.server = s
            ths.append(th)
    cl.sim.degrade(3, 8.0)               # server 3 throttled 8x
    assert cl.controller.detect_stragglers() == [3]
    moved = cl.controller.mitigate_stragglers()
    assert moved == 2                     # both of server 3's threads drained
    assert all(t.server != 3 for t in ths)


def test_straggler_mitigation_improves_makespan():
    def run(mitigate: bool) -> float:
        cl = Cluster(4, backend="drust")
        ths = []
        for s in range(4):
            th = cl.main_thread(0)
            th.server = s
            ths.append(th)
        cl.sim.degrade(2, 10.0)
        if mitigate:
            cl.controller.mitigate_stragglers()
        for i in range(40):               # 40 equal work items, round robin
            cl.sim.compute(ths[i % 4], 2.6e6)   # 1 ms healthy
        return cl.makespan_us()

    assert run(True) < run(False) * 0.5   # >2x makespan win


def test_straggler_heap_stays_readable():
    """Mitigation moves compute only — the straggler's partition serves."""
    cl = Cluster(3, backend="drust")
    t0 = cl.main_thread(0)
    t1 = cl.main_thread(0); t1.server = 1
    box = cl.backend.alloc(t0, 64, b"data", server=2)
    cl.sim.degrade(2, 50.0)
    cl.controller.mitigate_stragglers()
    assert cl.backend.read(t1, box) == b"data"


def test_live_protocol_rescale_in_process():
    """Shrink (crash + probe-declare + fail-over) then grow (add_server):
    the full driver behind ``python -m repro.launch.elastic --protocol``."""
    from repro.launch.elastic import run_protocol
    assert run_protocol(n_servers=4, verbose=False)


def test_probe_ladder_declares_after_miss_limit():
    """The controller declares a failing-undeclared server only after
    PROBE_MISS_LIMIT consecutive missed probes, charging the retry-timeout
    ladder to the prober's clock (degraded mode, not an instant oracle)."""
    cl = Cluster(3, backend="drust", replicate=True)
    t0 = cl.main_thread(0)
    t1 = cl.main_thread(0); t1.server = 1
    box = cl.backend.alloc(t1, 64, b"x", server=1)
    cl.replicator.flush_epoch()
    cl.recovery.crash(1)
    limit = cl.controller.PROBE_MISS_LIMIT
    t_before = t0.t_us
    for i in range(limit - 1):
        assert cl.controller.probe_failures(t0) == []
    assert 1 in cl.sim.failing and 1 not in cl.sim.failed
    assert cl.controller.probe_failures(t0) == [1]       # strike `limit`
    # declared + failed over: compute is lost, partition index rehosted
    assert 1 in cl.sim.lost and 1 in cl.sim.rehosted
    assert 1 not in cl.sim.failing and 1 not in cl.sim.failed
    assert cl.sim.net.degraded_retries >= limit
    assert t0.t_us >= t_before + limit * cl.sim.cost.retry_timeout_us
    assert cl.recovery.reports[-1].server == 1
    # sync verbs to a FAILING server burned the ladder; now that it is
    # declared and rehosted, the address serves from the promoted backup
    assert cl.backend.read(t0, box) == b"x"


def test_grow_after_shrink_controller_uses_new_server():
    """After a shrink the controller never places work on the dead member;
    after a grow it allocates on the new one."""
    cl = Cluster(3, backend="drust", replicate=True)
    t0 = cl.main_thread(0)
    for s in range(3):
        cl.backend.alloc(t0, 64, s, server=s)
    cl.replicator.flush_epoch()
    cl.recovery.fail_and_recover(2, t0)
    assert cl.sim.alive_servers() == [0, 1]
    for _ in range(8):                    # placement avoids the dead server
        assert cl.controller.pick_alloc_server(0, 64) != 2
        assert cl.controller.pick_spawn_server() != 2
    s_new = cl.add_server()
    assert s_new == 3
    assert cl.sim.alive_servers() == [0, 1, 3]
    th_new = cl.main_thread(s_new)
    nb = cl.backend.alloc(th_new, 64, "fresh", server=s_new)
    assert cl.backend.read(t0, nb) == "fresh"
    # replication covers the new member too
    cl.backend.write(th_new, nb, "fresh2")
    cl.replicator.flush_epoch()
    rep2 = cl.recovery.fail_and_recover(s_new, t0)
    assert rep2.rehomed_boxes >= 1
    assert cl.backend.read(t0, nb) == "fresh2"


def test_elastic_reshard_subprocess():
    """Checkpoint on a 2x4 mesh, restore onto 4x2 and 8x1."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for to in ("4x2", "8x1"):
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.elastic",
             "--from-mesh", "2x4", "--to-mesh", to],
            capture_output=True, text=True, timeout=600, env=env, cwd=cwd)
        assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
        assert "OK" in out.stdout
