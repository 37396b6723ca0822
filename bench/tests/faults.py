"""Faults planted under a run's timed path, for the tests that see
``correct`` come out false.  Each wraps the program's jitted train step
inside a runner; the harness drives the runner as it always does."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def unchanged(step):
    """The step computes its loss but returns the state it was given."""
    def broken(params, opt_state, batch):
        copy = jax.tree.map(jnp.copy, (params, opt_state))
        _, _, metrics = step(*copy, batch)
        return params, opt_state, metrics
    return broken


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def broken(params, opt_state, batch):
        half = jax.tree.map(
            lambda x: jax.device_put(x[:x.shape[0] // 2], x.sharding), batch)
        return step(params, opt_state, half)
    return broken


FAULTS = {"unchanged": unchanged, "half_batch": half_batch}


def run_with_fault(reg, name: str, fault: str, seed: int = 5) -> dict:
    """One run of ``name`` on the CPU with ``fault`` planted."""
    from bench import harness
    from bench.tests.tiny import CPU_PEAK

    made = harness.Cell.runner

    def runner(self, seed, devices):
        r = made(self, seed, devices)
        r.ts._step = FAULTS[fault](r.ts._step)
        return r

    harness.Cell.runner = runner
    try:
        cell = reg.cell(name)
        return harness.run(reg, name, seed, 0.5, False, time.perf_counter(),
                           jax.devices()[:cell["chips"]], peak=CPU_PEAK)
    finally:
        harness.Cell.runner = made
