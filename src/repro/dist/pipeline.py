"""Pipeline parallelism: GPipe-style microbatch scheduling over a mesh axis.

``pipeline_apply`` runs a stage function over stage-stacked parameters
(leading dim = number of stages) with the stages laid out along one mesh
axis.  Each schedule step every stage computes one microbatch and ships
its activation to the next stage with a single collective-permute — the
ML-stack analogue of the DSM channel: ownership of the activation moves,
the bytes cross the wire exactly once, and no coherence traffic follows.

Schedule shape (S stages, M microbatches): ``M + S - 1`` steps; the
pipeline "bubble" is the ``S * (S - 1)`` idle stage-steps at fill/drain,
i.e. a fraction ``(S - 1) / (M + S - 1)`` of every stage's time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def schedule_steps(n_stages: int, n_microbatches: int) -> int:
    """Total schedule steps for a GPipe fill-steady-drain schedule."""
    return n_microbatches + n_stages - 1


def bubble_stage_steps(n_stages: int, n_microbatches: int) -> int:
    """Idle (stage, step) slots: S * (M + S - 1) total minus S * M useful."""
    return n_stages * schedule_steps(n_stages, n_microbatches) \
        - n_stages * n_microbatches


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """Fraction of stage-time lost to fill/drain: (S - 1) / (M + S - 1)."""
    return bubble_stage_steps(n_stages, n_microbatches) / (
        n_stages * schedule_steps(n_stages, n_microbatches))


def _pick_axis(mesh, n_stages: int, axis_name: str | None) -> str:
    if axis_name is not None:
        return axis_name
    shape = dict(mesh.shape)
    if shape.get("pod") == n_stages:
        return "pod"
    for a, n in shape.items():
        if n == n_stages:
            return a
    raise ValueError(
        f"no mesh axis of size {n_stages} for the stage dim: {shape}")


def pipeline_apply(fn, mesh, stage_params, x, n_microbatches: int = 1,
                   axis_name: str | None = None):
    """Apply ``fn(stage_param, x) -> y`` sequentially over stacked stages.

    * ``stage_params``: pytree whose leaves carry a leading stage dim S;
      stage ``i`` runs on mesh rank ``i`` of the pipeline axis.
    * ``x``: global batch, split into ``n_microbatches`` along dim 0.
    * ``fn`` must preserve the activation shape/dtype (its output feeds
      the next stage's input).

    Returns the final-stage output for the whole batch, replicated.
    """
    leaves = jax.tree.leaves(stage_params)
    if not leaves:
        raise ValueError("stage_params has no leaves")
    S = leaves[0].shape[0]
    axis = _pick_axis(mesh, S, axis_name)
    if dict(mesh.shape)[axis] != S:
        raise ValueError(
            f"stage dim {S} != mesh axis {axis!r}={dict(mesh.shape)[axis]}")
    B = x.shape[0]
    M = int(n_microbatches)
    if M < 1 or B % M:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")
    mb = B // M
    xm = x.reshape(M, mb, *x.shape[1:])
    perm = [(i, (i + 1) % S) for i in range(S)]

    def run(p_loc, xm_loc):
        stage = jax.lax.axis_index(axis)
        p_stage = jax.tree.map(lambda l: l[0], p_loc)

        def step(t, carry):
            inp, outs = carry
            # stage 0 consumes microbatch t; the rest consume the activation
            # the previous stage shipped at the end of step t-1
            feed = xm_loc[jnp.clip(t, 0, M - 1)]
            y = fn(p_stage, jnp.where(stage == 0, feed, inp))
            # the last stage completes microbatch t-(S-1) once the fill ends
            o_idx = jnp.clip(t - (S - 1), 0, M - 1)
            done = jnp.logical_and(stage == S - 1, t >= S - 1)
            outs = jnp.where(done, outs.at[o_idx].set(y), outs)
            return jax.lax.ppermute(y, axis, perm), outs

        init = (jnp.zeros_like(xm_loc[0]), jnp.zeros_like(xm_loc))
        _, outs = jax.lax.fori_loop(0, schedule_steps(S, M), step, init)
        # only the last stage holds results; psum broadcasts them
        return jax.lax.psum(outs, axis)

    y = shard_map(run, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
                  check_vma=False)(stage_params, xm)
    return y.reshape(B, *y.shape[2:])
