"""Four chips: ``repro.train.TrainState.step`` over a (data 2, model 2)
mesh, as ``repro.launch.train --mesh 2x2`` runs it, with no backup slot.

The weights are made on the mesh from the seed, in the shardings the
program gives them (``dist.sharding.train_shardings``, which ``TrainState``
and ``dryrun`` use too); the ``TrainState`` makes its AdamW state beside
them.  Every step is one ownership epoch, and since no slot holds the
state the step donates its buffers.  The host batch is put on the mesh by
the ``TrainState`` itself.
"""

from __future__ import annotations

import jax

MESH = ((2, 2), ("data", "model"))


class Runner:
    def __init__(self, model_cfg, opt_cfg, init, key, cell: dict, devices):
        from repro.dist.sharding import train_shardings
        from repro.launch.mesh import make_mesh
        from repro.train import TrainState
        mesh = make_mesh(*MESH, devices=devices)
        shardings, _, _ = train_shardings(mesh, jax.eval_shape(init, key))
        params = jax.jit(init, out_shardings=shardings)(key)
        self.ts = TrainState(model_cfg, opt_cfg, params, mesh=mesh)
        self.metrics = {}

    def step(self, batch: dict) -> float:
        """Host batch in; the step's loss once it has reached the host."""
        self.metrics = self.ts.step(batch)
        return float(self.metrics["loss"])

    def grad_norm(self) -> float:
        return float(self.metrics["grad_norm"])

    def params(self):
        return self.ts.state.read()[0]

    def first_moment(self):
        return self.ts.state.read()[1]["mu"]

    def exact_numbers(self) -> dict:
        """Nothing is kept beside the state: no exact numbers."""
        return {}

    def close(self) -> None:
        del self.ts
