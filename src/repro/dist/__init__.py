"""Distributed execution rules for the JAX side of the reproduction.

This package is the ML-stack analogue of the DSM core's "global heap +
per-server sharded ownership" (DESIGN §2.2): a single *logical* view of
every tensor (the PGAS address space) plus a per-mesh partition map that
says which server owns which shard.  Three submodules:

* ``sharding``    — the partition map: name-based parameter rules,
                    batch/cache/activation specs, divisor fitting; the mesh
                    is always an argument.
* ``collectives`` — per-chip wire bytes of a compiled program's
                    collectives, parsed from its HLO text.
* ``compression`` — int8 wire/checkpoint compression with error bounds
                    compatible with error-feedback accumulation.
"""

from . import collectives, compression, sharding

__all__ = ["collectives", "compression", "sharding"]
