"""Job kind `enet_path_rows`: the `enet_path` job with X's rows sharded
over the cell's chips.

At set-up the job builds a one-axis mesh over the configuration's `chips`
(as many as JAX finds, if fewer: a CPU rehearsal runs on one) and places
each problem's rows over it once, with the program's own placement
(`repro.core.distributed.shard_rows`: zero rows pad the count to a multiple
of the mesh, which changes no answer of a path that neither standardizes
nor centers). A job then calls `repro.enet_path` under
`repro.dist.mesh_context(mesh)` on the placed arrays, so no row of X moves
inside the window. Grids, answers, the reference and the comparison are
those of the `enet_path` job, on the data as generated.
"""
from __future__ import annotations

import jax

from bench.jobs import enet_path


class Job(enet_path.Job):
    def __init__(self, config: dict, traffic: dict, problems: list,
                 grids=None):
        from repro import dist
        from repro.core.distributed import shard_rows

        super().__init__(config, traffic, problems, grids)
        chips = min(int(config["chips"]), len(jax.devices()))
        self.mesh = dist.data_mesh(chips)
        self._mesh_context = dist.mesh_context
        self.problems = [jax.block_until_ready(shard_rows(self.mesh, X, y))
                         for X, y in problems]

    def _call(self, i, lambda1s):
        with self._mesh_context(self.mesh):
            return super()._call(i, lambda1s)
