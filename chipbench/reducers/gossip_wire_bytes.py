"""Bytes one chip receives per step: the parameter tree's bytes on the wire
(bf16 leaves travel as bf16, every other leaf as f32) times the in-degree of
the topology.  From shapes; 0 where the step does not communicate."""

import jax
import numpy as np


def reduce(measured, params):
    cell = measured.cell
    if cell.traffic["comm"] != "neighbor":
        return 0
    topo = cell.ctx.topology
    in_degree = max(len(topo.in_neighbors(r)) for r in range(topo.size))
    wire = 0
    for leaf in jax.tree_util.tree_leaves(cell.param_shapes):
        itemsize = 2 if leaf.dtype == jax.numpy.bfloat16 else 4
        wire += int(np.prod(leaf.shape[1:])) * itemsize
    return wire * in_degree
