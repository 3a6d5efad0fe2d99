"""Graph generators, one module per configuration ``data.generator``,
and the renumbering that makes a run's graph from its seed.

A configuration serves one graph, drawn by its generator from the data
block's own ``seed``.  A run's ``--seed`` renumbers that graph's vertices
by a permutation drawn from it: every seed serves the same graph, with
the same partitions, the same padded sizes and the same work, in another
order of vertex ids and of rows on the device.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def permutation(n: int, seed: int) -> np.ndarray:
    """``perm[v]`` is the new id of vertex ``v``; drawn from its own stream
    of ``seed``, apart from the traffic's."""
    return np.random.default_rng([seed, 1]).permutation(n).astype(np.int32)


def relabel(ga, perm: np.ndarray):
    """The graph ``ga`` (a ``GraphArrays``) with vertex ``v`` renamed
    ``perm[v]``; edges keep their order."""
    node_label = np.empty_like(ga.node_label)
    node_label[perm] = ga.node_label
    node_value = np.empty_like(ga.node_value)
    node_value[perm] = ga.node_value
    return dataclasses.replace(ga, node_label=node_label, node_value=node_value,
                               edge_src=perm[ga.edge_src],
                               edge_dst=perm[ga.edge_dst])
