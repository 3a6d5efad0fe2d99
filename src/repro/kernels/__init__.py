"""Pallas TPU kernels for PGQP-JAX hot spots.

  frontier_expand — one-edge expansion match (engine inner loop)
  label_histogram — SNI start-node counting (one-pass metric)

Each kernel ships with ops.py (jit'd wrapper; interpret mode on CPU) and
ref.py (pure-jnp oracle).  See each module's docstring for the VMEM tiling.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
