"""MapReduceMP demo: the paper's Sec. 9 algorithm as ONE SPMD program —
4 mapper devices (one partition each), quota-based all_to_all shuffle,
global-psum stop test.  It needs four devices.  On CPU it makes four
virtual ones itself:

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/mapreduce_demo.py

On a host with four TPU chips, run it without JAX_PLATFORMS: each
partition then lives on its own chip.
"""
import os
if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import sys
sys.path.insert(0, "src")

import numpy as np
import jax

from repro.core import (EngineConfig, MAX_SN, build_catalog,
                        build_partitions, generate_plan, match_query,
                        partition_graph)
from repro.core.mapreduce_mp import MapReduceMPEngine, make_part_mesh
from repro.data.generators import subgen_like_graph, subgen_queries

graph = subgen_like_graph(n_nodes=1000, n_edges=3000, n_embed=30, seed=1)
k = 4
assign = partition_graph(graph, k, "ecosocial")
pg = build_partitions(graph, assign, k)
catalog = build_catalog(graph)
mesh = make_part_mesh(k)
print(f"graph {graph.n_nodes}/{graph.n_edges}; {k} partitions on "
      f"{jax.device_count()} devices")

engine = MapReduceMPEngine(pg, mesh, EngineConfig(cap=32768))
for dq in subgen_queries(graph):
    q = dq.disjuncts[0]
    plan = generate_plan(q, graph, catalog)
    res = engine.run(plan)
    ref = match_query(graph, q, q_pad=8)
    ok = np.array_equal(np.unique(res.answers, axis=0), ref)
    print(f"{q.name}: {res.answers.shape[0]} answers in "
          f"{res.n_iterations} map/reduce iterations "
          f"(plan max path {plan.max_path_len()}) — "
          f"{'MATCH' if ok else 'MISMATCH'} vs oracle")
