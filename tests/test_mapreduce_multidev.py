"""MapReduceMP with one partition per device — needs >1 device, so this
test runs a SUBPROCESS with xla_force_host_platform_device_count=4
(conftest must NOT set it globally; smoke tests see the real device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.core import (EngineConfig, MAX_SN, MAX_YIELD, MIN_SN,
                            build_catalog, build_partitions, generate_plan,
                            match_query, partition_graph)
    from repro.core.mapreduce_mp import MapReduceMPEngine
    from repro.data.generators import subgen_like_graph, subgen_queries

    g = subgen_like_graph(n_nodes=250, n_edges=700, n_embed=10, seed=3)
    assign = partition_graph(g, 4, "kway_shem")
    pg = build_partitions(g, assign, 4)
    cat = build_catalog(g)
    from repro.core.mapreduce_mp import make_part_mesh
    mesh = make_part_mesh(4)

    # (2, MAX_YIELD) gates expansion through the on-device completion-rate
    # ranking (all_gathered completed/spawned counters, paper Sec. 9.2)
    for m_limit, heur in [(4, MAX_SN), (2, MAX_SN), (2, MIN_SN),
                          (2, MAX_YIELD)]:
        eng = MapReduceMPEngine(pg, mesh, EngineConfig(cap=16384),
                                m_limit=m_limit, heuristic=heur)
        for dq in subgen_queries(g):
            q = dq.disjuncts[0]
            plan = generate_plan(q, g, cat)
            res = eng.run(plan)
            ref = match_query(g, q, q_pad=8)
            got = np.unique(res.answers, axis=0)
            assert got.shape == ref.shape and np.array_equal(got, ref), (
                q.name, m_limit, heur, got.shape, ref.shape)
            assert res.n_iterations >= plan.max_path_len()
            assert res.completed_from.shape == (4,)
            assert int(res.completed_from.sum()) >= ref.shape[0]

    # answer budget across 4 devices: the global-psum stop condition must
    # return exactly min(K, total) rows from the full answer set
    eng = MapReduceMPEngine(pg, mesh, EngineConfig(cap=16384))
    for dq in subgen_queries(g):
        q = dq.disjuncts[0]
        plan = generate_plan(q, g, cat)
        ref = match_query(g, q, q_pad=8)
        refset = {tuple(r) for r in ref}
        for K in (1, 5):
            res = eng.run(plan, max_answers=K)
            assert res.answers.shape[0] == min(K, ref.shape[0]), (q.name, K)
            assert all(tuple(r) in refset for r in res.answers), (q.name, K)
    print("MAPREDUCE_MULTIDEV_OK")
""")


@pytest.mark.slow
def test_mapreduce_4_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MAPREDUCE_MULTIDEV_OK" in proc.stdout
