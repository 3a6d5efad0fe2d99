"""The benchmark's Subgen generator: deterministic per seed, and the
distribution of ``repro.data.generators.subgen_like_graph``."""
import numpy as np
import pytest

from datagen import subgen

P = {"generator": "subgen", "n_nodes": 5000, "n_edges": 15000,
     "n_vlabels": 10, "n_elabels": 20, "n_embed": 40}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_deterministic_per_seed(seed):
    a, b = subgen.generate(P, seed), subgen.generate(P, seed)
    for f in ("node_label", "edge_src", "edge_dst", "edge_label"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    c = subgen.generate(P, seed + 1)
    assert not np.array_equal(a.edge_src, c.edge_src)


def test_shape_of_the_graph():
    g = subgen.generate(P, 3)
    n, m = P["n_nodes"], P["n_embed"]
    assert g.n_nodes == n + 4 * m
    assert g.n_edges == 3 * m + P["n_edges"] + m
    labels = [g.node_vocab[i] for i in g.node_label]
    assert all(s.startswith("v") for s in labels[:n])
    assert labels[n:n + 4] == list(subgen.TEMPLATE_LABELS)
    # template edges first, in the original's order
    first = [(int(g.edge_src[i]) - n, int(g.edge_dst[i]) - n,
              g.edge_vocab[g.edge_label[i]]) for i in range(3)]
    assert first == [(0, 1, "e_ab"), (1, 2, "e_bc"), (1, 3, "e_bd")]
    bg = slice(3 * m, 3 * m + P["n_edges"])
    assert (g.edge_src[bg] != g.edge_dst[bg]).all()
    assert g.edge_label[bg].max() < P["n_elabels"]
    # tie edges: background vertex -> the instance's tmpl_A vertex
    tie = slice(3 * m + P["n_edges"], None)
    assert (g.edge_src[tie] < n).all()
    assert np.array_equal(g.edge_dst[tie], n + 4 * np.arange(m))
    assert not g.edge_directed.any() and np.isnan(g.node_value).all()


def test_same_distribution_as_the_program_generator():
    """Label and endpoint frequencies agree with the original's within
    sampling noise."""
    from repro.data.generators import subgen_like_graph
    ours = subgen.generate(P, 11)
    theirs = subgen_like_graph(**{k: v for k, v in P.items()
                                  if k != "generator"}, seed=11)
    n = P["n_nodes"]
    f1 = np.bincount(ours.node_label[:n], minlength=P["n_vlabels"]) / n
    names = [theirs.node_vocab.str_of(int(i)) for i in theirs.node_label[:n]]
    f2 = np.bincount([int(s[1:]) for s in names], minlength=P["n_vlabels"]) / n
    assert np.abs(f1 - 1 / P["n_vlabels"]).max() < 0.02
    assert np.abs(f2 - 1 / P["n_vlabels"]).max() < 0.02
    d1 = np.bincount(np.concatenate([ours.edge_src, ours.edge_dst]),
                     minlength=ours.n_nodes)
    d2 = np.bincount(np.concatenate([theirs.edge_src, theirs.edge_dst]),
                     minlength=theirs.n_nodes)
    assert abs(d1.mean() - d2.mean()) < 1e-9
    assert abs(d1.std() - d2.std()) < 0.1 * d2.std()


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_renumbering_keeps_the_graph(seed):
    """A run's graph is the configuration's graph with its vertices
    renamed: the same labels, the same edges, other ids."""
    import datagen
    g = subgen.generate(P, 3)
    perm = datagen.permutation(g.n_nodes, seed)
    assert np.array_equal(perm, datagen.permutation(g.n_nodes, seed))
    assert not np.array_equal(perm, datagen.permutation(g.n_nodes, seed + 1))
    h = datagen.relabel(g, perm)
    assert np.array_equal(h.node_label[perm], g.node_label)
    assert np.array_equal(h.edge_src, perm[g.edge_src])
    assert np.array_equal(h.edge_dst, perm[g.edge_dst])
    assert np.array_equal(h.edge_label, g.edge_label)
    assert not np.array_equal(h.node_label, g.node_label)


def test_every_seed_serves_the_same_partitions(tmp_path, monkeypatch):
    """Two seeds build partitions of the same sizes, so one compiled
    evaluator serves both, and their reference answers are one set
    renamed."""
    import run
    cell = run.load_cell("subgen-400k-k4-resident.paper-closed", rehearse=True)
    monkeypatch.setattr(run, "BENCH", tmp_path)
    info, refs, perms = [], [], []
    for seed in (4, 2**31 + 9):
        (tmp_path / str(seed)).mkdir()
        info.append(run.build(cell.config, seed, tmp_path / str(seed)))
        ga, perm = run.seed_graph(cell.config, seed)
        refs.append(run.reference.match_all(ga, cell.queries, 8))
        perms.append(perm)
    for key in ("node_pad", "ell_width", "cut_edges", "part_bytes"):
        assert info[0][key] == info[1][key], key
    inv_a = np.argsort(perms[0])
    for name, a in refs[0].items():
        moved = np.where(a >= 0, perms[1][inv_a[np.maximum(a, 0)]], -1)
        assert np.array_equal(np.unique(moved, axis=0), refs[1][name]), name
