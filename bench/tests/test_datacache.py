"""Saved graph directories: one build per key, eviction to two entries."""
import datacache

CONFIG = {"data": {"generator": "subgen", "n_nodes": 10}, "partition": {"k": 2}}


def _builder(calls):
    def build(path):
        calls.append(path)
        (path / "graph").mkdir()
        return {"made": len(calls)}
    return build


def test_second_open_does_not_rebuild(tmp_path):
    calls = []
    p1, s1, i1 = datacache.ensure(tmp_path, CONFIG, 5, "prog", _builder(calls))
    p2, s2, i2 = datacache.ensure(tmp_path, CONFIG, 5, "prog", _builder(calls))
    assert p1 == p2 and len(calls) == 1
    assert s1 is not None and s2 is None
    assert i2["made"] == 1 and i2["build_s"] == s1


def test_changed_program_rebuilds(tmp_path):
    calls = []
    a, _, _ = datacache.ensure(tmp_path, CONFIG, 5, "prog1", _builder(calls))
    b, s, _ = datacache.ensure(tmp_path, CONFIG, 5, "prog2", _builder(calls))
    assert a != b and s is not None and len(calls) == 2


def test_program_digest_follows_the_source(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    d1 = datacache.tree_digest(tmp_path)
    (tmp_path / "m.py").write_text("x = 2\n")
    assert datacache.tree_digest(tmp_path) != d1


def test_build_key_follows_the_generator(tmp_path):
    """The key covers the program and the benchmark's own generator: a
    graph built by an older generator is not served."""
    import run
    assert run.BUILD_INPUTS == (run.ROOT / "src", run.BENCH / "datagen")
    src, gen = tmp_path / "src", tmp_path / "datagen"
    src.mkdir()
    gen.mkdir()
    (src / "m.py").write_text("x = 1\n")
    (gen / "g.py").write_text("y = 1\n")
    d1 = datacache.tree_digest(src, gen)
    assert datacache.tree_digest(src) != d1
    (gen / "g.py").write_text("y = 2\n")
    assert datacache.tree_digest(src, gen) != d1


def test_configs_with_the_same_data_share_an_entry(tmp_path):
    calls = []
    other = dict(CONFIG, store={"cache_parts": 1})
    a, _, _ = datacache.ensure(tmp_path, CONFIG, 5, "p", _builder(calls))
    b, _, _ = datacache.ensure(tmp_path, other, 5, "p", _builder(calls))
    assert a == b and len(calls) == 1


def test_eviction_keeps_the_two_most_recent(tmp_path):
    calls = []
    paths = [datacache.ensure(tmp_path, CONFIG, s, "p", _builder(calls))[0]
             for s in (1, 2, 3)]
    assert not paths[0].exists() and paths[1].exists() and paths[2].exists()
    datacache.ensure(tmp_path, CONFIG, 2, "p", _builder(calls))   # touch 2
    datacache.ensure(tmp_path, CONFIG, 4, "p", _builder(calls))
    assert paths[1].exists() and not paths[2].exists()


def test_an_interrupted_build_is_redone(tmp_path):
    calls = []
    path = datacache.entry_dir(tmp_path, CONFIG, 9, "p")
    (path / "graph").mkdir(parents=True)          # no ready marker
    p, s, _ = datacache.ensure(tmp_path, CONFIG, 9, "p", _builder(calls))
    assert p == path and s is not None and len(calls) == 1
