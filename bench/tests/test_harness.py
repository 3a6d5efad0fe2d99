"""The harness end to end, on the CPU at the rehearsal sizes."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

from conftest import all_cells_spec

SPEC = all_cells_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(root, *args, timeout=300):
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"), *args],
                          cwd=root, env=ENV, capture_output=True, text=True,
                          timeout=timeout)


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in line["device"]
    tail = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    assert tail == [f"check {n} {c['value']} limit {c['limit']}"
                    for n, c in line["checks"].items()]
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_result_line(cell, checkout_with_all_cells):
    line = _result(_run(checkout_with_all_cells, "--workload", cell,
                        "--seed", "2147483659",
                        "--seconds", "3", "--trace", "0", "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"]: m["unit"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_per_layer_metrics(cell, checkout_with_all_cells):
    line = _result(_run(checkout_with_all_cells, "--workload", cell, "--seed", "5",
                        "--seconds", "3", "--trace", "1", "--rehearse"))
    assert line["correct"] is True
    # the CPU has no device plane: device metrics are left out, not faked
    want = {m["name"] for m in SPEC["per_layer"] if cell in m["workloads"]
            and m["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert "busy_s" not in line["device"]


def test_no_tpu_exits_without_a_result():
    proc = _run(run.ROOT, "--workload", CELLS[0], "--seed", "1",
                "--seconds", "3", "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_benchmark_files_alone_exit_without_a_result(tmp_path):
    """A directory with only BENCHMARK.json and bench/: no program."""
    root = tmp_path / "checkout"
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(run.ROOT / "BENCHMARK.json", root)
    proc = _run(root, "--workload", CELLS[0], "--seed", "1", "--seconds", "3",
                "--trace", "0", "--rehearse")
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def _add_cell(root, name, config, traffic):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_a_new_mix_is_a_new_file(checkout_with_all_cells):
    """An open mix at another rate, added as data alone, runs through the
    same harness."""
    root = checkout_with_all_cells
    (root / "bench" / "traffic" / "dummy-open.json").write_text(json.dumps({
        "loop": "open", "rate_qps": 6.0, "max_in_flight": 4,
        "shared_heuristic": "max-sn"}))
    _add_cell(root, "subgen-400k-k4-resident.dummy-open",
              "subgen-400k-k4-resident", "dummy-open")
    line = _result(_run(root, "--workload", "subgen-400k-k4-resident.dummy-open",
                        "--seed", "77", "--seconds", "3", "--trace", "0",
                        "--rehearse"))
    assert line["correct"] is True and line["attempted"] == 18


def test_a_new_config_is_a_new_file(checkout_with_all_cells):
    """A configuration that sets other ``EngineConfig`` fields, added as
    data alone, runs with them: the program and the reference both take
    the binding width ``q_pad`` from the file, so a width that did not
    reach either would read as mismatched rows."""
    root = checkout_with_all_cells
    base = json.loads((root / "bench" / "configs" /
                       "subgen-400k-k4-resident.json").read_text())
    base["name"] = "dummy-engine"
    base["engine"]["config"] = {"cap": 16384, "q_pad": 6, "expand_block": 256}
    (root / "bench" / "configs" / "dummy-engine.json").write_text(json.dumps(base))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy-engine",
                            "source": "https://arxiv.org/abs/1905.05384",
                            "file": "bench/configs/dummy-engine.json",
                            "reduced": [], "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    _add_cell(root, "dummy-engine.paper-closed", "dummy-engine", "paper-closed")
    proc = _run(root, "--workload", "dummy-engine.paper-closed", "--seed", "78",
                "--seconds", "3", "--trace", "0", "--rehearse")
    line = _result(proc)
    assert line["correct"] is True and line["attempted"] > 0

    cell = run.load_cell("subgen-400k-k4-resident.paper-closed", rehearse=True)
    cell.config = dict(cell.config, engine=base["engine"])
    cfg = run.engine_config(cell)
    assert (cfg.q_pad, cfg.expand_block, cfg.cap) == (6, 256, 16384)
