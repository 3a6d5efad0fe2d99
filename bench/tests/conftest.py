"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
checkout's root, on the CPU (``JAX_PLATFORMS=cpu``).  The repo's tier-1
suite collects only ``tests/`` and does not run these.

Cells that are built but not yet admitted to ``BENCHMARK.json`` (see
PERF.md) are tested through a checkout whose ``BENCHMARK.json`` adds them:
``checkout_with_all_cells``."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

OOC1 = "subgen-400k-k4-ooc1"
PENDING = {
    "configs": [
        {"name": OOC1, "source": "https://arxiv.org/abs/1905.05384",
         "file": f"bench/configs/{OOC1}.json", "reduced": [],
         "why": "the graph one partition at a time"}],
    "workloads": [
        {"name": f"{OOC1}.paper-closed", "config": OOC1,
         "traffic": "paper-closed", "chips": 1, "why": "the store"},
        {"name": "subgen-400k-k4-resident.paper-open",
         "config": "subgen-400k-k4-resident", "traffic": "paper-open",
         "chips": 1, "why": "the scheduler"}],
    "per_layer": [
        {"name": "frontend.queue_wait_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "front end",
         "moves": "latency_p90_ms",
         "workloads": ["subgen-400k-k4-resident.paper-open"]},
        {"name": "loads_per_query.open", "unit": "loads/query",
         "better": "lower", "source": "program_counter",
         "layer": "scheduler", "moves": "qps",
         "workloads": ["subgen-400k-k4-resident.paper-open"]}],
}
CLOSED_METRICS = ("loads_per_query.closed",)


def all_cells_spec() -> dict:
    """``BENCHMARK.json`` with the pending cells and metrics added."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in PENDING.items():
        have = {e["name"] for e in spec[key]}
        spec[key] += [e for e in entries if e["name"] not in have]
    cells = [w["name"] for w in spec["workloads"]]
    for m in spec["per_layer"]:
        if m["name"] in ("frontend.queue_wait_ms", "loads_per_query.open"):
            continue
        want = [c for c in cells if not (m["name"] in CLOSED_METRICS
                                         and c.endswith("paper-open"))]
        m["workloads"] = sorted(set(m["workloads"]) | set(want))
    return spec


@pytest.fixture
def checkout_with_all_cells(tmp_path):
    """A checkout whose ``BENCHMARK.json`` holds every built cell: a copy
    of the benchmark, with the program linked in."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (root / "BENCHMARK.json").write_text(json.dumps(all_cells_spec()))
    os.symlink(ROOT / "src", root / "src")
    return root
