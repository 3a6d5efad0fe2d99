"""Saved graph directories, built once per (dataset, seed, program).

Users of the system partition a graph offline once and then serve it from
a saved graph directory (``GraphSession.save`` / ``GraphSession.open``).
The benchmark does the same: the first run of a seed builds its graph
directory under

    <root>/<dataset>/<seed>-<program>/

and every later run of that seed opens it (the harness keeps the
configuration's partition under ``bench/.cache/base/`` and the seeds'
directories under ``bench/.cache/seeds/``).  ``<dataset>`` is a digest of
the configuration's ``data`` and ``partition`` blocks, so two
configurations that serve the same data share it; ``<program>`` is a
digest of every file under ``src/`` and ``bench/datagen/``, so a changed
program or generator builds afresh.
At most ``KEEP`` entries stay: the least recently used go first.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from pathlib import Path
from typing import Callable, Optional, Tuple

KEEP = 2
READY = "ready.json"


def tree_digest(*roots: Path) -> str:
    """A digest of the relative paths and bytes of the files under each
    of ``roots`` (compiled Python caches left out)."""
    h = hashlib.sha256()
    for root in roots:
        h.update(f"{root.name}/\0".encode())
        for p in sorted(root.rglob("*")):
            if not p.is_file() or "__pycache__" in p.parts or p.suffix == ".pyc":
                continue
            h.update(str(p.relative_to(root)).encode())
            h.update(b"\0")
            h.update(p.read_bytes())
    return h.hexdigest()[:16]


def dataset_digest(config: dict) -> str:
    key = json.dumps({"data": config["data"], "partition": config["partition"]},
                     sort_keys=True)
    return hashlib.sha256(key.encode()).hexdigest()[:12]


def entry_dir(root: Path, config: dict, seed: int, program: str) -> Path:
    return root / dataset_digest(config) / f"{seed}-{program}"


def _entries(root: Path):
    if not root.is_dir():
        return []
    return [e for d in root.iterdir() if d.is_dir() for e in d.iterdir()
            if e.is_dir()]


def evict(root: Path, keep: int = KEEP, protect: Optional[Path] = None) -> None:
    """Remove all but the ``keep`` most recently used entries."""
    def used(e: Path) -> float:
        ready = e / READY
        return ready.stat().st_mtime if ready.exists() else 0.0
    entries = sorted(_entries(root), key=used, reverse=True)
    kept = [e for e in entries if e == protect][:1]
    for e in entries:
        if e in kept:
            continue
        if len(kept) < keep:
            kept.append(e)
        else:
            shutil.rmtree(e, ignore_errors=True)
    for d in root.iterdir() if root.is_dir() else []:
        if d.is_dir() and not any(d.iterdir()):
            d.rmdir()


def ensure(root: Path, config: dict, seed: int, program: str,
           build: Callable[[Path], dict]) -> Tuple[Path, Optional[float], dict]:
    """The entry for ``(config, seed, program)``, built by ``build(path)``
    on a miss.  Returns the path, the build's seconds (None on a hit) and
    what ``build`` returned, as stored on the miss."""
    path = entry_dir(root, config, seed, program)
    ready = path / READY
    if ready.exists():
        info = json.loads(ready.read_text())
        os.utime(ready)               # mark as most recently used
        evict(root, protect=path)
        return path, None, info
    shutil.rmtree(path, ignore_errors=True)   # an interrupted build
    path.mkdir(parents=True)
    t0 = time.perf_counter()
    info = build(path)
    seconds = time.perf_counter() - t0
    info = dict(info, build_s=seconds)
    ready.write_text(json.dumps(info))
    evict(root, protect=path)
    return path, seconds, info
