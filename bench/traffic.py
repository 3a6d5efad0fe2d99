"""The one traffic generator: a mix file of parameters in, a request list out.

A mix lives in ``bench/traffic/<name>.json``.  Each request is one of the
configuration's queries, asking for all its answers.  The keys:

``loop``         ``"closed"`` (one client sends its next request when the
                 last returns) or ``"open"`` (requests are due on a
                 Poisson schedule, whatever is still running).
``rate_qps``     open loop: the offered rate.
``max_in_flight`` open loop: how many admitted requests the server works
                 on at once; later arrivals queue in the front end.
``shared_heuristic`` open loop: the scheduler's workload-level ranking.

Every seed gets the same set of work in another order, so that seeds
change the order and not the amount: queries come in rounds
that each hold every query once, shuffled by the seed, and the
gaps between open-loop arrivals are the same stratified exponential
quantiles, shuffled by the seed and scaled so that exactly
``rate_qps x seconds`` requests fall due inside the window.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Request:
    index: int
    query: str
    due_s: Optional[float]       # open loop: offset from the window's start


def load_mix(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    loop = mix.get("loop")
    if loop not in ("closed", "open"):
        raise ValueError(f"mix {name!r}: loop must be closed or open, got {loop!r}")
    if loop == "open" and not float(mix.get("rate_qps", 0)) > 0:
        raise ValueError(f"mix {name!r}: an open loop needs rate_qps > 0")
    return mix


def _rounds(choices: List, n: int, rng: np.random.Generator) -> List:
    """``n`` picks from ``choices`` in shuffled rounds, each round holding
    every choice once: equal shares whatever the seed."""
    out: List = []
    while len(out) < n:
        out.extend(choices[i] for i in rng.permutation(len(choices)))
    return out[:n]


def _gaps(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` unit-mean exponential gaps at stratified quantiles, shuffled."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-u))


def requests(mix: dict, queries: List[str], seed: int, seconds: float,
             n_closed: int = 4096) -> List[Request]:
    """The run's requests.  Closed loop: ``n_closed`` of them in order
    (the client takes as many as the window holds).  Open loop: every
    request due within ``seconds``."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "closed":
        n, due = n_closed, [None] * n_closed
    else:
        # exactly rate x seconds arrivals, at the same scaled gaps for
        # every seed: the last gap runs past the window's close
        rate = float(mix["rate_qps"])
        n = max(1, int(round(rate * seconds)))
        g = _gaps(n + 1, rng)
        due = [float(t) for t in np.cumsum(g)[:n] / g.sum() * seconds]
    names = _rounds(list(queries), n, rng)
    return [Request(index=i, query=names[i], due_s=due[i]) for i in range(n)]
