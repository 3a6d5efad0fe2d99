"""Resource profiling — memory accounting, kernel cost attribution, and
SLO burn-rate monitoring on top of the PR 9 tracing plumbing.

The paper's whole premise is that *resources* (device memory, load
bandwidth) are the binding constraint; PR 9 made the system observable in
*time*.  This module closes the gap with three read-only instruments:

  memory accounting   ``ResourceProfiler.sample_device`` stamps the
                      store's live device bytes onto a closing span
                      (``store.load``/``kernel.eval``) and tracks the
                      session-level peak; ``observe_rss`` samples the
                      process peak RSS from ``getrusage``.  Byte *flows*
                      (cold/prefetch/disk/host-cache traffic) are already
                      counted by ``LoadStats``; the profiler adds the
                      *stock* — what is resident right now.
  cost attribution    ``attribute_kernel`` lowers a jitted evaluator once
                      per compiled bucket (abstract lowering — nothing
                      executes), runs ``launch/hlo_cost.analyze_hlo_text``
                      over the HLO, and folds the FLOPs/bytes estimate
                      through the roofline model
                      (``launch/hlo_analysis.RooflineTerms``) with the
                      peaks of the running device's kind; a kind with no
                      published peaks gets no bound ("not measured").
                      ``stamp_kernel`` then writes the per-key cost onto
                      every ``kernel.eval`` span, so a trace joins
                      *predicted* cost with *measured* wall time —
                      ``tools/trace_report.py --cost`` renders the
                      achieved-vs-predicted table.
  SLO burn rate       ``SloBurnMonitor`` keeps a rolling window of
                      deadline outcomes per SLO class; burn rate is the
                      window's miss fraction over the error budget
                      (burn > 1 → the budget is being spent faster than
                      it accrues — Google SRE workbook semantics).

Discipline is identical to ``trace.NULL_TRACER``: every hot-path call
site holds a profiler reference that is ``NULL_PROFILER`` when profiling
is off, so the disabled path costs ~a method call and profiling on/off
is answer-invariant (tests/test_profiling.py proves parity and the <5%
overhead gate).  All failures inside the profiler degrade to zeroed
attributions — profiling must never break serving.
"""
from __future__ import annotations

import collections
import resource
from typing import Any, Deque, Dict, Optional, Tuple


def _key_str(key: Any) -> str:
    """Canonical string form of a kernel bucket key (tuples stay readable:
    ('opat', 'eval') -> 'opat:eval', ('scheduler.tmp', 8) -> 'scheduler.tmp:8')."""
    if isinstance(key, tuple):
        return ":".join(str(k) for k in key)
    return str(key)


class NullResourceProfiler:
    """The disabled path: every method is a no-op, shared as the module
    singleton ``NULL_PROFILER`` so call sites never branch."""

    __slots__ = ()
    enabled = False

    def sample_device(self, span: Any, store: Any) -> None:
        pass

    def observe_rss(self) -> int:
        return 0

    def attribute_kernel(self, key: Any, fn: Any, *args: Any) -> None:
        pass

    def stamp_kernel(self, span: Any, key: Any) -> None:
        pass

    def snapshot(self) -> Dict[str, Any]:
        return {"enabled": False}


NULL_PROFILER = NullResourceProfiler()


class ResourceProfiler:
    """Collects resource facts for one session; owned by ``GraphSession``
    (built automatically whenever a real ``Tracer`` is attached) and
    threaded to the store and every engine the same way the tracer is."""

    enabled = True

    def __init__(self, tracer: Optional[Any] = None):
        self.tracer = tracer
        self.peak_device_bytes = 0
        self.peak_rss_bytes = 0
        # kernel bucket key -> predicted cost (computed once per key)
        self.kernel_costs: Dict[str, Dict[str, Any]] = {}

    # -- memory accounting -------------------------------------------------

    def sample_device(self, span: Any, store: Any) -> int:
        """Live device bytes held by the store's cache right now, stamped
        onto ``span`` (the closing ``store.load``/``kernel.eval``) and
        folded into the session peak."""
        try:
            live = int(sum(int(e.nbytes) for e in store._cache.values()))
        except Exception:
            return 0
        if live > self.peak_device_bytes:
            self.peak_device_bytes = live
        span.set(device_live_bytes=live)
        return live

    def observe_rss(self) -> int:
        """Process peak RSS in bytes (``ru_maxrss`` is KiB on Linux)."""
        try:
            rss = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
        except Exception:
            return self.peak_rss_bytes
        if rss > self.peak_rss_bytes:
            self.peak_rss_bytes = rss
        return rss

    # -- kernel cost attribution -------------------------------------------

    def attribute_kernel(self, key: Any, fn: Any, *args: Any) -> Dict[str, Any]:
        """Predicted cost of the compiled bucket ``key``: lower ``fn`` on
        ``args`` (abstract — no execution), analyze the HLO, fold through
        the roofline.  Computed once per key: every evaluator call passes
        through here (``core/engine.traced_eval``), and all but a key's
        first return the cached cost without lowering."""
        skey = _key_str(key)
        cached = self.kernel_costs.get(skey)
        if cached is not None:
            return cached
        cost: Dict[str, Any] = {"flops": 0.0, "bytes": 0.0,
                                "t_bound_us": None, "dominant": "not measured"}
        try:
            import jax
            from ..launch.hlo_analysis import CHIP_PEAKS, RooflineTerms
            from ..launch.hlo_cost import analyze_hlo_text
            kind = jax.devices()[0].device_kind
            text = fn.lower(*args).as_text(dialect="hlo")
            info = analyze_hlo_text(text)
            terms = RooflineTerms(
                device_flops=float(info["flops"]),
                device_bytes=float(info["bytes"]),
                device_coll_bytes=float(info["collective_bytes_total"]),
                peaks=CHIP_PEAKS.get(kind))
            t_bound = terms.t_bound
            cost = {
                "flops": float(info["flops"]),
                "bytes": float(info["bytes"]),
                "bytes_xla_convention": float(info["bytes_xla_convention"]),
                "device_kind": kind,
                "t_bound_us": None if t_bound is None else t_bound * 1e6,
                "dominant": terms.dominant,
            }
            if info.get("warnings"):
                cost["warnings"] = list(info["warnings"])
        except Exception as e:  # profiling must never break serving
            cost["cost_error"] = f"{type(e).__name__}: {e}"
        self.kernel_costs[skey] = cost
        return cost

    def stamp_kernel(self, span: Any, key: Any) -> None:
        """Write the bucket's predicted cost onto a ``kernel.eval`` span
        (no-op until ``attribute_kernel`` ran for the key, which every
        call does before it evaluates)."""
        c = self.kernel_costs.get(_key_str(key))
        if c is None:
            return
        span.set(kernel_key=_key_str(key),
                 cost_flops=c["flops"], cost_bytes=c["bytes"],
                 cost_t_bound_us=c["t_bound_us"],
                 cost_dominant=c["dominant"])

    # -- snapshot ----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        self.observe_rss()
        return {
            "enabled": True,
            "peak_rss_bytes": self.peak_rss_bytes,
            "peak_device_bytes": self.peak_device_bytes,
            "kernel_costs": {k: dict(v) for k, v in self.kernel_costs.items()},
        }


class SloBurnMonitor:
    """Rolling-window error-budget burn per SLO class.

    Each completion lands as ``observe(slo_class, met)``; the window holds
    the last ``window`` outcomes per class.  Burn rate is

        burn = miss_fraction(window) / error_budget

    burn == 1 means deadline misses exactly consume the budget; burn > 1
    means the budget is burning faster than it accrues (alert-worthy);
    burn == 0 means a clean window.  Shed/rejected requests are not
    deadline outcomes and do not enter the window — shedding is the
    mechanism that *protects* the budget, accounted separately by the
    frontend's shed counters.
    """

    def __init__(self, window: int = 100, error_budget: float = 0.01):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if not (0.0 < error_budget <= 1.0):
            raise ValueError(f"error_budget must be in (0, 1], "
                             f"got {error_budget}")
        self.window = int(window)
        self.error_budget = float(error_budget)
        self._events: Dict[str, Deque[bool]] = {}

    def observe(self, slo_class: str, met: bool) -> None:
        dq = self._events.get(slo_class)
        if dq is None:
            dq = self._events[slo_class] = collections.deque(
                maxlen=self.window)
        dq.append(bool(met))

    def miss_fraction(self, slo_class: str) -> float:
        dq = self._events.get(slo_class)
        if not dq:
            return 0.0
        return sum(1 for met in dq if not met) / len(dq)

    def burn_rate(self, slo_class: str) -> float:
        return self.miss_fraction(slo_class) / self.error_budget

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for cls, dq in self._events.items():
            misses = sum(1 for met in dq if not met)
            out[cls] = {
                "window": len(dq),
                "misses": misses,
                "miss_fraction": misses / len(dq) if dq else 0.0,
                "burn_rate": self.burn_rate(cls),
                "error_budget": self.error_budget,
            }
        return out


def resource_profile_snapshot(session: Any) -> Dict[str, Any]:
    """The serve-JSON ``profile`` block (schema_version 3): session peaks,
    per-kernel predicted costs, tier byte flows, and SLO burn."""
    prof = getattr(session, "profiler", NULL_PROFILER)
    block: Dict[str, Any] = {"enabled": bool(prof.enabled)}
    if not prof.enabled:
        return block
    block.update(prof.snapshot())
    ls = getattr(session, "load_stats", None)
    if ls is not None:
        block["bytes"] = {
            "cold": int(ls.bytes_cold),
            "prefetched": int(ls.bytes_prefetched),
            "disk": int(ls.bytes_disk),
            "host": int(getattr(ls, "bytes_host", 0)),
        }
        backing = getattr(getattr(session, "store", None), "backing", None)
        if backing is not None and hasattr(backing, "bytes_read"):
            block["bytes"]["disk_catalog"] = int(backing.bytes_read)
    burn = getattr(session, "_slo_burn", None)
    if burn:
        block["slo_burn"] = dict(burn)
    return block
