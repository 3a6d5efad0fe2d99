"""From a JAX profiler trace to device busy time, module time and idle gaps.

The harness traces a few seconds of a run (``jax.profiler``, Python
tracer off) with a ``TraceAnnotation`` named ``WINDOW`` around them.  This
module reads the ``.xplane.pb`` the profiler writes and reduces it:

* the traced window: the ``WINDOW`` annotation's interval on the host
  (or, where no device event falls inside it, the span of the device's
  own events, with no gap labelled);
* busy time of each device: the union of the intervals of the events on
  its op line (``XLA Ops``), clipped to the window;
* per-module time: the events of the device's module line
  (``XLA Modules``), by name;
* idle gaps: the stretches of the window in which a device ran nothing,
  each labelled with the innermost host interval that covers its middle
  (the program's own spans, mapped onto the trace's clock, else the
  harness's annotations).

Only events on planes named ``/device:TPU:<n>`` count as device events.
A TPU trace names a jitted function's module ``jit_<function name>``,
followed by its instance in parentheses; ``module_time`` matches on the
part before that.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Line:
    name: str
    events: List[Tuple[str, int, int]]      # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def load_xplane(log_dir: str) -> List[Plane]:
    """Every plane of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    return [Plane(p.name, [Line(ln.name, [(e.name, int(e.start_ns),
                                           int(e.duration_ns))
                                          for e in ln.events])
                           for ln in p.lines])
            for p in pd.planes]


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The union of half-open ``[start, end)`` intervals, sorted."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _line(plane: Plane, name: str) -> Optional[Line]:
    return next((ln for ln in plane.lines if ln.name == name), None)


def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    return [p for p in planes if p.name.startswith(DEVICE_PREFIX)]


def window(planes: Sequence[Plane]) -> Tuple[int, int]:
    """The ``WINDOW`` annotation's ``[start, end)`` in trace nanoseconds."""
    for p in planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for ln in p.lines:
            for name, s, d in ln.events:
                if name == WINDOW:
                    return s, s + d
    raise RuntimeError(f"the trace holds no {WINDOW!r} annotation")


def _clip(ivs, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def _busy_events(plane: Plane):
    """The events that mark the device busy: its op line, else its module
    line, else every line it has."""
    ln = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
    return ln.events if ln else [e for x in plane.lines for e in x.events]


def busy_intervals(plane: Plane, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The device's busy stretches inside ``[lo, hi)``."""
    return merge(_clip(((s, s + d) for _, s, d in _busy_events(plane)), lo, hi))


def module_time(plane: Plane, lo: int, hi: int) -> Dict[str, Tuple[int, int]]:
    """``{module: (total ns, executions)}`` of the modules that started
    inside ``[lo, hi)``, named without their instance suffix."""
    out: Dict[str, Tuple[int, int]] = {}
    ln = _line(plane, MODULES_LINE)
    for name, s, d in (ln.events if ln else []):
        if lo <= s < hi:
            key = name.split("(")[0]
            tot, n = out.get(key, (0, 0))
            out[key] = (tot + d, n + 1)
    return out


def op_time(plane: Plane, lo: int, hi: int) -> Dict[str, int]:
    """Device ns by op name inside ``[lo, hi)`` (nested ops count in each
    of their enclosing ops too, as the trace lists them).  A TPU trace
    names an op by its whole HLO instruction; the name is the part before
    `` = ``."""
    out: Dict[str, int] = {}
    for name, s, d in _busy_events(plane):
        name = name.split(" = ")[0]
        for cs, ce in _clip([(s, s + d)], lo, hi):
            out[name] = out.get(name, 0) + (ce - cs)
    return out


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The idle stretches of ``[lo, hi)`` between busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: Tuple[int, int],
              host: Sequence[Tuple[str, int, int, int]]) -> str:
    """The name of the innermost host interval ``(name, start, end,
    depth)`` that covers the gap's middle, or ``"none"``."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for name, s, e, depth in host:
        if s <= mid < e and (best is None or depth > best[1]):
            best = (name, depth)
    return best[0] if best else "none"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over the devices traced
    modules: Dict[str, Tuple[float, int]]    # seconds, executions
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    idle_by_label: Dict[str, float]


def reduce(planes: Sequence[Plane],
           host: Sequence[Tuple[str, int, int, int]] = (),
           top: int = 10) -> Summary:
    """Reduce a trace.  ``host`` holds host intervals already on the
    trace's clock, as ``(name, start_ns, end_ns, depth)``."""
    lo, hi = window(planes)
    devs = device_planes(planes)
    if not devs:
        raise RuntimeError("the trace holds no TPU device plane")
    events = [e for p in devs for e in _busy_events(p)]
    if events and not any(s < hi and s + d > lo for _, s, d in events):
        # the device's clock does not meet the host's annotation: take the
        # window from the device's own events, and label no gap
        lo = min(s for _, s, _ in events)
        hi = max(s + d for _, s, d in events)
        host = ()
    busy_ns, modules, ops = [], {}, {}
    all_gaps: List[Tuple[str, int]] = []
    for p in devs:
        b = busy_intervals(p, lo, hi)
        busy_ns.append(sum(e - s for s, e in b))
        for k, (t, n) in module_time(p, lo, hi).items():
            tot, cnt = modules.get(k, (0, 0))
            modules[k] = (tot + t, cnt + n)
        for k, t in op_time(p, lo, hi).items():
            ops[k] = ops.get(k, 0) + t
        all_gaps += [(label_gap(g, host), g[1] - g[0]) for g in gaps(b, lo, hi)]
    n = len(devs)
    by_label: Dict[str, float] = {}
    for lab, d in all_gaps:
        by_label[lab] = by_label.get(lab, 0.0) + d / 1e9 / n
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_ns) / n / 1e9,
        modules={k: (t / 1e9, c) for k, (t, c) in modules.items()},
        top_ops=[(k, t / 1e9) for k, t in
                 sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[(lab, d / 1e9) for lab, d in
                   sorted(all_gaps, key=lambda x: -x[1])[:top]],
        idle_by_label=by_label)
