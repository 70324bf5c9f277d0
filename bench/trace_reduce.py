"""From a JAX profiler trace of the measured window to device numbers.

A trace is reduced to plain data first (:func:`load_profile`):
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``, so the reduction below runs the same on a trace
taken on the chip and on the small recorded one the tests keep.

- The window is the span of the harness's ``bench.window`` annotation.
- A device is a ``/device:TPU:<i>`` plane; its operations are the events
  of its ``XLA Ops`` line. Busy time is the union of their intervals
  inside the window, averaged over the devices that ran an operation in
  it (a cell may hold chips it does not use); idle is the rest.
- Collective time is the summed duration of operations whose name
  starts with a collective's HLO name (all-reduce, all-gather, ...).
- ``breakdown`` names an operation by its HLO name and result type.
- An idle gap is named by the innermost ``bench.*`` host annotation
  open at its midpoint, or ``program`` where none was.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all", "collective-broadcast")
TOP = 10


def load_profile(log_dir: str) -> Dict[str, Any]:
    """The ``.xplane.pb`` that ``jax.profiler.stop_trace`` wrote under
    ``log_dir``, as plain data."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    prof = ProfileData.from_file(paths[0])
    planes = []
    for plane in prof.planes:
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events],
            })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def merge(intervals: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of ``[start, end)`` intervals clipped to ``[lo, hi]``, sorted."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The complement of merged ``busy`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def is_collective(op: str) -> bool:
    return op.lstrip("%").startswith(COLLECTIVES)


def short_name(op: str) -> str:
    """An operation's HLO name and result type (``fusion.16
    f32[15232,2]``) from the full HLO text a trace may carry."""
    name, _, rest = op.partition(" = ")
    kind = rest.split("{", 1)[0].split(" ", 1)[0] if rest and not rest.startswith("(") else ""
    return f"{name.lstrip('%')} {kind}".strip()


@dataclasses.dataclass
class Summary:
    window_s: float  # length of the traced window
    busy_s: float  # union of device-op intervals, mean over the devices used
    n_devices: int
    collective_s: float  # summed collective-op time, mean over devices
    top_ops: List[List[Any]]  # [[op, seconds per device], ...] longest first
    top_gaps: List[List[Any]]  # [[host annotation, seconds], ...] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_span(planes: List[dict]) -> Tuple[float, float]:
    for plane in planes:
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name == WINDOW:
                    return s, s + d
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def _annotations(planes: List[dict]) -> List[Tuple[float, float, str]]:
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name.startswith("bench.") and name != WINDOW:
                    out.append((s, s + d, name))
    return out


def _label(t: float, spans: List[Tuple[float, float, str]]) -> str:
    open_ = [(e - s, name) for s, e, name in spans if s <= t < e]
    return min(open_)[1] if open_ else "program"


def summarize(trace: Dict[str, Any]) -> Optional[Summary]:
    """The window's device numbers; None where the trace holds no TPU."""
    planes = trace["planes"]
    lo, hi = window_span(planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        return None
    spans = _annotations(planes)
    ops = {p["name"]: [ev for line in p["lines"] if line["name"] == OPS_LINE for ev in line["events"]]
           for p in devices}
    used = sorted(name for name, events in ops.items()
                  if merge([(s, s + d) for _, s, d in events], lo, hi)) or sorted(ops)
    busy_ns, coll_ns = 0.0, 0.0
    op_ns: Dict[str, float] = {}
    all_gaps: List[Tuple[float, str]] = []
    for i, name in enumerate(used):
        events = ops[name]
        busy = merge([(s, s + d) for _, s, d in events], lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for op_name, s, d in events:
            inside = max(0.0, min(s + d, hi) - max(s, lo))
            if inside <= 0:
                continue
            op = short_name(op_name)
            op_ns[op] = op_ns.get(op, 0.0) + inside
            if is_collective(op_name):
                coll_ns += inside
        if i == 0:  # gaps are named on the first device
            all_gaps = [(e - s, _label((s + e) / 2, spans)) for s, e in gaps(busy, lo, hi)]
    n = len(used)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(all_gaps, key=lambda g: -g[0])[:TOP]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / n / 1e9,
        n_devices=n,
        collective_s=coll_ns / n / 1e9,
        top_ops=[[name, ns / n / 1e9] for name, ns in top_ops],
        top_gaps=[[name, ns / 1e9] for ns, name in top_gaps],
    )


@dataclasses.dataclass
class Observations:
    """Everything a per-layer reader (``bench/metrics/<name>.py``) may read."""

    window_s: float  # host-clock window of the untraced measure
    rounds: Optional[int]  # training rounds completed in the window
    chips: int
    compiles_in_window: int
    trace: Optional[Summary]
    counters: Dict[str, float]
    config: dict
    traffic: dict
    device_kind: str
    notes: List[str]
