"""CI checker for the observability artifacts (the ``obs-smoke`` job).

Validates, without any third-party tooling:

  * a ``--trace`` file is well-formed Chrome trace JSON (the shape
    Perfetto / chrome://tracing load: complete "X" events with
    microsecond ts/dur and span_id/parent_id args) and — for an fl_run
    trace — that the ``round`` spans hold the host-phase children the
    caller names (the fused path: eval/publish; the multi-process path:
    fit/broadcast/score/aggregate);
  * a ``--metrics-out`` dump parses as Prometheus text exposition
    (HELP/TYPE headers, numeric samples, cumulative histogram buckets)
    and covers the expected metric families of every serving subsystem.

Usage::

    python scripts/check_obs.py --trace /tmp/fl_trace.json \
        --round-children round.eval,round.publish
    python scripts/check_obs.py --metrics /tmp/serve_metrics.prom \
        --families mafl_engine_,mafl_scheduler_,mafl_registry_

Exits non-zero with a message naming the first violated property.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from collections import defaultdict
from pathlib import Path

EVENT_KEYS = {"name", "ph", "ts", "dur", "pid", "tid", "args"}


def check_trace(path: str, round_children: list[str]) -> list[str]:
    problems = []
    doc = json.loads(Path(path).read_text())
    if "traceEvents" not in doc:
        return [f"{path}: no traceEvents key — not a Chrome trace"]
    events = doc["traceEvents"]
    if not events:
        return [f"{path}: trace is empty"]
    spans = {}
    for e in events:
        missing = EVENT_KEYS - set(e)
        if missing:
            problems.append(f"{path}: event {e.get('name')!r} missing {missing}")
            continue
        if e["ph"] != "X":
            problems.append(f"{path}: {e['name']!r} is not a complete event")
        if e["dur"] < 0:
            problems.append(f"{path}: {e['name']!r} has negative duration")
        sid = e["args"].get("span_id")
        if sid is None:
            problems.append(f"{path}: {e['name']!r} has no span_id")
        else:
            spans[sid] = e

    # parent links resolve, and children nest inside their parent's
    # interval (what makes the Perfetto flame view meaningful)
    kids = defaultdict(set)
    for e in events:
        pid = e["args"].get("parent_id")
        if pid is None:
            continue
        parent = spans.get(pid)
        if parent is None:
            problems.append(f"{path}: {e['name']!r} has dangling parent {pid}")
            continue
        kids[parent["name"]].add(e["name"])
        if e["ts"] + 1e-3 < parent["ts"] or (
            e["ts"] + e["dur"] > parent["ts"] + parent["dur"] + 1e-3
        ):
            problems.append(
                f"{path}: {e['name']!r} escapes its parent {parent['name']!r}"
            )

    if round_children:
        rounds = [e for e in events if e["name"] == "round"]
        if not rounds:
            problems.append(f"{path}: no 'round' spans recorded")
        missing = set(round_children) - kids["round"]
        if missing:
            problems.append(
                f"{path}: round spans lack phase children {sorted(missing)} "
                f"(have {sorted(kids['round'])})"
            )
    return problems


def check_metrics(path: str, families: list[str]) -> list[str]:
    problems = []
    text = Path(path).read_text()
    typed, seen_samples = {}, set()
    hist_cum: dict[str, tuple[float, float]] = {}  # series -> (last_le, last_cum)
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in ("counter", "gauge", "histogram"):
                problems.append(f"{path}:{ln}: bad TYPE line {line!r}")
            else:
                typed[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            problems.append(f"{path}:{ln}: unknown comment {line!r}")
            continue
        name_part, _, value = line.rpartition(" ")
        try:
            v = float(value)
        except ValueError:
            problems.append(f"{path}:{ln}: non-numeric sample {line!r}")
            continue
        name = name_part.split("{", 1)[0]
        seen_samples.add(name)
        if name.endswith("_bucket"):
            series = name_part.rsplit(",le=", 1)[0].rsplit('le="', 1)[0]
            le_s = name_part.split('le="', 1)[1].split('"', 1)[0]
            le = math.inf if le_s == "+Inf" else float(le_s)
            last_le, last_cum = hist_cum.get(series, (-math.inf, -math.inf))
            if le <= last_le:
                problems.append(f"{path}:{ln}: bucket edges not increasing")
            if v < last_cum:
                problems.append(f"{path}:{ln}: bucket counts not cumulative")
            hist_cum[series] = (le, v)

    base = lambda n: n.removesuffix("_bucket").removesuffix("_sum").removesuffix("_count")
    for name in seen_samples:
        root_candidates = {name, base(name)}
        if not root_candidates & set(typed):
            problems.append(f"{path}: sample {name!r} has no TYPE header")
    for fam in families:
        hits = [n for n in typed if n.startswith(fam)] if fam.endswith("_") else (
            [fam] if fam in typed else []
        )
        if not hits:
            problems.append(
                f"{path}: expected metric family {fam!r} absent "
                f"(have {sorted(typed)})"
            )
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None,
                    help="Chrome-trace JSON file to validate")
    ap.add_argument("--round-children", default="",
                    help="comma-separated span names every 'round' span "
                         "must have as children (fl_run traces)")
    ap.add_argument("--metrics", default=None,
                    help="Prometheus text dump to validate")
    ap.add_argument("--families", default="",
                    help="comma-separated metric family names (or prefixes "
                         "ending in '_') that must appear in the dump")
    args = ap.parse_args(argv)
    if not args.trace and not args.metrics:
        ap.error("nothing to check: pass --trace and/or --metrics")

    problems = []
    if args.trace:
        kids = [s for s in args.round_children.split(",") if s]
        problems += check_trace(args.trace, kids)
        if not problems:
            n = len(json.loads(Path(args.trace).read_text())["traceEvents"])
            print(f"ok: {args.trace} is a valid Chrome trace ({n} events)")
    if args.metrics:
        fams = [s for s in args.families.split(",") if s]
        p0 = len(problems)
        problems += check_metrics(args.metrics, fams)
        if len(problems) == p0:
            print(f"ok: {args.metrics} parses; families present: "
                  f"{args.families or '(none required)'}")
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
