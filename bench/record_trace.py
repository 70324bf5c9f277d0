"""Record a slice of a cell's profiler trace as plain data, for the test
of the trace reduction (``bench/tests/data/``).

  python3 bench/record_trace.py --workload adult_c8.train --seed 3 --seconds 5 --out sample.json

Runs the cell with ``--trace 1`` on the chip and keeps, of the window's
trace (as ``trace_reduce.load_profile`` reduces it), the harness's own
annotations and every event that starts within ``--slice-ms`` of the
window's middle, at most 4,000 per line.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench import trace_reduce  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--slice-ms", type=float, default=60.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    benchmark = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = bench_run.find_cell(args.workload, benchmark)
    try:
        devices = bench_run.require_chips(cell["chips"])
    except bench_run.NoChip as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return 3
    bench_run.use_compile_cache()
    _, run = bench_run.measure(benchmark, cell, seed=args.seed, seconds=args.seconds,
                               trace=True, devices=devices)
    planes = run.trace_data["planes"]
    lo, hi = trace_reduce.window_span(planes)
    mid = (lo + hi) / 2
    keep = []
    for plane in planes:
        lines = []
        for line in plane["lines"]:
            ev = [e for e in line["events"]
                  if mid <= e[1] <= mid + 1e6 * args.slice_ms or e[0].startswith("bench.")]
            if ev:
                lines.append({"name": line["name"], "events": ev[:4000]})
        if lines:
            keep.append({"name": plane["name"], "lines": lines})
    with open(args.out, "w") as f:
        json.dump({"planes": keep}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
