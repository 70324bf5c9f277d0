"""Find the serving knee once, on the chip: the highest open-loop rate the
deployment's ``DeadlineScheduler`` sustains.

  python3 bench/sweep_knee.py --config adult_c8 --seconds 4 --rates 1000 2000 4000

One process sets up the serving cell's engine (``drivers/serve.py``) and
drives one window per rate, in rising order, with the cell's own
generator and collector. A rate is sustained when the rows answered per
second stay within 2% of the offered rate and the 99th percentile stays
under ``--p99-limit-ms``; the knee is the highest sustained rate below
the first that is not (past it the queue is left unserved, so the sweep
stops there). Prints one JSON line per rate, then the knee. The serving cell runs at
0.8 x the knee, written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.drivers import serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="adult_c8")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--p99-limit-ms", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        bench_run.require_chips(1)
    except bench_run.NoChip as e:
        print(f"sweep_knee: {e}", file=sys.stderr)
        return 3
    bench_run.use_compile_cache()
    config = bench_run.load_json(bench_run.BENCH / "configs" / f"{args.config}.json")
    run = bench_run.Run({"name": "sweep", "chips": 1}, config, {}, seed=args.seed,
                        seconds=args.seconds, trace=False, compiles=None)
    engine, _, _, X = serve.setup_engine(run)
    knee = None
    with engine.scheduler() as sched:
        for i, rate in enumerate(sorted(args.rates)):
            due, rows = serve.schedule(rate, args.seconds, X.shape[0], args.seed + i)
            t0, sent, t_ans, _, ok = serve.drive(run, sched, X, due, rows, wait_s=10.0)
            lat = np.where(ok, t_ans - (t0 + due), np.inf)
            rate_out = ok.sum() / (np.nanmax(t_ans) - t0)
            p99 = 1e3 * float(np.percentile(lat, 99))
            sustained = bool(rate_out >= 0.98 * rate and p99 <= args.p99_limit_ms)
            if sustained:
                knee = rate
            print(json.dumps({
                "rate": rate, "answered_per_s": float(rate_out),
                "p50_ms": 1e3 * float(np.percentile(lat, 50)), "p99_ms": p99,
                "gen_lag_p99_ms": 1e3 * float(np.percentile(sent - (t0 + due), 99)),
                "failed": int((~ok).sum()), "sustained": sustained,
            }), flush=True)
            if not sustained:
                break
    print(json.dumps({"knee_rows_per_s": knee, "at_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
