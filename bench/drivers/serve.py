"""Serving through ``DeadlineScheduler`` over ``ServeEngine``, open loop.

Set-up trains the deployment's ensemble with ``Federation.run`` as
``fl_run`` does by default (``serving.ensemble_rounds`` rounds, eval
every ``serving.eval_every``), then ``save_artifact`` ->
``load_artifact`` -> ``ServeEngine.from_artifact`` with the default
``EngineConfig``, warms the engine, and sends ``warmup_s`` of traffic
through the scheduler.

The window is an open loop: ``rate_rows_per_s * --seconds`` requests of
one test row each, due on a fixed schedule whatever the server does.
The gaps between due times are the exponential distribution's
``N``-quantiles (a Poisson process's gaps with the sampling noise taken
out), in an order drawn from ``--seed``; the rows are drawn from the
test split by ``--seed``. A generator thread sends each request when it
is due, a collector thread takes each answer; a request's latency runs
from its due time to its answer, and one not answered by
``answer_wait_s`` after the window counts as failed.

Outputs: every answer, checked against the reference's vote of the
trained ensemble.
"""
from __future__ import annotations

import queue
import shutil
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from bench import compare
from bench.drivers import fused
from bench.reference import adaboost_f_decision_tree as ref


def schedule(rate: float, seconds: float, n_rows: int, seed: int):
    """(due offsets in seconds, test row per request) of one window."""
    n = max(int(round(rate * seconds)), 1)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due, rng.integers(0, n_rows, n)


def _hist_total(name: str):
    from repro.obs import metrics as obs_metrics

    h = obs_metrics.REGISTRY.get(name).solo
    return h.sum, h.count


def drive(run, sched, X: np.ndarray, due: np.ndarray, rows: np.ndarray, wait_s: float):
    """Send ``X[rows[i]]`` at ``t0 + due[i]``; returns (t0, sent, answered
    times, answers, ok). The collector gives up ``wait_s`` after the last
    due time."""
    n = len(due)
    rids: "queue.SimpleQueue" = queue.SimpleQueue()
    t_ans = np.full(n, np.nan)
    answers = np.full(n, -1, np.int64)
    ok = np.zeros(n, bool)
    t0 = time.perf_counter() + 0.002
    give_up = t0 + float(due[-1]) + wait_s

    def collect():
        for i in range(n):
            rid = rids.get()
            try:
                a = sched.result(rid, timeout_s=max(give_up - time.perf_counter(), 1e-3))
                answers[i], ok[i] = a, True
            except Exception:  # unanswered or failed: counted, not raised
                pass
            t_ans[i] = time.perf_counter()

    collector = threading.Thread(target=collect, name="bench-collector", daemon=True)
    collector.start()
    sent = np.empty(n)
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            with run.annotate("bench.generator.sleep"):
                time.sleep(wait)
        sent[i] = time.perf_counter()
        (rid,) = sched.submit(X[rows[i]])
        rids.put(rid)
    collector.join(max(give_up - time.perf_counter(), 0.0) + 5.0)
    return t0, sent, t_ans, answers, ok


def setup_engine(run):
    """Train the deployment's ensemble and bring up its engine, warmed.
    Returns (engine, members, count, test rows as float32)."""
    from repro.serve import ServeEngine, load_artifact, save_artifact

    spec, data, shards = fused.setup(run)
    srv = run.config["serving"]
    fed = fused.federation(run, spec, data, shards, srv["ensemble_rounds"])
    fed.run(eval_every=srv["eval_every"])
    ens = fed._fused_state.ensemble
    members = {k: np.asarray(v) for k, v in ens.params._asdict().items()}
    members["alpha"] = np.asarray(ens.alpha)
    count = int(ens.count)
    tmp = tempfile.mkdtemp(prefix="bench_artifact_")
    art = load_artifact(save_artifact(Path(tmp) / "ensemble.mafl", spec, ens))
    shutil.rmtree(tmp, ignore_errors=True)
    del fed, ens
    engine = ServeEngine.from_artifact(art)
    engine.warmup()
    return engine, members, count, np.asarray(data[2], np.float32)


def run(run):
    engine, members, count, X_test = setup_engine(run)
    tr = run.traffic
    rate, wait_s = float(tr["rate_rows_per_s"]), float(tr["answer_wait_s"])
    due, rows = schedule(rate, run.seconds, X_test.shape[0], run.seed)
    warm_due, warm_rows = schedule(rate, tr["warmup_s"], X_test.shape[0], run.seed + 1)

    with engine.scheduler() as sched:
        drive(run, sched, X_test, warm_due, warm_rows, wait_s)
        setup_end = time.perf_counter()
        wait0, batches0, req0 = (_hist_total("mafl_scheduler_queue_wait_seconds"),
                                 engine.stats.batches, engine.stats.requests)
        run.open_window(setup_end)
        t0, sent, t_ans, answers, ok = drive(run, sched, X_test, due, rows, wait_s)
        t_last = float(np.nanmax(t_ans)) if np.any(ok) else time.perf_counter()
        run.close_window(t_last)
        wait1 = _hist_total("mafl_scheduler_queue_wait_seconds")
        batches = engine.stats.batches - batches0
        requests = engine.stats.requests - req0
    run.window["start"] = t0  # the window runs from the first due time
    lat = np.where(ok, t_ans - (t0 + due), np.inf)
    cutoff = t0 + float(due[-1]) + wait_s
    lat = np.minimum(lat, cutoff - (t0 + due))  # an unanswered request waited at least this
    answered = int(ok.sum())
    counters = {
        "queue_wait_s_sum": wait1[0] - wait0[0],
        "queue_wait_count": wait1[1] - wait0[1],
        "batches": batches, "rows": requests, "batch_size": engine.batch_size,
        "gen_lag_p99_s": float(np.percentile(sent - (t0 + due), 99)),
    }
    outputs = {"members": members, "count": count, "rows": rows, "answers": answers, "ok": ok,
               "X_test": X_test}
    return {
        "setup_end": setup_end,
        "end_to_end": {
            "serve_p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "serve_p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "serve_rows_per_s": answered / (t_last - t0),
        },
        "attempted": len(due), "failed": len(due) - answered,
        "counters": counters, "outputs": outputs,
    }


def reference_votes(out) -> tuple:
    """(the reference's vote tally of every test row, summed |alpha|)."""
    T, m = out["count"], out["members"]
    K = m["leaf_logits"].shape[-1]
    votes = ref.ensemble_predict(m["feature"][:T], m["threshold"][:T], m["leaf_logits"][:T],
                                 m["alpha"][:T], out["X_test"], K)
    return votes, float(np.sum(np.abs(m["alpha"][:T])))


def check(run, out) -> dict:
    votes, mass = reference_votes(out)
    return compare.serving(votes, out["rows"], out["answers"], out["ok"], mass)
