"""``correct`` for the serving cell: a sound run passes; an answer altered
where the engine produces it, or half of a batch left unanswered, fails;
the bfloat16 control fails the vote limit."""
import numpy as np
import pytest

from bench import compare, run as bench_run
from bench.drivers import serve
from bench.reference import adaboost_f_decision_tree as ref
from bench.tests import small

RATE = 1500.0


def test_sound_run_is_correct():
    res = small.run("adult_c8.serve", rate_rows_per_s=RATE, warmup_s=0.2)
    assert res["correct"], res["checks"]
    assert res["attempted"] == RATE and res["failed"] == 0
    m = res["metrics"]
    assert 0 < m["serve_p50_ms"]["value"] <= m["serve_p99_ms"]["value"]
    assert m["serve_rows_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["altered", "half"])
def test_fault_in_the_engine_is_not_correct(monkeypatch, fault):
    from repro.serve.engine import ServeEngine

    real = ServeEngine._run_batch

    def altered(self, Xb, n_valid):
        return (real(self, Xb, n_valid) + 1) % self.spec.n_classes

    def half(self, Xb, n_valid):
        return real(self, Xb, n_valid)[: n_valid // 2]

    monkeypatch.setattr(ServeEngine, "_run_batch", {"altered": altered, "half": half}[fault])
    res = small.run("adult_c8.serve", rate_rows_per_s=RATE, warmup_s=0.2, answer_wait_s=1.0)
    assert not res["correct"], res["checks"]


def test_bfloat16_control_fails_the_vote_limit():
    import jax

    rng = np.random.default_rng(0)
    T, d, K, n = 100, 14, 2, 4000
    X = rng.normal(size=(n, d))
    members = {
        "feature": rng.integers(0, d, (T, 4)), "threshold": rng.normal(size=(T, 4)),
        "leaf_logits": np.log(rng.dirichlet([1.0, 1.0], (T, 16))), "alpha": rng.uniform(0.05, 1, T),
    }
    out = {"members": members, "count": T, "X_test": X}
    votes, mass = serve.reference_votes(out)
    bf16 = ref.rounder("bfloat16")
    ctl = ref.ensemble_predict(members["feature"], bf16(members["threshold"]),
                               bf16(members["leaf_logits"]), bf16(members["alpha"]), bf16(X), K,
                               rnd=bf16)
    rows = np.arange(n)
    limits = bench_run.load_json(bench_run.BENCH / "limits" / "adult_c8.serve.json")
    checks = compare.serving(votes, rows, np.argmax(ctl, -1), np.ones(n, bool), mass)
    assert checks["vote_gap"] > limits["vote_gap"], checks
    exact = compare.serving(votes, rows, np.argmax(votes, -1), np.ones(n, bool), mass)
    assert exact == {"unanswered": 0.0, "vote_gap": 0.0}
    del jax
