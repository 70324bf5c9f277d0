"""``correct`` for the fused training cells: sound runs pass, the control
fails, and each fault planted in the timed path makes ``correct`` false."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, control, run as bench_run
from bench.reference import adaboost_f_decision_tree as ref
from bench.tests import small


def test_sound_run_is_correct():
    res = small.run("adult_c8.train")
    assert res["correct"], res["checks"]
    assert res["metrics"]["round_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def _faulty_round(kind):
    from repro.core import boosting

    real = boosting.ROUND_FNS["adaboost_f"]

    def stuck(learner, spec, s, X, y, m, **kw):
        _, metrics = real(learner, spec, s, X, y, m, **kw)
        return s, metrics

    def half(learner, spec, s, X, y, m, **kw):
        m = m.at[:, m.shape[1] // 2:].set(0.0)
        w = s.weights * m
        s = s._replace(weights=w / jnp.sum(w))
        return real(learner, spec, s, X, y, m, **kw)

    def altered(learner, spec, s, X, y, m, **kw):
        s, metrics = real(learner, spec, s, X, y, m, **kw)
        ens = s.ensemble
        ens = ens._replace(alpha=ens.alpha.at[ens.count - 1].multiply(1.5))
        return s._replace(ensemble=ens), metrics

    return {"stuck": stuck, "half": half, "altered": altered}[kind]


@pytest.mark.parametrize("fault", ["stuck", "half", "altered"])
def test_fault_in_the_round_is_not_correct(monkeypatch, fault):
    from repro.core import boosting

    monkeypatch.setitem(boosting.ROUND_FNS, "adaboost_f", _faulty_round(fault))
    res = small.run("adult_c8.train")
    assert not res["correct"], res["checks"]


def test_eval_fault_is_not_correct(monkeypatch):
    """An F1 altered where the eval produces it."""
    from repro.fl import federation

    real = federation.f1_macro
    monkeypatch.setattr(federation, "f1_macro", lambda *a: real(*a) * 0.9)
    res = small.run("adult_c8.train")
    assert not res["correct"]
    assert res["checks"]["f1_gap"]["value"] > res["checks"]["f1_gap"]["limit"]


def _inputs(n_train=4000):
    import jax

    from bench import datagen

    _, _, config, _ = small.cell("adult_c8.train", n_train=n_train)
    key = jax.random.PRNGKey(3)
    X, y, Xte, yte = (np.asarray(a) for a in datagen.make_classification(config["data"], key))
    perm = np.asarray(jax.random.permutation(key, X.shape[0]))
    return config, X, y, Xte, yte, perm


@pytest.mark.parametrize("precision, fault", [("bfloat16", None), ("float64", "half"),
                                              ("float64", "alter")])
def test_control_and_faults_fail_the_limits(precision, fault):
    """The reference in bfloat16, or with a planted fault, put in the
    program's place: its numbers break at least one limit."""
    config, X, y, Xte, yte, perm = _inputs()
    fed, K = config["federation"], config["data"]["n_classes"]
    limits = bench_run.load_json(bench_run.BENCH / "limits" / "adult_c8.train.json")
    got = compare.reference_members(X, y, perm, fed, K, 3, precision=precision, fault=fault)
    if fault is None:  # the control's eval tallies its votes in bfloat16 too
        f1_row = control.control_f1_row(got, Xte, yte, K)
    else:
        votes = ref.ensemble_predict(got["feature"], got["threshold"], got["leaf_logits"],
                                     got["alpha"], Xte, K)
        f1_row = {"round": 2, "f1": ref.f1_macro(yte, np.argmax(votes, -1), K)}
    checks = compare.training(got, X, y, perm, fed, K, 3, count=3, rounds_done=3,
                              f1_row=f1_row, X_test=Xte, y_test=yte)
    assert any(checks[k] > limits[k] for k in limits), checks
    want = compare.reference_members(X, y, perm, fed, K, 3)
    same = compare.training(want, X, y, perm, fed, K, 3, count=3, rounds_done=3, f1_row=None,
                            X_test=Xte, y_test=yte)
    assert all(v == 0 for v in same.values()), same
