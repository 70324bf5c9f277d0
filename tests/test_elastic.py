"""Elastic federation runtime (fl/elastic.py): lockstep equivalence,
participation-machinery invariants, and in-process chaos.

The multi-process chaos harness lives in tests/test_elastic_chaos.py;
the hypothesis-driven generalisations of the invariants here live in
tests/test_elastic_property.py (skipped without the dev extra — this
module keeps deterministic seeded versions in tier 1).
"""
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scoring
from repro.core.plan import adaboost_plan, bagging_plan
from repro.data import get_dataset
from repro.fl.elastic import (
    ElasticFederation, FaultPlan, ParticipationPolicy, _ArrivalBoard,
    staleness_discount,
)
from repro.fl.federation import Federation
from repro.fl.partition import iid_partition
from repro.learners import LearnerSpec

ALGOS = ["adaboost_f", "distboost_f", "preweak_f", "bagging"]
C, T = 4, 3


@pytest.fixture(scope="module")
def setup():
    dspec, (Xtr, ytr, Xte, yte) = get_dataset("vehicle", jax.random.PRNGKey(0))
    Xs, ys, masks = iid_partition(Xtr, ytr, C, jax.random.PRNGKey(1))
    lspec = LearnerSpec("decision_tree", dspec.n_features, dspec.n_classes,
                        {"depth": 3, "n_bins": 8})
    return Xs, ys, masks, Xte, yte, lspec


def _make_plan(alg, rounds=T):
    return (bagging_plan(rounds=rounds) if alg == "bagging"
            else adaboost_plan(rounds=rounds, algorithm=alg))


def _run(setup, alg, rounds=T, **run_kw):
    Xs, ys, masks, Xte, yte, lspec = setup
    fed = Federation(_make_plan(alg, rounds), Xs, ys, masks, Xte, yte,
                     lspec, jax.random.PRNGKey(2))
    hist = fed.run(eval_every=1, **run_kw)
    return fed, hist


# -- the tentpole contract: all-ones participation == lockstep, to the bit


@pytest.mark.parametrize("alg", ALGOS)
def test_elastic_noop_policy_equals_lockstep_bitforbit(setup, alg):
    """With no faults and deadline=None the elastic runtime reproduces
    lockstep ``Federation.run`` exactly — history, weights, ensemble
    leaves — for every algorithm (the test_distributed.py contract
    applied to the elastic loop)."""
    lock, hist_lock = _run(setup, alg)
    elas, hist_elas = _run(setup, alg, policy=ParticipationPolicy(deadline_s=None))
    assert len(hist_lock) == len(hist_elas)
    for a, b in zip(hist_lock, hist_elas):
        for k in ("f1", "epsilon", "alpha", "chosen"):
            assert a[k] == b[k], (alg, k)
    s1, s2 = lock._fused_state, elas._fused_state
    np.testing.assert_array_equal(np.asarray(s1.weights), np.asarray(s2.weights))
    np.testing.assert_array_equal(np.asarray(s1.ensemble.alpha),
                                  np.asarray(s2.ensemble.alpha))
    assert int(s1.ensemble.count) == int(s2.ensemble.count)
    for l1, l2 in zip(jax.tree.leaves(s1.ensemble.params),
                      jax.tree.leaves(s2.ensemble.params)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


# -- participation machinery invariants (deterministic seeds; the
# hypothesis generalisation lives in test_elastic_property.py)


def test_masked_helpers_all_ones_identity():
    rng = np.random.default_rng(0)
    errs = jnp.asarray(rng.random((5, 7)), jnp.float32)
    w = jnp.asarray(rng.random((5, 11)), jnp.float32)
    w = w / jnp.sum(w)
    part = jnp.ones(5, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(scoring.masked_error_sum(errs, part)),
        np.asarray(jnp.sum(errs, axis=0)),
    )
    eps = jnp.sum(errs, axis=0)
    hyp_part = jnp.ones(7, jnp.float32)
    assert int(scoring.masked_argmin(eps, hyp_part)) == int(jnp.argmin(eps))
    assert float(scoring.participation_denom(w, part)) == 1.0
    mis = jnp.asarray(rng.integers(0, 2, (5, 11)), jnp.float32)
    mask = jnp.ones((5, 11), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(scoring.masked_update_weights(w, mis, mask, part, 0.7)),
        np.asarray(scoring.update_weights(w, mis, mask, 0.7)),
        rtol=scoring.CROSS_PATH_RTOL, atol=0,
    )


def test_masked_aggregation_permutation_invariant_in_dropped_set():
    """What the dropped collaborators' rows CONTAIN cannot matter: with
    the same responder set, permuting (or scrambling) absent rows leaves
    the chosen error, the denominator, and every responder's updated
    weight row unchanged."""
    rng = np.random.default_rng(1)
    Cn, H, n = 6, 8, 13
    errs = jnp.asarray(rng.random((Cn, H)), jnp.float32)
    w = jnp.asarray(rng.random((Cn, n)), jnp.float32)
    w = w / jnp.sum(w)
    mis = jnp.asarray(rng.integers(0, 2, (Cn, n)), jnp.float32)
    mask = jnp.ones((Cn, n), jnp.float32)
    part = jnp.asarray([1, 0, 1, 0, 0, 1], jnp.float32)
    dropped = [1, 3, 4]

    scrambled_errs = errs.at[jnp.asarray(dropped)].set(
        jnp.asarray(rng.random((3, H)), jnp.float32) * 100.0
    )
    eps_a = scoring.masked_error_sum(errs, part)
    eps_b = scoring.masked_error_sum(scrambled_errs, part)
    np.testing.assert_array_equal(np.asarray(eps_a), np.asarray(eps_b))

    # absent rows' mis cannot move responders' updated weights
    scrambled_mis = mis.at[jnp.asarray(dropped)].set(1.0 - mis[jnp.asarray(dropped)])
    w_a = scoring.masked_update_weights(w, mis, mask, part, 0.9)
    w_b = scoring.masked_update_weights(w, scrambled_mis, mask, part, 0.9)
    resp = np.asarray(part) > 0
    np.testing.assert_array_equal(np.asarray(w_a)[resp], np.asarray(w_b)[resp])

    # and a permutation among the dropped rows leaves the denominator fixed
    perm = jnp.asarray([0, 3, 2, 4, 1, 5])
    assert float(scoring.participation_denom(w, part)) == float(
        scoring.participation_denom(w[perm], part[perm])
    )


def test_staleness_discount_monotone_in_lateness():
    for gamma in (0.25, 0.5, 0.9, 1.0):
        ds = [staleness_discount(gamma, k) for k in range(6)]
        assert ds[0] == 1.0
        assert all(a >= b for a, b in zip(ds, ds[1:]))
    with pytest.raises(ValueError):
        staleness_discount(0.0, 1)
    with pytest.raises(ValueError):
        staleness_discount(0.5, -1)


# -- fault plans are deterministic and seed-driven


def test_fault_plan_schedule_deterministic():
    fp = FaultPlan(seed=42, delay_p=0.3, delay_range_s=(0.1, 0.5),
                   drop_p=0.2, kills=((1, 3),), flaky=((2, 1, 4),))
    a, b = fp.schedule(6, 4), fp.schedule(6, 4)
    np.testing.assert_array_equal(a.delay, b.delay)
    np.testing.assert_array_equal(a.drop, b.drop)
    assert not a.alive[3:, 1].any() and a.alive[:3, 1].all()
    assert a.offline[1:4, 2].all() and not a.offline[4:, 2].any()
    assert (a.delay[a.delay > 0] >= 0.1).all()


# -- in-process chaos: kills + drops, then delay-only late merges


def test_virtual_chaos_kills_and_drops(setup):
    Xs, ys, masks, Xte, yte, lspec = setup
    rounds = 6
    fed = Federation(_make_plan("adaboost_f", rounds), Xs, ys, masks,
                     Xte, yte, lspec, jax.random.PRNGKey(2))
    hist = fed.run(
        eval_every=1,
        policy=ParticipationPolicy(deadline_s=1.0),
        faults=FaultPlan(seed=7, drop_p=0.2, kills=((2, 3),)),
    )
    e = fed.elastic
    assert len(hist) == rounds  # the federation finishes every round
    assert e.dropouts["dead"] == 1
    assert all(r <= C - 1 for r in e.responders_log[3:])  # 2 is gone for good
    assert hist[-1]["f1"] > 0.6
    assert all(row["responders"] >= 1 for row in hist)


def test_virtual_delay_only_late_merges_land_discounted(setup):
    Xs, ys, masks, Xte, yte, lspec = setup
    rounds = 6
    fed = Federation(_make_plan("adaboost_f", rounds), Xs, ys, masks,
                     Xte, yte, lspec, jax.random.PRNGKey(2))
    fed.run(
        eval_every=1,
        policy=ParticipationPolicy(deadline_s=0.5, staleness_gamma=0.5,
                                   max_staleness=2),
        faults=FaultPlan(seed=3, delay_p=0.4, delay_range_s=(0.6, 1.4)),
    )
    e = fed.elastic
    assert e.late_log, "expected stragglers to merge late"
    for row in e.late_log:
        assert row["alpha"] <= row["base_alpha"]
        assert row["discount"] == staleness_discount(0.5, row["lateness"])
        # monotone: two rounds late is discounted at least as hard as one
    by_lateness = sorted(e.late_log, key=lambda r: r["lateness"])
    for a, b in zip(by_lateness, by_lateness[1:]):
        assert a["discount"] >= b["discount"]
    skipped = sum(1 for r in e.responders_log if r == 0)
    assert int(np.asarray(e.state.ensemble.count)) == \
        rounds - skipped + len(e.late_log)


def test_membership_churn_joins_and_leaves(setup):
    """A collaborator joining at round 2 and another leaving at round 3:
    the responder counts must track the membership windows."""
    Xs, ys, masks, Xte, yte, lspec = setup
    rounds = 5
    fed = Federation(_make_plan("adaboost_f", rounds), Xs, ys, masks,
                     Xte, yte, lspec, jax.random.PRNGKey(2))
    hist = fed.run(
        eval_every=1,
        policy=ParticipationPolicy(deadline_s=1.0, joins=((1, 2),),
                                   leaves=((3, 3),)),
        faults=FaultPlan(),
    )
    e = fed.elastic
    assert e.responders_log == [3, 3, 4, 3, 3]
    assert len(hist) == rounds


def test_realtime_board_respects_deadline_and_floor():
    board = _ArrivalBoard()
    board.post(0, 0)
    t0 = time.monotonic()
    resp, late, wait, hit = board.close_round(0, {0, 1}, 0.2, 1)
    assert resp == {0} and hit and wait >= 0.2
    assert time.monotonic() - t0 < 2.0
    # the floor stretches the deadline until an arrival lands
    import threading
    threading.Timer(0.3, board.post, (1, 1)).start()
    resp, late, wait, hit = board.close_round(1, {1}, 0.05, 1)
    assert resp == {1} and wait >= 0.25
    # a straggler posting for an old round surfaces as a late post
    board.post(1, 0)
    resp, late, _, _ = board.close_round(2, set(), None, 1)
    assert late == [(1, 0)]


def test_realtime_mode_smoke(setup):
    Xs, ys, masks, Xte, yte, lspec = setup
    fed = Federation(_make_plan("adaboost_f", 3), Xs, ys, masks,
                     Xte, yte, lspec, jax.random.PRNGKey(2))
    hist = fed.run(
        eval_every=1,
        policy=ParticipationPolicy(deadline_s=0.15, realtime=True),
        faults=FaultPlan(seed=5, delay_p=0.5, delay_range_s=(0.3, 0.5)),
    )
    e = fed.elastic
    assert len(hist) == 3
    assert all(r >= 1 for r in e.responders_log)  # min_responders floor


def test_elastic_rejects_hetero_and_interpreted(setup):
    Xs, ys, masks, Xte, yte, lspec = setup
    from repro.core.hetero import HeterogeneousSpec

    hspec = HeterogeneousSpec.cycle(
        ["decision_tree", "gaussian_nb"], C, lspec.n_features, lspec.n_classes,
        hparams={"decision_tree": {"depth": 3, "n_bins": 8}},
    )
    fed = Federation(_make_plan("adaboost_f"), Xs, ys, masks, Xte, yte,
                     hspec, jax.random.PRNGKey(2))
    with pytest.raises(NotImplementedError):
        fed.run(policy=ParticipationPolicy())
    with pytest.raises(ValueError):
        ParticipationPolicy(deadline_s=-1.0).validate()
    with pytest.raises(ValueError):
        ParticipationPolicy(staleness_gamma=1.5).validate()


# -- launcher: _join_all can no longer hang on a wedged process


def _sleeper(seconds: float) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", f"import time; time.sleep({seconds})"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def test_join_all_kills_hung_orphans(capsys):
    from repro.launch.fl_spawn import _join_all

    procs = [_sleeper(0.2), _sleeper(60.0)]
    t0 = time.monotonic()
    rcs = _join_all(procs, [None, None], timeout=5.0, grace=0.5)
    assert time.monotonic() - t0 < 10.0
    assert rcs[0] == 0 and rcs[1] == 124
    assert procs[1].poll() is not None  # really killed, not leaked


def test_join_all_happy_path_streams_stdout():
    from repro.launch.fl_spawn import _join_all

    procs = [
        subprocess.Popen([sys.executable, "-c", "print('final F1 0.9000')"],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True),
        _sleeper(0.1),
    ]
    out: list = []
    import io
    rcs = _join_all(procs, [None, None], timeout=30.0, out_lines=out,
                    stream=io.StringIO())
    assert rcs == [0, 0]
    assert "final F1 0.9000" in "".join(out)
