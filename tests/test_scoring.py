"""Predict-once scoring engine: Pallas-vs-ref parity on ragged shapes,
round-level kernel/oracle agreement, the PreWeak.F prediction cache, the
incremental vote tally, and the no-double-predict regression."""
import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting, scoring
from repro.data import get_dataset
from repro.fl.partition import iid_partition
from repro.kernels import ref
from repro.kernels.boost_update import weight_update, weighted_errors
from repro.learners import LearnerSpec, get_learner


# ---------------------------------------------------------------------------
# Kernel parity on ragged/masked shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("H,n,block_h,block_s", [
    (13, 1000, 8, 256),    # H % block_h != 0, n % block_s != 0
    (8, 4097, 8, 2048),    # n one past a block boundary
    (5, 31, 8, 2048),      # everything smaller than one block
    (33, 2048, 16, 512),   # ragged H, aligned n
])
def test_weighted_errors_ragged_parity(H, n, block_h, block_s):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    preds = jax.random.randint(k1, (H, n), 0, 7)
    y = jax.random.randint(k2, (n,), 0, 7)
    w = jax.random.uniform(k3, (n,))
    # masked/padded samples carry zero weight — they must not contribute
    w = w * (jnp.arange(n) < n - 7).astype(jnp.float32)
    got = weighted_errors(preds, y, w, block_h=block_h, block_s=block_s, interpret=True)
    want = ref.weighted_errors_ref(preds, y, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,block_s,alpha", [
    (1037, 256, 0.7),   # ragged n
    (4097, 4096, -2.0), # one past a block boundary, negative alpha
    (17, 4096, 3.1),    # smaller than one block
])
def test_weight_update_ragged_parity(n, block_s, alpha):
    key = jax.random.PRNGKey(1)
    k1, k2 = jax.random.split(key)
    w = jax.random.uniform(k1, (n,))
    mis = jax.random.bernoulli(k2, 0.4, (n,)).astype(jnp.float32)
    mask = (jnp.arange(n) < n - 4).astype(jnp.float32)  # padded tail masked out
    got = weight_update(w, mis, mask, jnp.float32(alpha), block_s=block_s, interpret=True)
    want = ref.boost_weight_update_ref(w, mis, mask, jnp.float32(alpha))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    assert np.all(np.asarray(got)[-4:] == 0.0)  # masked tail stays zero


def test_error_matrix_kernel_path_matches_ref_path():
    """Acceptance: kernel path and ref path agree to 1e-5 on the error
    matrix (the scoring engine's central reduction)."""
    key = jax.random.PRNGKey(2)
    k1, k2, k3 = jax.random.split(key, 3)
    C, H, n = 4, 33, 1000  # ragged vs the default block sizes
    preds = jax.random.randint(k1, (C, H, n), 0, 5)
    y = jax.random.randint(k2, (C, n), 0, 5)
    w = jax.random.uniform(k3, (C, n)) / (C * n)
    got = scoring.error_matrix(preds, y, w, use_pallas=True)
    want = scoring.error_matrix(preds, y, w, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# Round-level behaviour
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vehicle():
    key = jax.random.PRNGKey(0)
    dspec, data = get_dataset("vehicle", key)
    lspec = LearnerSpec("decision_tree", dspec.n_features, dspec.n_classes,
                        {"depth": 3, "n_bins": 8})
    learner = get_learner("decision_tree")
    Xtr, ytr, Xte, yte = data
    Xs, ys, masks = iid_partition(Xtr, ytr, 4, jax.random.PRNGKey(1))
    return learner, lspec, Xs, ys, masks, Xte, yte


def test_adaboost_round_pallas_matches_ref(vehicle):
    learner, lspec, Xs, ys, masks, *_ = vehicle
    s_ref = boosting.init_boost_state(learner, lspec, 3, masks, jax.random.PRNGKey(2))
    s_pal = s_ref
    rfn_ref = jax.jit(lambda s: boosting.adaboost_f_round(learner, lspec, s, Xs, ys, masks))
    rfn_pal = jax.jit(
        lambda s: boosting.adaboost_f_round(learner, lspec, s, Xs, ys, masks, use_pallas=True)
    )
    for _ in range(3):
        s_ref, m_ref = rfn_ref(s_ref)
        s_pal, m_pal = rfn_pal(s_pal)
        assert int(m_ref["chosen"]) == int(m_pal["chosen"])
        np.testing.assert_allclose(float(m_ref["epsilon"]), float(m_pal["epsilon"]), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_ref.weights), np.asarray(s_pal.weights), rtol=1e-5, atol=1e-8
    )


def test_preweak_cache_matches_uncached_bitforbit(vehicle):
    learner, lspec, Xs, ys, masks, *_ = vehicle
    T = 4
    state = boosting.init_boost_state(learner, lspec, T, masks, jax.random.PRNGKey(3))
    hyp_space, state = boosting.preweak_f_setup(learner, lspec, state, Xs, ys, masks, T)
    cache = boosting.preweak_f_predictions(learner, lspec, hyp_space, Xs)
    s_a = s_b = state
    for _ in range(T):
        s_a, m_a = boosting.preweak_f_round(learner, lspec, s_a, hyp_space, Xs, ys, masks)
        s_b, m_b = boosting.preweak_f_round(
            learner, lspec, s_b, hyp_space, Xs, ys, masks, pred_cache=cache
        )
        assert int(m_a["chosen"]) == int(m_b["chosen"])
    np.testing.assert_array_equal(np.asarray(s_a.weights), np.asarray(s_b.weights))
    np.testing.assert_array_equal(np.asarray(s_a.ensemble.alpha), np.asarray(s_b.ensemble.alpha))


def test_incremental_tally_matches_full_votes(vehicle):
    learner, lspec, Xs, ys, masks, Xte, yte = vehicle
    state = boosting.init_boost_state(learner, lspec, 4, masks, jax.random.PRNGKey(4))
    rfn = jax.jit(lambda s: boosting.adaboost_f_round(learner, lspec, s, Xs, ys, masks))
    tally = scoring.init_tally(Xte.shape[0], lspec.n_classes)
    tally_fn = jax.jit(
        lambda ens, tl: scoring.tally_new_votes(learner, lspec, ens, tl, Xte)
    )
    for _ in range(4):
        state, _ = rfn(state)
        tally = tally_fn(state.ensemble, tally)  # adds exactly ONE new member
        full = boosting.ensemble_votes(learner, lspec, state.ensemble, Xte)
        np.testing.assert_allclose(np.asarray(tally.votes), np.asarray(full), atol=1e-4)
    assert int(tally.counted) == 4


def test_round_predicts_once_per_hypothesis_space(vehicle):
    """Acceptance regression: no round function invokes learner.predict
    twice on the same (hypothesis, shard) pair — tracing a round must hit
    the predict path exactly once (vmap folds the H and C axes)."""
    learner, lspec, Xs, ys, masks, *_ = vehicle
    calls = {"n": 0}

    def counting(fn):
        def wrapped(spec, params, X):
            calls["n"] += 1
            return fn(spec, params, X)
        return wrapped

    # the tree predicts through ``predict_class``; count either predict path
    counted = dataclasses.replace(
        learner,
        predict_logits=counting(learner.predict_logits),
        predict_class=learner.predict_class and counting(learner.predict_class),
    )
    state = boosting.init_boost_state(counted, lspec, 2, masks, jax.random.PRNGKey(5))
    jax.make_jaxpr(
        lambda s: boosting.adaboost_f_round(counted, lspec, s, Xs, ys, masks)
    )(state)
    assert calls["n"] == 1, f"predict traced {calls['n']} times; hot path must predict once"

    # PreWeak.F with a cache must not predict AT ALL inside the round.
    T = 2
    st = boosting.init_boost_state(counted, lspec, T, masks, jax.random.PRNGKey(6))
    hyp_space, st = boosting.preweak_f_setup(learner, lspec, st, Xs, ys, masks, T)
    cache = boosting.preweak_f_predictions(learner, lspec, hyp_space, Xs)
    calls["n"] = 0
    jax.make_jaxpr(
        lambda s: boosting.preweak_f_round(
            counted, lspec, s, hyp_space, Xs, ys, masks, pred_cache=cache
        )
    )(st)
    assert calls["n"] == 0, "cached PreWeak.F round must be a pure reduction"


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("name", ["decision_tree", "extra_tree"])
def test_tree_predict_tensor_has_no_per_row_logit_gather(name):
    """The tree's scoring tensor picks each row's class from a per-leaf
    class table: its program gathers no K leaf logits a row and holds no
    ``[C, H, n, K]`` float buffer.  The column fetches ``X[:, f]`` stay.
    The logit-argmax formulation of the same predictions has both, so
    the check sees them when they come back."""
    from repro.learners.tree import init_tree, tree_predict_logits

    C, H, n, d, K = 3, 5, 40, 6, 3
    spec = LearnerSpec(name, d, K, {"depth": 4})
    learner = get_learner(name)
    hyps = jax.vmap(lambda k: init_tree(spec, k))(jax.random.split(jax.random.PRNGKey(0), H))
    X = jnp.zeros((C, n, d))

    def logit_gathers(fn):
        eqns = list(_eqns(jax.make_jaxpr(fn)(hyps, X).jaxpr))
        gathers = [e for e in eqns if e.primitive.name == "gather"
                   and e.params["slice_sizes"][-1] == K]
        buffers = [v for e in eqns for v in e.outvars
                   if v.aval.shape == (C, H, n, K) and v.aval.dtype == jnp.float32]
        return gathers, buffers

    assert logit_gathers(
        lambda h, x: scoring.predict_tensor(learner, spec, h, x)
    ) == ([], [])
    oracle = jax.vmap(lambda Xi: jax.vmap(
        lambda p: jnp.argmax(tree_predict_logits(spec, p, Xi), axis=-1))(hyps))
    gathers, buffers = logit_gathers(lambda h, x: oracle(x))
    assert gathers and buffers
