"""Property tests for the elastic participation machinery.

Generalises the deterministic invariants in tests/test_elastic.py with
hypothesis-generated masks, weights, and error matrices.  Requires the
dev extra (hypothesis); deterministic seeded versions stay in tier 1.
"""
import pytest

pytest.importorskip("hypothesis", reason="property tests need the dev extra")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import scoring  # noqa: E402
from repro.fl.elastic import FaultPlan, staleness_discount  # noqa: E402

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

_C, _H, _N = 5, 7, 11


def _errs(seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random((_C, _H)), jnp.float32)


def _weights(seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.random((_C, _N)), jnp.float32) + 1e-3
    return w / jnp.sum(w)


def _mis(seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2, (_C, _N)), jnp.float32)


@given(seed=st.integers(0, 2**31 - 1))
def test_all_ones_mask_is_bitforbit_lockstep(seed):
    """An all-ones participation mask must reduce to the literal lockstep
    ops: the same sums and weights within ``scoring.CROSS_PATH_RTOL``."""
    errs, w, mis = _errs(seed), _weights(seed + 1), _mis(seed + 2)
    part = jnp.ones(_C, jnp.float32)
    mask = jnp.ones((_C, _N), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(scoring.masked_error_sum(errs, part)),
        np.asarray(jnp.sum(errs, axis=0)),
    )
    eps = jnp.sum(errs, axis=0)
    assert int(scoring.masked_argmin(eps, jnp.ones(_H, jnp.float32))) == \
        int(jnp.argmin(eps))
    assert float(scoring.participation_denom(w, part)) == 1.0
    np.testing.assert_allclose(
        np.asarray(scoring.masked_update_weights(w, mis, mask, part, 0.7)),
        np.asarray(scoring.update_weights(w, mis, mask, 0.7)),
        rtol=scoring.CROSS_PATH_RTOL, atol=0,
    )


@given(
    seed=st.integers(0, 2**31 - 1),
    part_bits=st.lists(st.booleans(), min_size=_C, max_size=_C).filter(any),
)
def test_masked_aggregation_invariant_in_dropped_rows(seed, part_bits):
    """Whatever a dropped collaborator's rows contain cannot move the
    aggregate: scrambling absent rows leaves the masked error sum, the
    denominator, and every responder's updated weights unchanged."""
    errs, w, mis = _errs(seed), _weights(seed + 1), _mis(seed + 2)
    part = jnp.asarray(part_bits, jnp.float32)
    mask = jnp.ones((_C, _N), jnp.float32)
    dropped = np.flatnonzero(~np.asarray(part_bits))
    if dropped.size == 0:
        return  # all-ones is the lockstep identity, covered above

    rng = np.random.default_rng(seed + 3)
    d = jnp.asarray(dropped)
    errs2 = errs.at[d].set(jnp.asarray(rng.random((d.size, _H)), jnp.float32) * 50)
    w2 = w.at[d].set(jnp.asarray(rng.random((d.size, _N)), jnp.float32))
    mis2 = mis.at[d].set(1.0 - mis[d])

    np.testing.assert_array_equal(
        np.asarray(scoring.masked_error_sum(errs, part)),
        np.asarray(scoring.masked_error_sum(errs2, part)),
    )
    assert float(scoring.participation_denom(w, part)) == \
        float(scoring.participation_denom(w2, part))
    resp = np.asarray(part_bits)
    wa = scoring.masked_update_weights(w, mis, mask, part, 0.9)
    wb = scoring.masked_update_weights(w, mis2, mask, part, 0.9)
    np.testing.assert_array_equal(np.asarray(wa)[resp], np.asarray(wb)[resp])


@given(
    gamma=st.floats(0.05, 1.0, allow_nan=False, allow_infinity=False),
    lateness=st.integers(0, 20),
)
def test_staleness_discount_monotone_and_bounded(gamma, lateness):
    d = staleness_discount(gamma, lateness)
    assert 0.0 < d <= 1.0
    assert staleness_discount(gamma, lateness + 1) <= d
    if lateness == 0:
        assert d == 1.0


@given(
    seed=st.integers(0, 2**31 - 1),
    rounds=st.integers(1, 12),
    n=st.integers(1, 8),
)
def test_fault_plan_schedule_is_a_pure_function_of_the_seed(seed, rounds, n):
    fp = FaultPlan(seed=seed, delay_p=0.3, delay_range_s=(0.1, 0.5), drop_p=0.2)
    a, b = fp.schedule(rounds, n), fp.schedule(rounds, n)
    np.testing.assert_array_equal(a.delay, b.delay)
    np.testing.assert_array_equal(a.drop, b.drop)
    np.testing.assert_array_equal(a.alive, b.alive)
    np.testing.assert_array_equal(a.offline, b.offline)
    assert a.delay.shape == (rounds, n) and a.delay.dtype == np.float64
