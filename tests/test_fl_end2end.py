"""End-to-end federation behaviour: fused vs interpreted equivalence,
non-IID, FedAvg workflow, checkpointing, and the data pipeline."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core.plan import OptimizationFlags, adaboost_plan, fedavg_plan
from repro.data import get_dataset
from repro.data.pipeline import TokenStreamConfig, token_batches
from repro.fl.federation import Federation
from repro.fl.partition import iid_partition
from repro.learners import LearnerSpec


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    dspec, (Xtr, ytr, Xte, yte) = get_dataset("vehicle", k1)
    Xs, ys, masks = iid_partition(Xtr, ytr, 4, k2)
    lspec = LearnerSpec("decision_tree", dspec.n_features, dspec.n_classes,
                        {"depth": 4, "n_bins": 16})
    return Xs, ys, masks, Xte, yte, lspec, k3


def test_fused_equals_interpreted(setup):
    """The §5.1 optimisations must not change the ML result."""
    Xs, ys, masks, Xte, yte, lspec, key = setup
    T = 6
    runs = {}
    for fused in (True, False):
        flags = OptimizationFlags(True, True, 2, True, fused)
        plan = adaboost_plan(rounds=T, optimizations=flags)
        fed = Federation(plan, Xs, ys, masks, Xte, yte, lspec, key)
        hist = fed.run(eval_every=T)
        runs[fused] = hist[-1]
    assert abs(runs[True]["f1"] - runs[False]["f1"]) < 1e-5
    assert abs(runs[True]["alpha"] - runs[False]["alpha"]) < 1e-4


def test_f1_improves_over_rounds(setup):
    Xs, ys, masks, Xte, yte, lspec, key = setup
    fed = Federation(adaboost_plan(rounds=12), Xs, ys, masks, Xte, yte, lspec, key)
    hist = fed.run(eval_every=3)
    assert hist[-1]["f1"] >= hist[0]["f1"] - 0.05
    assert hist[-1]["f1"] > 0.6


def test_interpreted_round_syncs_once_and_renormalizes(setup):
    """Regression for the batched-transfer refactor: the interpreted
    adaboost round now moves per-collaborator error rows, norms, and
    weight sums to the host as stacked arrays (one sync each) — the
    global renormalisation must still leave total weight mass at 1, and
    the recorded norms must equal a direct recomputation."""
    Xs, ys, masks, Xte, yte, lspec, key = setup
    flags = OptimizationFlags(True, True, 2, True, False)  # interpreted path
    fed = Federation(
        adaboost_plan(rounds=2, optimizations=flags),
        Xs, ys, masks, Xte, yte, lspec, key,
    )
    fed.run(eval_every=2)
    total = sum(float(jnp.sum(c.weights)) for c in fed.collaborators)
    assert abs(total - 1.0) < 1e-5
    # the stacked transfers must land as the same f64 host arrays the old
    # per-element float() loop produced
    norms = fed._round_scratch["norms"]
    errs = fed._round_scratch["errs"]
    assert norms.shape == (len(fed.collaborators),)
    assert norms.dtype == np.float64 and np.all(norms > 0)
    assert errs.dtype == np.float64 and errs.shape[0] == len(fed.collaborators)


def test_fedavg_workflow(setup):
    Xs, ys, masks, Xte, yte, _, key = setup
    lspec = LearnerSpec("mlp", Xs.shape[-1], 4, {"hidden": 32, "local_steps": 20})
    fed = Federation(fedavg_plan(rounds=8), Xs, ys, masks, Xte, yte, lspec, key)
    hist = fed.run()
    assert hist[-1]["f1"] > 0.6
    assert fed.comm_bytes > 0  # params actually travelled


def test_comm_accounting_scales_with_collaborators(setup):
    Xs, ys, masks, Xte, yte, lspec, key = setup
    flags = OptimizationFlags(True, True, 2, True, False)  # interpreted: real wire
    byts = {}
    for C in (2, 4):
        fed = Federation(
            adaboost_plan(rounds=3, optimizations=flags),
            Xs[:C], ys[:C], masks[:C], Xte, yte, lspec, key,
        )
        fed.run(eval_every=3)
        byts[C] = fed.comm_bytes
    # hypothesis-space broadcast is O(C^2): 4 collabs >> 2 collabs
    assert byts[4] > byts[2] * 2


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    save_checkpoint(tree, tmp_path / "ckpt")
    back = load_checkpoint(jax.tree.map(jnp.zeros_like, tree), tmp_path / "ckpt")
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(back["b"]["c"]), np.asarray(tree["b"]["c"]))


def test_token_pipeline_is_learnable_and_deterministic():
    cfg = TokenStreamConfig(vocab_size=128, seq_len=32, batch_size=4, seed=7)
    a = next(token_batches(cfg))["tokens"]
    b = next(token_batches(cfg))["tokens"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # same seed
    assert a.shape == (4, 33)
    assert int(a.max()) < 128 and int(a.min()) >= 0


@pytest.mark.parametrize("from_env", [True, False], ids=["env-set", "env-unset"])
def test_persistent_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """Entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
    says (the helper sets nothing), else in ``.jax_cache/`` at the repo root."""
    from pathlib import Path

    from repro.launch import jax_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        jax_cache.use_persistent_cache()
        repo_cache = Path(__file__).resolve().parent.parent / ".jax_cache"
        want = before if from_env else str(repo_cache)
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
