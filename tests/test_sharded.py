"""SPMD (shard_map) MAFL round: multi-device equivalence with the
single-host fused round.  Runs in a subprocess so the 8-device
XLA_FLAGS setting never leaks into other tests (the dry-run owns the
512-device setting; everything else sees 1 device).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = Path(__file__).resolve().parent.parent / "src"

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import boosting
    from repro.core.metrics import f1_macro
    from repro.core.scoring import CROSS_PATH_RTOL
    from repro.fl.sharded import federation_mesh, sharded_adaboost_round, sharded_strong_predict
    from repro.learners import LearnerSpec, get_learner
    from repro.data import get_dataset
    from repro.fl.partition import iid_partition

    mesh = federation_mesh(4)  # (data=4, model=2) over the 8 devices
    key = jax.random.PRNGKey(0)
    spec_d, (Xtr, ytr, Xte, yte) = get_dataset("vehicle", key)
    Xs, ys, masks = iid_partition(Xtr, ytr, 4, jax.random.PRNGKey(1))
    lspec = LearnerSpec("decision_tree", spec_d.n_features, spec_d.n_classes, {"depth": 4})
    learner = get_learner("decision_tree")
    T = 6
    # X=Xs: both paths carry the shard-static BinnedDataset fit cache —
    # the SPMD round consumes it through the shard_map boundary.
    with jax.set_mesh(mesh):
        state = boosting.init_boost_state(learner, lspec, T, masks, jax.random.PRNGKey(2), X=Xs)
        assert state.fit_cache is not None
        rfn = jax.jit(lambda s, X, y, m: sharded_adaboost_round(learner, lspec, mesh, s, X, y, m))
        chosen = []
        for _ in range(T):
            state, metrics = rfn(state, Xs, ys, masks)
            chosen.append(int(metrics["chosen"]))
        n = Xte.shape[0] - Xte.shape[0] % 4
        pred = sharded_strong_predict(learner, lspec, mesh, state.ensemble, Xte[:n])
    f1_sharded = float(f1_macro(yte[:n], pred, lspec.n_classes))

    state2 = boosting.init_boost_state(learner, lspec, T, masks, jax.random.PRNGKey(2), X=Xs)
    host_fn = jax.jit(lambda s, X, y, m: boosting.adaboost_f_round(learner, lspec, s, X, y, m))
    chosen2 = []
    for _ in range(T):
        state2, m2 = host_fn(state2, Xs, ys, masks)
        chosen2.append(int(m2["chosen"]))
    pred2 = boosting.strong_predict(learner, lspec, state2.ensemble, Xte[:n])
    f1_host = float(f1_macro(yte[:n], pred2, lspec.n_classes))

    # the cross-path contract: same choices, weights and alphas within
    # CROSS_PATH_RTOL (protocol equivalence, not just outcome)
    assert chosen == chosen2, (chosen, chosen2)
    assert abs(f1_sharded - f1_host) < 1e-6, (f1_sharded, f1_host)
    for a, b in [(state.weights, state2.weights), (state.ensemble.alpha, state2.ensemble.alpha)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=CROSS_PATH_RTOL, atol=0)

    # serving: ONE engine spans the mesh — EngineConfig(mesh=...) routes
    # every static batch through fl/sharded.make_batch_predict (batch
    # axis split over the 4 data shards), bit-for-bit vs the local engine
    from repro.serve import EngineConfig, ServeEngine
    Xte_n = np.asarray(Xte[:n])
    want_serve = ServeEngine(learner, lspec, state.ensemble, batch_size=64).predict(Xte_n)
    with jax.set_mesh(mesh):
        mesh_eng = ServeEngine(
            learner, lspec, state.ensemble, config=EngineConfig(batch_size=64, mesh=mesh)
        )
        got_serve = mesh_eng.predict(Xte_n)
        with mesh_eng.scheduler(t_max_s=0.05) as sched:  # deadline loop on top
            ids = sched.submit(Xte_n[:5])
            sched_serve = sched.results(ids, timeout_s=60.0)
    np.testing.assert_array_equal(got_serve, want_serve)
    np.testing.assert_array_equal(sched_serve, want_serve[:5])
    try:  # multi-shard admission: B must divide over the federation shards
        ServeEngine(learner, lspec, state.ensemble,
                    config=EngineConfig(batch_size=30, mesh=mesh))
        raise SystemExit("admission must reject B=30 over 4 shards")
    except ValueError:
        pass
    print("SHARDED_OK", f1_sharded)
    """
)


def test_sharded_round_matches_host():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_OK" in proc.stdout
