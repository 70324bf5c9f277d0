"""Async deadline serving + federation→serving checkpoint handoff.

The deadline scheduler must answer a lone request within its deadline
WITHOUT any flush, match the synchronous path bit for bit on arbitrary
ragged streams, and keep per-request latencies flowing into the engine
stats.  The publishing loop must emit loadable versioned artifacts whose
consumers (engine + vote cache) fold only the appended members.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_serve import _blobs, _small_ensemble

from repro.core import boosting
from repro.core.plan import adaboost_plan
from repro.fl.federation import Federation
from repro.learners import LearnerSpec, get_learner
from repro.serve import (
    EngineConfig,
    ServeEngine,
    ShardVoteCache,
    latest_artifact,
    load_artifact,
    publish_artifact,
)

# generous CI margin on top of a deadline: covers one warm batch run +
# thread wakeup jitter on a loaded shared runner
SLACK_S = 1.0


def _warm_engine(name="decision_tree", B=64, key=0):
    learner, spec, ens, X = _small_ensemble(name, jax.random.PRNGKey(key))
    engine = ServeEngine(learner, spec, ens, batch_size=B)
    want = engine.predict(np.asarray(X))  # warms the compile cache for B
    return engine, np.asarray(X), want


# ---------------------------------------------------------------------------
# Deadline scheduler
# ---------------------------------------------------------------------------


def test_lone_request_answered_within_deadline_no_flush():
    engine, X, want = _warm_engine()
    t_max = 0.2
    with engine.scheduler(t_max_s=t_max) as sched:
        t0 = time.perf_counter()
        (rid,) = sched.submit(X[0])
        got = sched.result(rid, timeout_s=t_max + SLACK_S)
        dt = time.perf_counter() - t0
    assert got == want[0]  # bit-for-bit the sync predict answer
    assert dt <= t_max + SLACK_S
    # the partial batch really ran padded to the static shape
    assert engine.stats.padded_rows >= engine.batch_size - 1
    assert len(engine.stats.request_latencies) == 1


def test_full_batch_dispatches_before_any_deadline():
    engine, X, want = _warm_engine(B=64)
    with engine.scheduler(t_max_s=60.0) as sched:  # deadline far away
        ids = sched.submit(X[:64])  # exactly one full batch
        got = sched.results(ids, timeout_s=10.0)  # answered long before 60s
    np.testing.assert_array_equal(got, want[:64])


def test_requests_carry_their_own_deadlines():
    engine, X, want = _warm_engine()
    with engine.scheduler(t_max_s=60.0) as sched:
        (rid,) = sched.submit(X[0], deadline_s=0.05)  # urgent override
        assert sched.result(rid, timeout_s=10.0) == want[0]
    # ...and the min-deadline triggers even when it is NOT the queue head
    with engine.scheduler(t_max_s=60.0) as sched:
        (slow,) = sched.submit(X[0])  # head: 60s deadline
        (fast,) = sched.submit(X[1], deadline_s=0.05)
        # the urgent request drags the whole partial batch out with it
        assert sched.result(slow, timeout_s=10.0) == want[0]
        assert sched.result(fast, timeout_s=10.0) == want[1]


def test_deadline_stream_matches_sync_bitforbit():
    engine, X, want = _warm_engine()
    with engine.scheduler(t_max_s=0.01) as sched:
        ids = []
        for i in range(0, X.shape[0], 7):  # ragged stream, NO flush ever
            ids.extend(sched.submit(X[i : i + 7]))
        got = sched.results(ids, timeout_s=30.0)
    np.testing.assert_array_equal(got, want)
    assert len(engine.stats.request_latencies) == X.shape[0]


def test_close_drains_pending_requests():
    engine, X, want = _warm_engine()
    sched = engine.scheduler(t_max_s=60.0)
    ids = sched.submit(X[:5])
    sched.close()  # dispatches the queued partial immediately
    np.testing.assert_array_equal(sched.results(ids), want[:5])
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(X[0])


def test_result_timeout_and_unknown_rid_raise():
    engine, X, want = _warm_engine()
    with engine.scheduler(t_max_s=60.0) as sched:
        (rid,) = sched.submit(X[0])
        with pytest.raises(KeyError, match="never submitted"):
            sched.result(10_000)  # would otherwise block forever
        with pytest.raises(TimeoutError):  # legit but still queued (60s deadline)
            sched.result(rid, timeout_s=0.05)
    # close() drained it; a second read of a popped answer must raise,
    # not hang (the worker will never notify again)
    assert sched.result(rid) == want[0]
    with pytest.raises(KeyError, match="already taken"):
        sched.result(rid)


# ---------------------------------------------------------------------------
# Mesh-backed engine (degenerate 1-device mesh; the multi-device case is
# covered by the subprocess test in test_sharded.py)
# ---------------------------------------------------------------------------


def test_engine_config_mesh_backend_matches_local():
    from repro.launch.mesh import make_host_mesh

    learner, spec, ens, X = _small_ensemble("decision_tree", jax.random.PRNGKey(40))
    Xn = np.asarray(X)
    want = ServeEngine(learner, spec, ens, batch_size=64).predict(Xn)
    mesh = make_host_mesh()
    # knobs travel inside the config OR as kwargs, never both — silently
    # preferring one source would serve under knobs the caller never set
    with pytest.raises(ValueError, match="inside the EngineConfig"):
        ServeEngine(learner, spec, ens, batch_size=64,
                    config=EngineConfig(batch_size=64, mesh=mesh))
    with jax.set_mesh(mesh):
        eng = ServeEngine(
            learner, spec, ens, config=EngineConfig(batch_size=64, mesh=mesh)
        )
        np.testing.assert_array_equal(eng.predict(Xn), want)
        with eng.scheduler(t_max_s=0.05) as sched:  # deadline loop composes
            ids = sched.submit(Xn[:5])
            np.testing.assert_array_equal(
                sched.results(ids, timeout_s=10.0), want[:5]
            )


# ---------------------------------------------------------------------------
# Federation checkpoint publishing → serving consumers
# ---------------------------------------------------------------------------


def _tiny_federation(rounds, key):
    X, y = _blobs(key, n=240)
    Xs = jnp.stack([X[:120], X[120:]])
    ys = jnp.stack([y[:120], y[120:]])
    masks = jnp.ones(ys.shape, jnp.float32)
    Xq, yq = _blobs(jax.random.fold_in(key, 9), n=100)
    spec = LearnerSpec("decision_tree", X.shape[1], 3, {"depth": 3, "n_bins": 8})
    plan = adaboost_plan(rounds=rounds)
    return Federation(plan, Xs, ys, masks, Xq, yq, spec, key), Xq


def test_federation_publishes_rolling_artifacts(tmp_path):
    fed, Xq = _tiny_federation(rounds=5, key=jax.random.PRNGKey(50))
    seen = []
    fed.run(
        eval_every=5, publish_every=2, publish_dir=tmp_path,
        on_checkpoint=lambda path, r: seen.append((path, r)),
    )
    # rounds 2, 4 and the final round 5
    assert [r for _, r in seen] == [2, 4, 5]
    assert fed.published == [p for p, _ in seen]
    assert latest_artifact(tmp_path) == fed.published[-1]
    counts = []
    for path, r in seen:
        art = load_artifact(path)
        assert art.manifest["publish_version"] == r
        assert art.manifest["round"] == r
        assert art.manifest["algorithm"] == "adaboost_f"
        counts.append(int(art.manifest["ensemble_count"]))
    assert counts == [2, 4, 5]  # capacity fixed, count grows append-only
    # the final checkpoint IS the fused state's ensemble
    want = np.asarray(
        boosting.strong_predict(
            fed.learner, fed.spec, fed._fused_state.ensemble, Xq
        )
    )
    art = load_artifact(latest_artifact(tmp_path))
    got = np.asarray(
        boosting.strong_predict(art.learner, art.spec, art.ensemble, Xq)
    )
    np.testing.assert_array_equal(got, want)


def test_publish_requires_dir_and_fused_path(tmp_path):
    fed, _ = _tiny_federation(rounds=2, key=jax.random.PRNGKey(51))
    with pytest.raises(ValueError, match="publish_dir"):
        fed.run(publish_every=1)
    with pytest.raises(ValueError, match="positive"):
        fed.run(publish_every=0, publish_dir=tmp_path)
    import dataclasses

    from repro.core.plan import OptimizationFlags

    interp_plan = dataclasses.replace(
        fed.plan, optimizations=OptimizationFlags(fused_round=False)
    )
    fed2 = Federation(
        interp_plan,
        jnp.stack([fed.collaborators[0].X, fed.collaborators[1].X]),
        jnp.stack([fed.collaborators[0].y, fed.collaborators[1].y]),
        jnp.stack([fed.collaborators[0].mask, fed.collaborators[1].mask]),
        fed.X_test, fed.y_test, fed.spec, fed.key,
    )
    with pytest.raises(ValueError, match="fused"):
        fed2.run(publish_every=1, publish_dir=tmp_path)


def test_checkpoint_consumers_fold_only_appended_members(tmp_path):
    """The train→publish→serve loop end to end: each checkpoint loads,
    hot-swaps into a live engine (no recompile) and vote cache, and the
    cache folds ONLY the appended members (``members_folded`` counts
    exactly the final member total)."""
    fed, Xq = _tiny_federation(rounds=6, key=jax.random.PRNGKey(52))
    engine = cache = None
    folded_per_checkpoint = []

    def consume(path, round_idx):
        nonlocal engine, cache
        art = load_artifact(path)
        if engine is None:
            engine = ServeEngine(art.learner, art.spec, art.ensemble, batch_size=64)
            engine.warmup()
            cache = ShardVoteCache(art.learner, art.spec, art.ensemble)
        else:
            engine.update_ensemble(art.ensemble)
            cache.update_ensemble(art.ensemble)
        before = cache.stats()["members_folded"]
        got = cache.predict("q", Xq)
        folded_per_checkpoint.append(cache.stats()["members_folded"] - before)
        # both consumers serve the checkpoint bit-for-bit
        want = np.asarray(
            boosting.strong_predict(art.learner, art.spec, art.ensemble, Xq)
        )
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(engine.predict(Xq), want)

    fed.run(eval_every=6, publish_every=2, publish_dir=tmp_path, on_checkpoint=consume)
    assert folded_per_checkpoint == [2, 2, 2]  # never re-folds old members
    assert cache.stats()["members_folded"] == 6
    assert cache.stats()["misses"] == 1  # one residency build, then appends
    # swaps never recompiled the predict (the one program may come warm
    # from the process-wide cache)
    assert engine.stats.compiles + engine.stats.cache_hits == 1
