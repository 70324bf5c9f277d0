"""The round's steps are named scopes inside its one jitted program.

``fit`` (with every level histogram under ``hist``), ``score`` and
``aggregate`` — plus ``exchange`` on the SPMD round — travel in each
HLO op's ``op_name`` metadata, so a profiler trace of the fused program
attributes device time to the round's steps without splitting it.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

SRC = Path(__file__).resolve().parent.parent / "src"
OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_paths(hlo_text: str):
    """Every op's scope path in compiled HLO text, as segment lists."""
    return [name.split("/") for name in OP_NAME.findall(hlo_text)]


def has_path(paths, *segments) -> bool:
    """Some path holds ``segments`` in this order (not necessarily adjacent)."""
    for path in paths:
        it = iter(path)
        if all(seg in it for seg in segments):
            return True
    return False


@pytest.fixture(scope="module")
def tiny():
    from repro.data import get_dataset
    from repro.fl.partition import iid_partition
    from repro.learners import LearnerSpec

    dspec, (Xtr, ytr, _, _) = get_dataset("vehicle", jax.random.PRNGKey(0))
    Xs, ys, masks = iid_partition(Xtr, ytr, 4, jax.random.PRNGKey(1))
    lspec = LearnerSpec("decision_tree", dspec.n_features, dspec.n_classes,
                        {"depth": 3, "n_bins": 8})
    return Xs, ys, masks, lspec


@pytest.mark.parametrize("algorithm, steps", [
    ("adaboost_f", ("fit", "score", "aggregate")),
    ("distboost_f", ("fit", "score", "aggregate")),
    ("bagging", ("fit", "aggregate")),
])
def test_fused_round_ops_carry_step_scopes(tiny, algorithm, steps):
    from repro.core import boosting
    from repro.learners import get_learner

    Xs, ys, masks, lspec = tiny
    learner = get_learner(lspec.name)
    committee = Xs.shape[0] if algorithm == "distboost_f" else None
    state = boosting.init_boost_state(learner, lspec, 3, masks, jax.random.PRNGKey(2),
                                      committee_size=committee, X=Xs)
    base = boosting.ROUND_FNS[algorithm]
    text = jax.jit(
        lambda s, X, y, m: base(learner, lspec, s, X, y, m, batched_fit=True)
    ).lower(state, Xs, ys, masks).compile().as_text()
    paths = scope_paths(text)
    for step in steps:
        assert has_path(paths, step), (step, sorted({tuple(p[1:2]) for p in paths}))
    # the level histograms are a step of the fit, and only of the fit
    assert has_path(paths, "fit", "hist")
    assert all("fit" in p[: p.index("hist")] for p in paths if "hist" in p)
    # the steps do not nest in one another
    assert not any(sum(s in p for s in steps) > 1 for p in paths)


def test_hetero_round_ops_carry_step_scopes(tiny):
    from repro.core import hetero
    from repro.core.hetero import HeterogeneousSpec
    from repro.learners import LearnerSpec

    Xs, ys, masks, lspec = tiny
    nb = LearnerSpec("gaussian_nb", lspec.n_features, lspec.n_classes)
    hspec = HeterogeneousSpec(specs=(lspec, nb), assignment=(0, 0, 1, 1))
    state = hetero.init_hetero_boost_state(hspec, 3, masks, jax.random.PRNGKey(2), X=Xs)
    text = jax.jit(
        lambda s, X, y, m: hetero.hetero_adaboost_f_round(hspec, s, X, y, m)
    ).lower(state, Xs, ys, masks).compile().as_text()
    paths = scope_paths(text)
    assert has_path(paths, "fit", "hist")
    assert has_path(paths, "score") and has_path(paths, "aggregate")


def test_elastic_round_ops_carry_step_scopes(tiny):
    from repro.core import boosting
    from repro.fl.elastic import elastic_adaboost_f_stages, run_elastic_stages
    from repro.learners import get_learner

    Xs, ys, masks, lspec = tiny
    learner = get_learner(lspec.name)
    state = boosting.init_boost_state(learner, lspec, 3, masks, jax.random.PRNGKey(2), X=Xs)
    stages = elastic_adaboost_f_stages(learner, lspec)
    part = jnp.ones((Xs.shape[0],), jnp.float32)
    text = jax.jit(
        lambda s, X, y, m, p: run_elastic_stages(stages, s, X, y, m, p)
    ).lower(state, Xs, ys, masks, part).compile().as_text()
    paths = scope_paths(text)
    assert has_path(paths, "fit", "hist")
    assert has_path(paths, "score") and has_path(paths, "aggregate")


SHARDED_SCRIPT = textwrap.dedent(
    """
    import json, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.core import boosting
    from repro.data import get_dataset
    from repro.fl.partition import iid_partition
    from repro.fl.sharded import federation_mesh, sharded_adaboost_round
    from repro.learners import LearnerSpec, get_learner

    mesh = federation_mesh(4)
    spec_d, (Xtr, ytr, _, _) = get_dataset("vehicle", jax.random.PRNGKey(0))
    Xs, ys, masks = iid_partition(Xtr, ytr, 4, jax.random.PRNGKey(1))
    lspec = LearnerSpec("decision_tree", spec_d.n_features, spec_d.n_classes, {"depth": 3})
    learner = get_learner("decision_tree")
    with jax.set_mesh(mesh):
        state = boosting.init_boost_state(learner, lspec, 3, masks, jax.random.PRNGKey(2), X=Xs)
        fn = jax.jit(lambda s, X, y, m: sharded_adaboost_round(learner, lspec, mesh, s, X, y, m))
        text = fn.lower(state, Xs, ys, masks).compile().as_text()
    ops = []
    for line in text.splitlines():
        m = re.search(r"= \\S+ ([a-z-]+)\\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name:
            ops.append([m.group(1), name.group(1)])
    print("SCOPES " + json.dumps(ops))
    """
)


def test_sharded_round_ops_carry_step_scopes():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [l for l in proc.stdout.splitlines() if l.startswith("SCOPES ")]
    ops = json.loads(line[len("SCOPES "):])
    paths = [name.split("/") for _, name in ops]
    assert has_path(paths, "fit", "hist")
    for step in ("exchange", "score", "aggregate"):
        assert has_path(paths, step), step
    collectives = [name for kind, name in ops if kind.startswith(("all-gather", "all-reduce"))]
    assert collectives
    assert all("exchange" in name.split("/") for name in collectives), collectives
