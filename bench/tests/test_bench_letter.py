"""The multiclass cell ``letter_c8.train`` (K = 26, depth-6 trees) at a
small size: a sound run is ``correct``, the control and each planted
fault break its limits, and ``hist_leaf_blocks`` counts the leaf blocks
of one fit."""
import jax
import numpy as np
import pytest

from bench import compare, control, run as bench_run
from bench.metrics import hist_leaf_blocks
from bench.tests import small

CELL = "letter_c8.train"


def test_sound_run_is_correct():
    res = small.run(CELL)
    assert res["correct"], res["checks"]
    assert res["metrics"]["round_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["control", "half", "alter", "no_exchange"])
def test_control_and_faults_fail_the_limits(fault):
    """The reference in bfloat16 (``control``), or with a planted fault,
    put in the program's place, as ``bench/control.py`` reads them at the
    cell's size: its numbers break at least one limit."""
    _, c, config, traffic = small.cell(CELL)
    run = bench_run.Run(c, config, traffic, seed=2**31 + 11, seconds=1.0, trace=False,
                        compiles=None)
    faults = [] if fault == "control" else [fault]
    checks = control.training(run, faults)[fault]
    limits = bench_run.load_json(bench_run.BENCH / "limits" / f"{CELL}.json")
    assert any(checks[k] > limits[k] for k in limits if k in checks), checks


def test_eval_skip_is_not_correct(monkeypatch):
    """``bench/control.py``'s eval fault (the tally leaves out the newest
    member of every fold) in the timed path, at the small size."""
    benchmark, c, config, traffic = small.cell(CELL)
    real = bench_run.run_cell
    monkeypatch.setattr(bench_run, "run_cell",
                        lambda *a, **kw: real(*a, config=config, traffic=traffic, **kw))
    checks = control.eval_skip(benchmark, c, 2**31 + 7, 1.0, jax.devices())
    limits = bench_run.load_json(bench_run.BENCH / "limits" / f"{CELL}.json")
    assert checks["f1_gap"] > limits["f1_gap"], checks


def test_reference_follows_itself_at_k26():
    """The reference's own members, followed, read every gap as 0."""
    _, c, config, traffic = small.cell(CELL)
    run = bench_run.Run(c, config, traffic, seed=5, seconds=1.0, trace=False, compiles=None)
    X, y, Xte, yte = (np.asarray(a) for a in run.data())
    fed, K = config["federation"], config["data"]["n_classes"]
    perm = np.asarray(jax.random.permutation(run.key("partition"), X.shape[0]))
    want = compare.reference_members(X, y, perm, fed, K, 3)
    assert want["leaf_logits"].shape == (3, 64, 26)
    same = compare.training(want, X, y, perm, fed, K, 3, count=3, rounds_done=3, f1_row=None,
                            X_test=Xte, y_test=yte)
    assert all(v == 0 for v in same.values()), same


@pytest.mark.parametrize("name, want", [("letter_c8", 7), ("adult_c8", 4)])
def test_hist_leaf_blocks_counts_one_fit(monkeypatch, name, want):
    """One traced collaborator-batched fit at the configuration's shapes,
    on the interpreted kernel: letter's depth-6 fit at K = 26 splits its
    32-leaf level in two blocks (1+1+1+1+1+2), adult's depth-4 fit takes
    one block a level."""
    from repro.kernels import ops
    from repro.learners import LearnerSpec
    from repro.learners.tree import fit_tree_batched
    from repro.obs import metrics

    registry = metrics.MetricsRegistry()
    family = registry.counter(ops._M_LEAF_BLOCKS.name, labels=ops._M_LEAF_BLOCKS.label_names)
    monkeypatch.setattr(metrics, "REGISTRY", registry)
    monkeypatch.setattr(ops, "_M_LEAF_BLOCKS", family)
    assert hist_leaf_blocks.read(None) is None

    config = bench_run.load_json(bench_run.BENCH / "configs" / f"{name}.json")
    data, fed = config["data"], config["federation"]
    C = fed["collaborators"]
    n, d, K = data["n_train"] // C, data["n_features"], data["n_classes"]
    spec = LearnerSpec(fed["learner"], d, K, dict(fed["hparams"]))
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in
              [((C, n, d), np.float32), ((C, n), np.int32), ((C, n), np.float32)]]
    keys = jax.random.split(jax.random.PRNGKey(0), C)
    jax.eval_shape(lambda X, y, w: fit_tree_batched(spec, X, y, w, keys, use_pallas=True),
                   *shapes)
    assert hist_leaf_blocks.read(None) == want
