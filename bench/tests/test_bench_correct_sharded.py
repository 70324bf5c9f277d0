"""``correct`` for the sharded cell, on four virtual CPU devices in a
child process: a sound run passes; with the exchange between chips left
out, the state left unchanged, half of each shard left out, or an alpha
altered where the round produces it, it fails."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import run as bench_run

SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    import jax.numpy as jnp
    from repro.fl import sharded

    real = sharded.sharded_adaboost_round
    fault = {fault!r}

    def stuck(learner, spec, mesh, s, X, y, m, **kw):
        return s, real(learner, spec, mesh, s, X, y, m, **kw)[1]

    def half(learner, spec, mesh, s, X, y, m, **kw):
        m = m.at[:, m.shape[1] // 2:].set(0.0)
        w = s.weights * m
        return real(learner, spec, mesh, s._replace(weights=w / jnp.sum(w)), X, y, m, **kw)

    def altered(learner, spec, mesh, s, X, y, m, **kw):
        s, metrics = real(learner, spec, mesh, s, X, y, m, **kw)
        ens = s.ensemble
        return s._replace(ensemble=ens._replace(alpha=ens.alpha.at[ens.count - 1].multiply(1.5))), metrics

    if fault == "no_exchange":
        sharded._multi_psum = lambda x, axes: x
    elif fault:
        sharded.sharded_adaboost_round = {{"stuck": stuck, "half": half, "altered": altered}}[fault]
    from bench.tests import small
    res = small.run("covtype_c4.sharded", n_train=8000, n_test=2000)
    print(json.dumps({{"correct": res["correct"], "checks": res["checks"],
                       "count": res["device"]["count"]}}))
""")


def _child(fault) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(bench_run.ROOT), src=str(bench_run.ROOT / "src"), fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_cell_sound_and_without_exchange():
    sound = _child(None)
    assert sound["count"] == 4
    assert sound["correct"], sound["checks"]
    broken = _child("no_exchange")
    assert not broken["correct"], broken["checks"]


@pytest.mark.parametrize("fault", ["stuck", "half", "altered"])
def test_fault_in_the_sharded_round_is_not_correct(fault):
    res = _child(fault)
    assert not res["correct"], res["checks"]
