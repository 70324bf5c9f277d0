"""Process-per-collaborator runtime (fl/distributed.py).

The headline assertion: an N=4 MULTI-PROCESS federation — four OS
processes exchanging rounds over real gloo collectives — is bit-for-bit
identical to the single-process fused federation, per algorithm:
history rows (f1/epsilon/alpha/chosen), final sample weights, and every
leaf of the final ensemble.  Plus the packed wire format round-trips
in-process and across processes.

Subprocess layout mirrors tests/test_sharded.py: the children pop
XLA_FLAGS (one real device per process) and run from src/ on the path.
"""
import json
import os
import subprocess
import sys
import socket
import tempfile
import textwrap
from pathlib import Path

import numpy as np
import pytest

SRC = Path(__file__).resolve().parent.parent / "src"

ALGOS = ["adaboost_f", "distboost_f", "bagging", "preweak_f"]
C, T = 4, 3

# Shared by the in-process fused reference and the spawned collaborators:
# same dataset keys, same partition, same spec — so any result divergence
# is the runtime's fault, never the harness's.
def _setup_src(c: int, t: int) -> str:
    return textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.plan import adaboost_plan, bagging_plan
        from repro.data import get_dataset
        from repro.fl.partition import iid_partition
        from repro.learners import LearnerSpec

        C, T = {C}, {T}
        dspec, (Xtr, ytr, Xte, yte) = get_dataset("vehicle", jax.random.PRNGKey(0))
        Xs, ys, masks = iid_partition(Xtr, ytr, C, jax.random.PRNGKey(1))
        lspec = LearnerSpec("decision_tree", dspec.n_features, dspec.n_classes,
                            {{"depth": 3, "n_bins": 8}})

        def make_plan(alg):
            return (bagging_plan(rounds=T) if alg == "bagging"
                    else adaboost_plan(rounds=T, algorithm=alg))
        """
    ).format(C=c, T=t)


_SETUP = _setup_src(C, T)

_CHILD = textwrap.dedent(
    """
    import sys
    from repro.fl import distributed as dist

    # before ANY jax computation (the setup block below runs some)
    pid, nproc, coord, out = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    dist.initialize(coord, nproc, pid)
    """
) + _SETUP + textwrap.dedent(
    """
    results = {}
    for alg in %r:
        fed = dist.DistributedFederation(
            make_plan(alg), Xs, ys, masks, Xte, yte, lspec, jax.random.PRNGKey(2))
        hist = fed.run(eval_every=1)
        if dist.is_main():
            st = fed.state
            results[f"{alg}_weights"] = np.asarray(st.weights)
            results[f"{alg}_ens_alpha"] = np.asarray(st.ensemble.alpha)
            results[f"{alg}_ens_count"] = np.asarray(st.ensemble.count)
            for i, leaf in enumerate(jax.tree.leaves(st.ensemble.params)):
                results[f"{alg}_ens_{i}"] = np.asarray(leaf)
            for k in ("f1", "epsilon", "alpha", "chosen"):
                results[f"{alg}_hist_{k}"] = np.asarray([row[k] for row in hist])
            results[f"{alg}_comm_bytes"] = np.asarray(fed.comm_bytes)
    if dist.is_main():
        np.savez(out, **results)
        print("EQUIV_CHILD_OK", flush=True)
    """
) % (ALGOS,)


def _child_env():
    env = dict(
        os.environ,
        PYTHONPATH=os.pathsep.join(
            p for p in [str(SRC), os.environ.get("PYTHONPATH", "")] if p
        ),
        JAX_PLATFORMS="cpu",
    )
    env.pop("XLA_FLAGS", None)  # one real device per process
    return env


def _free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _fused_reference():
    """Single-process fused federation results for every algorithm."""
    import jax

    from repro.fl.federation import Federation

    ns = {}
    exec(compile(_SETUP, "<setup>", "exec"), ns)
    out = {}
    for alg in ALGOS:
        fed = Federation(
            ns["make_plan"](alg), ns["Xs"], ns["ys"], ns["masks"],
            ns["Xte"], ns["yte"], ns["lspec"], jax.random.PRNGKey(2),
        )
        hist = fed.run(eval_every=1)
        st = fed._fused_state
        out[alg] = {
            "weights": np.asarray(st.weights),
            "ens_alpha": np.asarray(st.ensemble.alpha),
            "ens_count": np.asarray(st.ensemble.count),
            "ens_leaves": [np.asarray(l) for l in jax.tree.leaves(st.ensemble.params)],
            "hist": {
                k: np.asarray([row[k] for row in hist])
                for k in ("f1", "epsilon", "alpha", "chosen")
            },
        }
    return out


def test_multiprocess_equals_fused_bitforbit(tmp_path):
    """4 processes over gloo collectives == 1 fused process, to the bit,
    for all four MAFL algorithms (decision_tree — a batch-invariant fit)."""
    coord = f"127.0.0.1:{_free_port()}"
    out = tmp_path / "dist_results.npz"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(i), str(C), coord, str(out)],
            env=_child_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for i in range(C)
    ]
    outs = [p.communicate(timeout=1200)[0] for p in procs]
    for i, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{o[-3000:]}"
    assert "EQUIV_CHILD_OK" in outs[0]

    got = np.load(out)
    ref = _fused_reference()
    for alg in ALGOS:
        r = ref[alg]
        np.testing.assert_array_equal(
            got[f"{alg}_weights"], r["weights"], err_msg=f"{alg}: weights"
        )
        np.testing.assert_array_equal(
            got[f"{alg}_ens_alpha"], r["ens_alpha"], err_msg=f"{alg}: ensemble alpha"
        )
        assert int(got[f"{alg}_ens_count"]) == int(r["ens_count"]), alg
        for i, leaf in enumerate(r["ens_leaves"]):
            np.testing.assert_array_equal(
                got[f"{alg}_ens_{i}"], leaf, err_msg=f"{alg}: ensemble leaf {i}"
            )
        for k, v in r["hist"].items():
            np.testing.assert_array_equal(
                got[f"{alg}_hist_{k}"], v, err_msg=f"{alg}: history {k}"
            )
        # real collectives moved real bytes (3 gathers/round for adaboost)
        assert int(got[f"{alg}_comm_bytes"]) > 0, alg


def test_pack_unpack_roundtrip():
    """The packed one-buffer wire format is lossless for f32 + i32 pytrees
    (f32 leaves travel bitcast through the i32 buffer)."""
    import jax
    import jax.numpy as jnp

    from repro.fl.sharded import _pack_leaves, _unpack_leaves

    tree = {
        "thr": jnp.linspace(-3.0, 7.0, 13, dtype=jnp.float32).reshape(13),
        "feat": jnp.arange(-5, 7, dtype=jnp.int32).reshape(3, 4),
        "leaf": jnp.array([[1.5, -0.0], [np.inf, 2.0**-30]], jnp.float32),
    }
    buf, fmt = _pack_leaves(tree)
    out = _unpack_leaves(buf, fmt)
    for k in tree:
        assert out[k].dtype == tree[k].dtype, k
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(tree[k]), err_msg=k)
    # gathered form: a stacked [P, L] buffer unpacks with a lead dim; an
    # all-gather lowered to a sum into zeros (XLA:TPU) leaves it exact
    assert buf.dtype == jnp.int32
    stacked = jnp.stack([buf, buf]) + jnp.zeros((2,) + buf.shape, buf.dtype)
    out2 = _unpack_leaves(stacked, fmt, lead=(2,))
    for k in tree:
        assert out2[k].shape == (2,) + tree[k].shape, k
        np.testing.assert_array_equal(np.asarray(out2[k][1]), np.asarray(tree[k]))


_WIRE_CHILD = textwrap.dedent(
    """
    import sys
    import jax, jax.numpy as jnp, numpy as np
    from repro.fl import distributed as dist
    from repro.fl.sharded import _pack_leaves, _unpack_leaves
    from jax.experimental import multihost_utils

    pid, nproc, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    dist.initialize(coord, nproc, pid)

    def tree_for(p):
        return {
            "thr": jnp.arange(6, dtype=jnp.float32) * (p + 1) - 2.5,
            "feat": (jnp.arange(8, dtype=jnp.int32) + 11 * p).reshape(2, 4),
        }

    buf, fmt = _pack_leaves(tree_for(pid))
    g = multihost_utils.process_allgather(buf, tiled=False)  # [P, L]
    out = _unpack_leaves(jnp.asarray(g), fmt, lead=(nproc,))
    for p in range(nproc):
        want = tree_for(p)
        for k in want:
            row = np.asarray(out[k][p])
            assert row.dtype == want[k].dtype, (k, row.dtype)
            np.testing.assert_array_equal(row, np.asarray(want[k]),
                                          err_msg=f"src process {p}, leaf {k}")
    print("WIRE_OK", flush=True)
    """
)


def test_wire_format_cross_process_roundtrip():
    """Each process packs a distinct hypothesis pytree; after one gather
    every process reconstructs every sender's tree bit-for-bit."""
    coord = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WIRE_CHILD, str(i), "2", coord],
            env=_child_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for i, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{o[-3000:]}"
        assert "WIRE_OK" in o, o[-3000:]


def test_single_process_equals_fused_inprocess():
    """C=1 needs no coordinator (process_count() is already 1), so it runs
    in-process — and covers the single-process gather edge the scaling
    bench's P=1 base point relies on (process_allgather returns the input
    unstacked when there is only one process)."""
    import jax

    from repro.fl import distributed as dist
    from repro.fl.federation import Federation

    ns = {}
    exec(compile(_setup_src(1, 3), "<setup>", "exec"), ns)
    for alg in ("adaboost_f", "bagging"):  # errors+mis gathers / hyps-only
        dfed = dist.DistributedFederation(
            ns["make_plan"](alg), ns["Xs"], ns["ys"], ns["masks"],
            ns["Xte"], ns["yte"], ns["lspec"], jax.random.PRNGKey(2),
        )
        dhist = dfed.run(eval_every=1)
        fed = Federation(
            ns["make_plan"](alg), ns["Xs"], ns["ys"], ns["masks"],
            ns["Xte"], ns["yte"], ns["lspec"], jax.random.PRNGKey(2),
        )
        fhist = fed.run(eval_every=1)
        np.testing.assert_array_equal(
            np.asarray(dfed.state.weights),
            np.asarray(fed._fused_state.weights), err_msg=alg,
        )
        for dl, fl in zip(jax.tree.leaves(dfed.state.ensemble.params),
                          jax.tree.leaves(fed._fused_state.ensemble.params)):
            np.testing.assert_array_equal(np.asarray(dl), np.asarray(fl),
                                          err_msg=alg)
        assert [r["f1"] for r in dhist] == [r["f1"] for r in fhist], alg
        assert dfed.comm_bytes > 0  # the P=1 gathers still account payloads


def test_constructor_rejects_unsupported_topologies():
    """Process-count mismatch and fedavg fail fast at construction (the
    hetero rejection is exercised through fl_run's guard rails)."""
    import jax

    from repro.core.plan import fedavg_plan
    from repro.fl.distributed import DistributedFederation

    ns = {}
    exec(compile(_setup_src(2, 3), "<setup>", "exec"), ns)
    args = (ns["Xs"], ns["ys"], ns["masks"], ns["Xte"], ns["yte"],
            ns["lspec"], jax.random.PRNGKey(2))
    with pytest.raises(NotImplementedError, match="fedavg"):
        DistributedFederation(fedavg_plan(rounds=3), *args)
    # 2 collaborators, but this pytest process is a process-group of 1
    with pytest.raises(ValueError, match="process-per-collaborator"):
        DistributedFederation(ns["make_plan"]("adaboost_f"), *args)


def test_fl_spawn_smoke(tmp_path):
    """The launcher end-to-end: 2 local processes, convergence floor,
    history JSON with real comm accounting."""
    hist_out = tmp_path / "hist.json"
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.fl_spawn", "-n", "2",
            "--min-f1", "0.4", "--",
            "--dataset", "vehicle", "--rounds", "3", "--eval-every", "3",
            "--history-out", str(hist_out),
        ],
        env=_child_env(), capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "final F1" in proc.stdout
    payload = json.loads(hist_out.read_text())
    assert payload["processes"] == 2
    assert payload["packed_broadcast"] is True
    assert payload["comm_bytes"] > 0
    # adaboost_f: hypotheses + errors + mis = 3 collectives per round
    assert payload["collective_calls"] == 3 * 3
    assert payload["history"][-1]["f1"] >= 0.4
