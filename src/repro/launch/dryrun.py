import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape) on the production meshes, record
memory_analysis / cost_analysis / collective schedule for §Roofline.

The two lines above MUST stay first: jax locks the device count on
first initialisation.  Everything below imports jax afterwards.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
  PYTHONPATH=src python -m repro.launch.dryrun --fl-round          # MAFL round
Results: experiments/dryrun/<arch>__<shape>__<mesh>.json (incremental).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import roofline  # noqa: E402
from repro.configs import INPUT_SHAPES, all_archs, get_arch  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import shardings, transformer  # noqa: E402
from repro.optim.optimizers import AdamWState, init_adamw  # noqa: E402

# cost_analysis() reports while-loop bodies once; unroll structural scans
# so the roofline reads true per-step totals (EXPERIMENTS.md §Dry-run).
transformer.set_dryrun_unroll(True)

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# long_500k applicability (DESIGN.md §4): constant-state or native-local
# architectures only; pure full-attention archs are skipped and recorded.
LONG_OK = {"xlstm-1.3b", "llama4-scout-17b-a16e"}


def combos(mesh_kind: str):
    for arch in sorted(all_archs()):
        for shape in INPUT_SHAPES.values():
            yield arch, shape.name, mesh_kind


def skip_reason(arch: str, shape_name: str) -> str | None:
    if shape_name == "long_500k" and arch not in LONG_OK:
        return (
            "long_500k requires sub-quadratic context handling; "
            f"{arch} is pure full-attention (no native local/SSM variant) — skip per brief"
        )
    return None


def _tokens_for(cfg, shape, batch_override=None):
    specs = M.input_specs(cfg, shape)
    return specs


def pad_heads(cfg, model_n: int = 16):
    """Pad attention heads up to a multiple of the model axis so attention
    shards instead of replicating (llama4: 40->48 heads; whisper: 20->32).
    Extra heads are structurally zero-initialised at runtime; for the
    dry-run only shapes matter.  §Perf iteration."""
    import dataclasses as _dc

    H, Kv = cfg.n_heads, cfg.n_kv_heads
    if H % model_n:
        H = -(-H // model_n) * model_n
    if H % Kv or (Kv % model_n and Kv > model_n):
        Kv = model_n if Kv != cfg.n_heads else H
    if Kv == cfg.n_heads and cfg.n_kv_heads == cfg.n_heads:
        Kv = H  # MHA stays MHA
    return _dc.replace(cfg, n_heads=H, n_kv_heads=Kv)


def _compile(cfg, shape, mesh, policy="baseline", zero1=False, accum=1):
    """Lower + compile one (arch, shape, mesh) under the current unroll mode."""
    shapes, axes = M.shapes_and_axes(cfg)
    pspecs = shardings.param_specs(cfg, shapes, axes, mesh, policy=policy)
    in_specs = M.input_specs(cfg, shape)
    ispecs = shardings.input_spec_tree(cfg, shape, in_specs, mesh)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(init_adamw, shapes)
            opt_pspecs = shardings.param_specs(
                cfg, shapes, axes, mesh, policy=policy, zero1=zero1
            )
            opt_specs = AdamWState(
                step=jax.sharding.PartitionSpec(), mu=opt_pspecs, nu=opt_pspecs
            )
            state_shapes = M.TrainState(shapes, opt_shapes)
            state_specs = M.TrainState(pspecs, opt_specs)

            def step(state, batch):
                return M.train_step(cfg, state, batch, accum=accum)

            jitted = jax.jit(
                step,
                in_shardings=(shardings.named(mesh, state_specs), shardings.named(mesh, ispecs)),
                out_shardings=(shardings.named(mesh, state_specs), None),
            )
            lowered = jitted.lower(state_shapes, in_specs)
        elif shape.kind == "prefill":

            def step(params, batch):
                return M.prefill(cfg, params, batch)

            jitted = jax.jit(
                step,
                in_shardings=(shardings.named(mesh, pspecs), shardings.named(mesh, ispecs)),
            )
            lowered = jitted.lower(shapes, in_specs)
        else:  # decode

            def step(params, state, token):
                return M.serve_step(cfg, params, state, token)

            jitted = jax.jit(
                step,
                in_shardings=(
                    shardings.named(mesh, pspecs),
                    shardings.named(mesh, ispecs["state"]),
                    shardings.named(mesh, ispecs["token"]),
                ),
                out_shardings=(None, shardings.named(mesh, ispecs["state"])),
            )
            lowered = jitted.lower(shapes, in_specs["state"], in_specs["token"])

        compiled = lowered.compile()
    return compiled, shapes, axes


def lower_one(arch: str, shape_name: str, mesh_kind: str, unrolled: bool = True,
              policy: str = "baseline", zero1: bool = False, accum: int = 1,
              padded_heads: bool = False, chunked_local: bool = True,
              grouped_dispatch: bool = False):
    """Up to two compiles per combo:
      * scanned  — realistic steady-state memory_analysis (scan bodies
        share buffers, as they would on TPU) + proof the combo lowers;
      * unrolled — cost_analysis / collective totals (XLA counts loop
        bodies once, so per-step totals need the unrolled module).
        Single-pod only: the roofline table is single-pod per the brief,
        so the multi-pod pass stops after the scanned compile.
    """
    from repro.models import attention as _attn

    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_devices = int(np.prod(list(mesh.shape.values())))
    if padded_heads:
        cfg = pad_heads(cfg, mesh.shape["model"])
    _attn.set_chunked_local(chunked_local)
    # "fsdp-gather" = baseline param layout + explicit weight-gather
    # constraints at every use (shardings.maybe_gather_weight)
    shardings.set_fsdp_weight_gather(policy == "fsdp-gather")
    spec_policy = "baseline" if policy == "fsdp-gather" else policy
    from repro.models import moe as _moe
    if grouped_dispatch:
        dp = int(np.prod([mesh.shape[a] for a in ("pod", "data") if a in mesh.shape]))
        _moe.set_dispatch_groups(dp)
    else:
        _moe.set_dispatch_groups(1)

    opts = dict(policy=spec_policy, zero1=zero1, accum=accum)
    t0 = time.time()
    transformer.set_dryrun_unroll(False)
    compiled_mem, shapes, axes = _compile(cfg, shape, mesh, **opts)
    mem = compiled_mem.memory_analysis()
    t_mem = time.time() - t0

    _, R = cfg.pattern()
    U = transformer.unroll_factor(R)
    extrapolated = False
    if unrolled:
        del compiled_mem
        t0 = time.time()
        transformer.set_dryrun_unroll(True)
        compiled, _, _ = _compile(cfg, shape, mesh, **opts)
        t_cost = time.time() - t0
        cost = compiled.cost_analysis()
        coll = roofline.parse_collectives(compiled.as_text(), n_devices)
        flops = float(cost.get("flops", 0.0))
        bytes_accessed = float(cost.get("bytes accessed", 0.0))
        if U < R:
            # Deep stack (granite 88, grok 64): the U-unrolled loop body is
            # counted once.  Compile a second, smaller unroll U2 and solve
            # linearly for the per-unit cost:  m(U) = out + U * unit.
            U2 = next((u for u in (4, 2, 1) if u < U and R % u == 0), 1)
            transformer.set_unit_unroll(U2)
            try:
                compiled2, _, _ = _compile(cfg, shape, mesh, **opts)
            finally:
                transformer.set_unit_unroll(None)
            cost2 = compiled2.cost_analysis()
            coll2 = roofline.parse_collectives(compiled2.as_text(), n_devices)

            def extra(mU, mU2):
                unit = (mU - mU2) / (U - U2)
                return mU + (R - U) * unit

            flops = extra(flops, float(cost2.get("flops", 0.0)))
            bytes_accessed = extra(
                bytes_accessed, float(cost2.get("bytes accessed", 0.0))
            )
            wire = extra(coll.wire_bytes, coll2.wire_bytes)
            ops = {
                k: int(round(extra(coll.ops.get(k, 0), coll2.ops.get(k, 0))))
                for k in set(coll.ops) | set(coll2.ops)
            }
            raw = {
                k: int(round(extra(coll.raw_bytes.get(k, 0), coll2.raw_bytes.get(k, 0))))
                for k in set(coll.raw_bytes) | set(coll2.raw_bytes)
            }
            coll = roofline.CollectiveStats(ops, raw, max(wire, 0.0))
            extrapolated = True
    else:
        compiled = compiled_mem  # collectives still parsed; flops undercount loops
        t_cost = 0.0
        cost = compiled.cost_analysis()
        coll = roofline.parse_collectives(compiled.as_text(), n_devices)
        flops = float(cost.get("flops", 0.0))
        bytes_accessed = float(cost.get("bytes accessed", 0.0))
    terms = roofline.roofline_terms(flops, bytes_accessed, coll.wire_bytes)
    mf = roofline.model_flops(cfg, shapes, axes, shape)
    del compiled
    total_p, active_p = roofline.param_counts(cfg, shapes, axes)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "n_devices": n_devices,
        "compile_seconds": {"scanned": round(t_mem, 1), "unrolled": round(t_cost, 1)},
        "cost_from_unrolled": unrolled,
        "cost_extrapolated": extrapolated,
        "unit_repeats": R,
        "unroll_used": U if unrolled else 1,
        "variant": {"policy": policy, "zero1": zero1, "accum": accum,
                    "padded_heads": padded_heads, "chunked_local": chunked_local,
                    "grouped_dispatch": grouped_dispatch},
        "memory": {
            k: int(getattr(mem, k, 0) or 0)
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        },
        "cost": {"flops_per_device": flops, "bytes_per_device": bytes_accessed},
        "collectives": coll.to_dict(),
        "roofline": terms,
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_devices,
        "useful_flops_ratio": (mf / n_devices) / flops if flops else None,
        "params_total": total_p,
        "params_active": active_p,
    }
    return result


def run_combo(arch, shape_name, mesh_kind, out_dir: Path, force=False,
              policy="baseline", zero1=False, accum=1,
              padded_heads=False, chunked_local=False, grouped_dispatch=False):
    out_dir.mkdir(parents=True, exist_ok=True)
    parts = []
    if policy != "baseline":
        parts.append(policy.replace("-", ""))
    if zero1:
        parts.append("zero1")
    if accum != 1:
        parts.append(f"accum{accum}")
    if padded_heads:
        parts.append("padheads")
    if chunked_local:
        parts.append("chunkedlocal")
    if grouped_dispatch:
        parts.append("groupdisp")
    suffix = ("__" + "_".join(parts)) if parts else ""
    path = out_dir / f"{arch}__{shape_name}__{mesh_kind}{suffix}.json"
    if path.exists() and not force:
        print(f"[skip-cached] {path.name}")
        return json.loads(path.read_text())
    reason = skip_reason(arch, shape_name)
    if reason:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "skipped": reason}
        path.write_text(json.dumps(result, indent=2))
        print(f"[skipped] {arch} x {shape_name}: noted")
        return result
    print(f"[lower] {arch} x {shape_name} x {mesh_kind} ...", flush=True)
    try:
        result = lower_one(arch, shape_name, mesh_kind, unrolled=(mesh_kind == "single"),
                           policy=policy, zero1=zero1, accum=accum,
                           padded_heads=padded_heads, chunked_local=chunked_local,
                           grouped_dispatch=grouped_dispatch)
        print(
            f"[ok] {arch} x {shape_name} x {mesh_kind}: "
            f"compile {result['compile_seconds']}s, "
            f"bottleneck {result['roofline']['bottleneck']}",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        result = {
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_kind,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(f"[FAIL] {arch} x {shape_name} x {mesh_kind}: {type(e).__name__}: {e}", flush=True)
    path.write_text(json.dumps(result, indent=2))
    return result


def run_fl_round(mesh_kind: str, out_dir: Path, force=False, packed=False):
    """Dry-run the paper's own workload: the SPMD AdaBoost.F round."""
    from repro.core import boosting
    from repro.fl.sharded import sharded_adaboost_round
    from repro.learners import LearnerSpec, get_learner

    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "__packed" if packed else ""
    path = out_dir / f"mafl-adaboost-f__fl_round__{mesh_kind}{suffix}.json"
    if path.exists() and not force:
        print(f"[skip-cached] {path.name}")
        return json.loads(path.read_text())

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_devices = int(np.prod(list(mesh.shape.values())))
    C = int(np.prod([mesh.shape[a] for a in ("pod", "data") if a in mesh.shape]))
    n, d, K, T = 65536, 54, 8, 100  # forestcover-scale shards
    lspec = LearnerSpec("decision_tree", d, K, {"depth": 4, "n_bins": 16})
    learner = get_learner("decision_tree")

    sds = jax.ShapeDtypeStruct
    mask = jnp.ones((C, n), jnp.float32)  # tiny, fine to allocate
    state = jax.eval_shape(
        lambda m: boosting.init_boost_state(learner, lspec, T, m, jax.random.PRNGKey(0)), mask
    )
    X = sds((C, n, d), jnp.float32)
    y = sds((C, n), jnp.int32)
    m = sds((C, n), jnp.float32)

    t0 = time.time()
    with jax.set_mesh(mesh):
        fn = jax.jit(
            lambda s, X, y, m: sharded_adaboost_round(
                learner, lspec, mesh, s, X, y, m, packed_broadcast=packed
            )
        )
        lowered = fn.lower(state, X, y, m)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    cost = compiled.cost_analysis()
    coll = roofline.parse_collectives(compiled.as_text(), n_devices)
    flops = float(cost.get("flops", 0.0))
    by = float(cost.get("bytes accessed", 0.0))
    result = {
        "arch": "mafl-adaboost-f",
        "shape": "fl_round",
        "mesh": mesh_kind,
        "packed_broadcast": packed,
        "n_devices": n_devices,
        "collaborators": C,
        "local_samples": n,
        "compile_seconds": round(t_compile, 1),
        "cost": {"flops_per_device": flops, "bytes_per_device": by},
        "collectives": coll.to_dict(),
        "roofline": roofline.roofline_terms(flops, by, coll.wire_bytes),
    }
    path.write_text(json.dumps(result, indent=2))
    print(f"[ok] MAFL fl_round x {mesh_kind}: {result['roofline']['bottleneck']}", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fl-round", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--policy", default="baseline",
                    choices=["baseline", "gather2d", "fsdp-gather"])
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--pad-heads", action="store_true")
    ap.add_argument("--chunked-local", action="store_true")
    ap.add_argument("--grouped-dispatch", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    out_dir = Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.fl_round:
        for mk in meshes:
            run_fl_round(mk, out_dir, force=args.force, packed=args.packed)
        return
    if args.all:
        for mk in meshes:
            for arch, shape_name, mesh_kind in combos(mk):
                run_combo(arch, shape_name, mesh_kind, out_dir, force=args.force)
            run_fl_round(mk, out_dir, force=args.force)
        return
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    for mk in meshes:
        run_combo(args.arch, args.shape, mk, out_dir, force=args.force,
                  policy=args.policy, zero1=args.zero1, accum=args.accum,
                  padded_heads=args.pad_heads, chunked_local=args.chunked_local,
                  grouped_dispatch=args.grouped_dispatch)


if __name__ == "__main__":
    main()
