"""Batch-invariance rules — the two PR 8 bug classes.

The distributed runtime (``fl/distributed.py``) is bit-for-bit equal to
the fused single-process simulation ONLY while every reduction on the
collective schedule is batch-invariant: a row-independent
``sum(x * w, -1)`` computes the same bits for any batch tiling, while a
matvec/``@``/``dot_general`` reassociates the contraction as the batch
dimension changes.  Likewise the fused round must seal its stage
boundaries with ``optimization_barrier`` — in the distributed runtime a
stage boundary is a real network collective, so XLA fusing a reduction
across it in the single-process program changes the bits.
"""
from __future__ import annotations

import ast

from repro.analysis import astutil
from repro.analysis.framework import Finding, Project, rule

# modules whose reductions sit on the distributed score/aggregate path
_SUBJECT_SUFFIXES = ("kernels/ref.py", "core/scoring.py")
_ROOT_SUFFIX = "fl/distributed.py"

_MATVEC_FUNCS = {"dot", "matmul", "einsum", "inner", "tensordot", "vdot"}


def _is_matvec_call(node: ast.Call, aliases) -> bool:
    tgt = astutil.call_target(node, aliases)
    if tgt is None:
        return False
    tail = tgt.rsplit(".", 1)[-1]
    if tail == "dot_general":
        return tgt.startswith("jax.") or tgt.startswith("lax.")
    if tail in _MATVEC_FUNCS:
        return tgt.startswith("jax.numpy.") or tgt.startswith("jnp.") or tgt.startswith("numpy.")
    return False


@rule(
    "batch-matvec",
    "matvec-shaped reduction (@ / jnp.dot / einsum) in a function on the "
    "distributed collective schedule — dot tilings are batch-size "
    "dependent, breaking N-process bit-exactness",
)
def check_batch_matvec(project: Project):
    roots_mods = project.modules_matching(_ROOT_SUFFIX)
    if not roots_mods:
        return
    graph = astutil.CallGraph(project)
    roots = [
        f.key for m in roots_mods for f in astutil.module_functions(m)
    ]
    reach = graph.reachable(iter(roots))
    for mod in project.modules_matching(*_SUBJECT_SUFFIXES):
        for fn in astutil.module_functions(mod):
            if fn.key not in reach:
                continue
            aliases = astutil.import_aliases(mod.tree)
            for node in ast.walk(fn.node):
                hit = None
                if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
                    hit = "@"
                elif isinstance(node, ast.Call) and _is_matvec_call(node, aliases):
                    hit = astutil.call_target(node, aliases)
                if hit:
                    yield Finding(
                        "batch-matvec", mod.rel, node.lineno,
                        f"{hit} inside {fn.name}, which is reachable from "
                        f"the distributed collective schedule ({_ROOT_SUFFIX})",
                        hint="reduce row-independently: "
                        "jnp.sum(x * w[None, :], axis=-1)",
                    )


@rule(
    "stage-barrier",
    "a fused stage-composition loop without an optimization_barrier (or "
    "a block on each stage's own jit) lets XLA fuse reductions across what "
    "the distributed runtime runs as a network collective",
)
def check_stage_barrier(project: Project):
    for mod in project.modules:
        for fn in astutil.module_functions(mod):
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.For):
                    continue
                try:
                    iter_src = ast.unparse(node.iter)
                except Exception:  # pragma: no cover
                    continue
                if "stage" not in iter_src.lower():
                    continue
                bound = astutil.assigned_names(node.target)
                calls_stage = any(
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Name)
                    and n.func.id in bound
                    for n in ast.walk(node)
                )
                if not calls_stage:
                    continue
                sealed = any(
                    isinstance(n, ast.Call)
                    and isinstance(n.func, (ast.Name, ast.Attribute))
                    and (astutil.dotted_name(n.func) or "").rsplit(".", 1)[-1]
                    in ("optimization_barrier", "block_until_ready")
                    for n in ast.walk(node)
                )
                if not sealed:
                    yield Finding(
                        "stage-barrier", mod.rel, node.lineno,
                        f"stage loop in {fn.name} composes stages with no "
                        "boundary seal",
                        hint="seal each boundary: state, carry = "
                        "jax.lax.optimization_barrier((state, carry)) — or "
                        "jit each stage separately and block on its carry",
                    )
