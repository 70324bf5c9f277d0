"""The Plan — MAFL's run-time configuration object (paper §4.1).

OpenFL's Plan is a YAML file naming the software components, the number
of rounds, and — after the MAFL extension — the *task vocabulary* that
composes a federated round.  Here the Plan is a typed dataclass tree,
loadable from YAML/dict, and **every field is honoured** (the paper calls
out that OpenFL silently overrode plan fields; we validate instead).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

try:  # PyYAML is available in this environment, but keep it optional.
    import yaml
except ImportError:  # pragma: no cover
    yaml = None

# The six tasks of the MAFL vocabulary (paper §4.1).  The first three are
# OpenFL's original DNN workflow; the last three are the MAFL extension.
STANDARD_TASKS = (
    "aggregated_model_validation",
    "train",
    "locally_tuned_model_validation",
)
MAFL_TASKS = (
    "weak_learners_validate",
    "adaboost_update",
    "adaboost_validate",
)
ALL_TASKS = STANDARD_TASKS + MAFL_TASKS


@dataclasses.dataclass(frozen=True)
class TaskSpec:
    name: str
    kind: str  # one of ALL_TASKS
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class OptimizationFlags:
    """The paper's §5.1 optimisation toggles, as TPU/JAX analogues.

    packed_serialization: single contiguous wire buffer per message
        (gRPC 2MB->32MB buffer-resize fix analogue).
    bounded_tensordb: keep only the last ``tensordb_retention`` rounds
        (the clean_up fix — constant memory + query time).
    fast_barrier: structural SPMD barrier instead of sleep-polling
        (10s/1s -> 0.01s sleep calibration analogue).
    fused_round: jit the whole federated round as one program
        (removes per-task dispatch overhead; beyond-paper).
    use_pallas: route the step-3/4 scoring reductions (error matrix,
        fused weight update) through the Pallas TPU kernels instead of
        the pure-jnp oracles (beyond-paper; off-TPU backends run the
        kernels in interpret mode, so the default is off).  The step-2
        tree-fit histogram (``kernels/tree_hist.py``) no longer waits
        for it: on the TPU every fit path runs the MXU kernel; off the
        TPU the flag runs that kernel interpreted, else its oracle.
    cache_predictions: predict-once caching (beyond-paper) —
        (a) PreWeak.F keeps a setup-time ``[C, C*T, n]`` prediction
        cache of its static hypothesis space, turning every round into
        a pure weighted reduction, and (b) ensemble evaluation keeps a
        running vote tally and scores only newly appended members
        instead of re-predicting all T slots each eval.
    batched_fit: collaborator-batched local fits (beyond-paper) — the
        fused round trains all C weak hypotheses as ONE tensor program
        via ``WeakLearner.fit_batched`` over the shard-static
        ``BinnedDataset`` fit cache, instead of a vmap of C independent
        fits; on the TPU the per-level histogram is a single
        ``tree_hist`` kernel launch whose grid folds the batch axis.
    tree_block_s / tree_block_d: sample/feature tile sizes of the
        ``tree_hist`` kernel; ``None`` (the default) derives them from
        the shape so they are legal for the TPU and fit its VMEM
        (``kernels/tree_hist.tiles``; ignored on the oracle path).
    """

    packed_serialization: bool = True
    bounded_tensordb: bool = True
    tensordb_retention: int = 2
    fast_barrier: bool = True
    fused_round: bool = True
    use_pallas: bool = False
    cache_predictions: bool = True
    batched_fit: bool = True
    tree_block_s: Optional[int] = None
    tree_block_d: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class RolePlan:
    nn: bool = False  # nn: False triggers the model-agnostic workflow (§4.1)
    rounds: int = 100
    sleep_s: float = 0.01  # polling interval when fast_barrier is off


@dataclasses.dataclass(frozen=True)
class LearnerPlan:
    name: str = "decision_tree"
    hparams: Dict[str, Any] = dataclasses.field(default_factory=dict)


# ``Plan.learners`` (heterogeneous federations): a non-empty tuple of
# LearnerPlans is cycled across collaborators — collaborator i trains
# learners[i % len(learners)].  ``Plan.learner`` is ignored when set.
# The model-agnostic workflow never inspects hypothesis structure, so
# any mix of registry keys is valid for adaboost_f/distboost_f/
# preweak_f/bagging; fedavg averages parameters and stays homogeneous.


@dataclasses.dataclass(frozen=True)
class DataPlan:
    dataset: str = "adult"
    n_collaborators: int = 8
    split: str = "iid"  # iid | dirichlet
    dirichlet_alpha: float = 0.5
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class Plan:
    aggregator: RolePlan = dataclasses.field(default_factory=RolePlan)
    collaborator: RolePlan = dataclasses.field(default_factory=RolePlan)
    tasks: List[TaskSpec] = dataclasses.field(default_factory=list)
    algorithm: str = "adaboost_f"  # adaboost_f | distboost_f | preweak_f | bagging | fedavg
    learner: LearnerPlan = dataclasses.field(default_factory=LearnerPlan)
    # heterogeneous federation: cycle these learner types across
    # collaborators (empty tuple == homogeneous, use ``learner``)
    learners: tuple = ()
    data: DataPlan = dataclasses.field(default_factory=DataPlan)
    optimizations: OptimizationFlags = dataclasses.field(default_factory=OptimizationFlags)

    def validate(self) -> "Plan":
        for t in self.tasks:
            if t.kind not in ALL_TASKS:
                raise ValueError(f"unknown task kind {t.kind!r}; vocabulary: {ALL_TASKS}")
        kinds = [t.kind for t in self.tasks]
        if self.algorithm in ("adaboost_f", "distboost_f", "preweak_f"):
            if "adaboost_update" not in kinds:
                raise ValueError(f"{self.algorithm} requires an adaboost_update task")
            if kinds.index("adaboost_update") < kinds.index("weak_learners_validate"):
                raise ValueError("adaboost_update must follow weak_learners_validate")
            if self.aggregator.nn or self.collaborator.nn:
                raise ValueError("model-agnostic workflow requires nn: False (paper §4.1)")
        if self.algorithm == "bagging" and "adaboost_update" in kinds:
            raise ValueError("bagging is obtained by OMITTING adaboost_update (paper §4.1)")
        if self.aggregator.rounds != self.collaborator.rounds:
            raise ValueError("aggregator and collaborator round counts must agree")
        if self.learners:
            if self.algorithm == "fedavg":
                raise ValueError(
                    "heterogeneous learners require the model-agnostic workflow; "
                    "fedavg averages parameters and cannot mix model families"
                )
            if not self.optimizations.fused_round:
                raise ValueError(
                    "heterogeneous learners require optimizations.fused_round: the "
                    "interpreted simulation stacks one hypothesis pytree per round"
                )
        return self


def adaboost_plan(**over: Any) -> Plan:
    """The default MAFL model-agnostic plan (paper's AdaBoost.F workflow)."""
    tasks = [
        TaskSpec("train", "train"),
        TaskSpec("weak_learners_validate", "weak_learners_validate"),
        TaskSpec("adaboost_update", "adaboost_update"),
        TaskSpec("adaboost_validate", "adaboost_validate"),
    ]
    return _build(tasks, algorithm=over.pop("algorithm", "adaboost_f"), **over)


def bagging_plan(**over: Any) -> Plan:
    tasks = [
        TaskSpec("train", "train"),
        TaskSpec("weak_learners_validate", "weak_learners_validate"),
        TaskSpec("adaboost_validate", "adaboost_validate"),
    ]
    return _build(tasks, algorithm="bagging", **over)


def fedavg_plan(**over: Any) -> Plan:
    """OpenFL's original three-task DNN workflow (standard FL baseline)."""
    tasks = [
        TaskSpec("aggregated_model_validation", "aggregated_model_validation"),
        TaskSpec("train", "train"),
        TaskSpec("locally_tuned_model_validation", "locally_tuned_model_validation"),
    ]
    nn_over = dict(over)
    rounds = nn_over.pop("rounds", 100)
    return Plan(
        aggregator=RolePlan(nn=True, rounds=rounds),
        collaborator=RolePlan(nn=True, rounds=rounds),
        tasks=tasks,
        algorithm="fedavg",
        **nn_over,
    ).validate()


def _build(tasks: List[TaskSpec], algorithm: str, rounds: int = 100, **over: Any) -> Plan:
    return Plan(
        aggregator=RolePlan(nn=False, rounds=rounds),
        collaborator=RolePlan(nn=False, rounds=rounds),
        tasks=tasks,
        algorithm=algorithm,
        **over,
    ).validate()


# ---------------------------------------------------------------------------
# YAML / dict round-trip
# ---------------------------------------------------------------------------


def plan_from_dict(d: Dict[str, Any]) -> Plan:
    def role(key: str) -> RolePlan:
        return RolePlan(**d.get(key, {}))

    tasks = [TaskSpec(**t) for t in d.get("tasks", [])]
    return Plan(
        aggregator=role("aggregator"),
        collaborator=role("collaborator"),
        tasks=tasks,
        algorithm=d.get("algorithm", "adaboost_f"),
        learner=LearnerPlan(**d.get("learner", {})),
        learners=tuple(LearnerPlan(**l) for l in d.get("learners", [])),
        data=DataPlan(**d.get("data", {})),
        optimizations=OptimizationFlags(**d.get("optimizations", {})),
    ).validate()


def plan_to_dict(p: Plan) -> Dict[str, Any]:
    d = dataclasses.asdict(p)
    d["learners"] = list(d.get("learners", ()))  # YAML has no tuple type
    return d


def load_plan(path: str) -> Plan:
    if yaml is None:  # pragma: no cover
        raise RuntimeError("PyYAML unavailable; use plan_from_dict")
    with open(path) as f:
        return plan_from_dict(yaml.safe_load(f))


def save_plan(p: Plan, path: str) -> None:
    if yaml is None:  # pragma: no cover
        raise RuntimeError("PyYAML unavailable; use plan_to_dict")
    with open(path, "w") as f:
        yaml.safe_dump(plan_to_dict(p), f)
