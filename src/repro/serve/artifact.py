"""Deployable ensemble artifact — the federation's inference deliverable.

A trained strong hypothesis for ANY registered learner — or any MIX of
registered learners (``core/hetero.py``) — becomes one file:

    MAFLSRV1 | u32 manifest_len | manifest JSON | packed payload

The payload is ``core/serialization.serialize(ensemble, packed=True)`` —
every pytree leaf in one contiguous buffer, the same wire format the
federation exchanges hypotheses in.  The manifest is the model-agnostic
part: it names the learner (registry key), the learning problem
(n_features/n_classes/hparams), and the ensemble geometry (capacity T,
used count, committee size), which is exactly enough to rebuild the
pytree *structure* via ``learner.init`` + ``init_ensemble`` and pour the
payload back into it — no pickle, no code in the artifact.

Heterogeneous ensembles (format_version 2, ``"learner":
"heterogeneous"``) additionally record the per-group learner specs, the
collaborator→group ``assignment``, and the **per-member learner key
list** (``member_learners`` — which model family cast each used vote,
in the group-blocked member order), so a serving consumer knows exactly
what it is running without touching the payload.  ``load_artifact``
rejects manifests naming learner keys missing from this process's
registry with the documented ``ValueError`` — an artifact must never
silently deserialize into the wrong model family.

A still-training federation publishes a ROLLING artifact stream with
``publish_artifact``: each checkpoint is a fresh versioned file plus an
atomically-replaced ``LATEST`` pointer, so a serving consumer polling
``latest_artifact`` never reads a half-written file and (capacity being
fixed across checkpoints) folds each new version in as a pure append.
"""
from __future__ import annotations

import json
import struct
import time
import zlib
from pathlib import Path
from typing import Any, NamedTuple, Optional

import jax
import numpy as np

from repro.core import boosting, hetero
from repro.core.hetero import HeterogeneousSpec
from repro.core.serialization import (
    CODEC_BF16,
    CODEC_INT8,
    CODEC_RAW,
    CODEC_U8,
    decode_leaf,
    deserialize,
    encode_leaf,
    encoded_nbytes,
    outlier_rows,
    wire_format,
)
from repro.learners import LearnerSpec, WeakLearner, available_learners, get_learner

MAGIC = b"MAFLSRV1"
# Reader capability.  Homogeneous artifacts still write format_version 1
# (their layout is unchanged — old readers keep working); heterogeneous
# artifacts write 2; quantized artifacts (either flavour) write 3 and
# carry a per-leaf "leaf_codecs" list in the manifest.
MANIFEST_VERSION = 3
HOMOGENEOUS_VERSION = 1
HETERO_VERSION = 2
QUANTIZED_VERSION = 3
HETERO_LEARNER = "heterogeneous"  # the manifest "learner" key of a mix

QUANTIZE_MODES = ("bf16", "int8")
# float leaves below this share of the float payload stay raw: biases,
# thresholds, and priors are noise-sized but decision-critical
SMALL_LEAF_SHARE = 0.05


class LoadedArtifact(NamedTuple):
    learner: WeakLearner | None  # None for heterogeneous artifacts
    spec: LearnerSpec | HeterogeneousSpec
    ensemble: Any  # boosting.Ensemble | hetero.HeteroEnsemble
    committee_size: int | None  # DistBoost.F stores a committee per slot
    manifest: dict

    @property
    def committee(self) -> bool:
        return self.committee_size is not None

    @property
    def hetero(self) -> bool:
        return isinstance(self.spec, HeterogeneousSpec)


def ensemble_signature(ensemble: boosting.Ensemble) -> tuple:
    """Full structural identity of an ensemble pytree: treedef plus every
    leaf's (shape, dtype).  Two ensembles with equal signatures are
    interchangeable under a compiled serving program — this is the check
    both ``save_artifact`` (vs the manifest-derived template) and
    ``ServeEngine.update_ensemble`` (vs the live ensemble) apply."""
    leaves, treedef = jax.tree.flatten(ensemble)
    return treedef, [(tuple(l.shape), str(l.dtype)) for l in leaves]


def _require_learner(name: str, context: str) -> WeakLearner:
    """Registry lookup that raises the documented ``ValueError`` (an
    artifact naming a learner this process cannot build must be
    rejected, not crash with a bare KeyError)."""
    try:
        return get_learner(name)
    except KeyError:
        raise ValueError(
            f"{context}: unknown learner key {name!r}; "
            f"registered: {available_learners()}"
        ) from None


def _ensemble_template(
    spec: LearnerSpec, T: int, committee_size: int | None, *, context: str = "artifact"
) -> boosting.Ensemble:
    """The pytree structure an artifact's payload pours back into.

    ``init_ensemble`` is shape-deterministic (keys only seed values), so
    saver and loader independently derive the same treedef + leaf
    shapes from the manifest alone."""
    learner = _require_learner(spec.name, context)
    return boosting.init_ensemble(
        learner, spec, T, jax.random.PRNGKey(0), committee_size=committee_size
    )


def _hetero_template(
    hspec: HeterogeneousSpec, T: int, committee: bool, *, context: str = "artifact"
) -> hetero.HeteroEnsemble:
    for name in hspec.names:
        _require_learner(name, context)
    return hetero.init_hetero_ensemble(
        hspec, T, jax.random.PRNGKey(0), committee=committee
    )


# ---------------------------------------------------------------------------
# Quantization planning — which codec each leaf gets, and the
# vote-preserving calibration that promotes un-quantizable member slots
# ---------------------------------------------------------------------------


def _group_leaf_plans(params_leaves, mode: str) -> list:
    """Default per-leaf codec plan for ONE ensemble's params leaves."""
    float_total = sum(
        l.nbytes for l in params_leaves if np.issubdtype(l.dtype, np.floating)
    )
    plans = []
    for l in params_leaves:
        if np.issubdtype(l.dtype, np.integer):
            # host numpy leaves: int() here is a cast, not a device sync
            in_range = l.size == 0 or (int(l.min()) >= 0 and int(l.max()) <= 255)  # mafl: allow[host-sync]
            plans.append({"codec": CODEC_U8 if in_range else CODEC_RAW})
        elif not np.issubdtype(l.dtype, np.floating) or l.ndim < 2 \
                or l.nbytes < SMALL_LEAF_SHARE * float_total:
            plans.append({"codec": CODEC_RAW})
        elif mode == "bf16":
            plans.append({"codec": CODEC_BF16})
        else:
            plans.append({"codec": CODEC_INT8, "outlier_rows": outlier_rows(l),
                          "promoted_slots": []})
    return plans


def _plan_ensembles(ensembles: list, mode: str) -> list:
    """Per-leaf plans for the FULL artifact pytree flatten order — params
    leaves get the requested codec, alpha/count stay raw (they weight the
    vote tally directly; quantizing them would change served votes)."""
    if mode not in QUANTIZE_MODES:
        raise ValueError(f"quantize must be one of {QUANTIZE_MODES}, got {mode!r}")
    plans = []
    for ens in ensembles:
        params_leaves = [np.asarray(l) for l in jax.tree.flatten(ens.params)[0]]
        n_rest = len(jax.tree.flatten(ens)[0]) - len(params_leaves)
        plans += _group_leaf_plans(params_leaves, mode)
        plans += [{"codec": CODEC_RAW}] * n_rest  # alpha, count
    return plans


def _quantize_roundtrip(ensemble: Any, plans: list) -> Any:
    """What a consumer will serve: encode + decode every leaf."""
    leaves, treedef = jax.tree.flatten(ensemble)
    out = []
    for l, p in zip(leaves, plans):
        ln = np.asarray(l)
        out.append(
            jax.numpy.asarray(decode_leaf(encode_leaf(ln, p), p, ln.shape, ln.dtype))
        )
    return jax.tree.unflatten(treedef, out)


def _calibrate_plans(
    spec, ensemble, plans: list, calibrate, committee_size: int | None
) -> list:
    """Greedy vote-preserving promotion: serve the quantized ensemble on
    the calibration rows and, while any vote differs from the f32
    ensemble's, promote the member slot whose raw restoration fixes the
    most rows (its params are stored raw; alpha stays untouched either
    way).  Terminates at all-slots-raw, which is exact by construction —
    so the saved artifact's votes on the calibration set are bit-identical
    to the f32 artifact's."""
    X = jax.numpy.asarray(np.asarray(calibrate, np.float32))
    is_hetero = isinstance(spec, HeterogeneousSpec)
    committee = committee_size is not None

    def votes(ens):
        if is_hetero:
            return np.asarray(
                hetero.hetero_strong_predict(spec, ens, X, committee=committee)
            )
        learner = get_learner(spec.name)
        return np.asarray(
            boosting.strong_predict(learner, spec, ens, X, committee=committee)
        )

    ensembles = list(ensemble) if is_hetero else [ensemble]
    group_slices = []  # plan-index range per group
    off = 0
    for ens in ensembles:
        n = len(jax.tree.flatten(ens)[0])
        group_slices.append((off, off + n))
        off += n

    want = votes(ensemble)

    def rebuild(ps):
        groups = [
            _quantize_roundtrip(ens, ps[a:b])
            for ens, (a, b) in zip(ensembles, group_slices)
        ]
        return tuple(groups) if is_hetero else groups[0]

    flips = int((votes(rebuild(plans)) != want).sum())
    if flips == 0:
        return plans

    # Promotion actions: an int8 leaf can restore ONE member slot raw
    # (cheap — one slot's rows); a bf16 leaf has no per-slot sections,
    # so its only escape hatch is falling back to raw wholesale.
    actions: list = []
    for g, ens in enumerate(ensembles):
        a, b = group_slices[g]
        if any(p["codec"] == CODEC_INT8 for p in plans[a:b]):
            # ens.count is a host-side int-like; publish path, not a hot loop
            actions += [("slot", g, t) for t in range(int(ens.count))]  # mafl: allow[host-sync]
    actions += [
        ("leaf", i, None) for i, p in enumerate(plans) if p["codec"] == CODEC_BF16
    ]

    def apply(ps, action):
        kind, x, t = action
        if kind == "slot":
            a, b = group_slices[x]
            return [
                dict(p, promoted_slots=sorted(set(p["promoted_slots"]) | {t}))
                if a <= i < b and p["codec"] == CODEC_INT8 else p
                for i, p in enumerate(ps)
            ]
        return [dict(p, codec=CODEC_RAW) if i == x else p for i, p in enumerate(ps)]

    # Greedy: each round, apply the single action that fixes the most
    # calibration rows (ties → first).  Applying EVERY action makes the
    # round-trip the identity on all voting members, so the loop always
    # reaches flips == 0.
    applied: set = set()
    while flips > 0 and len(applied) < len(actions):
        best = None
        for act in actions:
            if act in applied:
                continue
            trial = apply(plans, act)
            # calibration search is offline; each trial's flip count gates
            # the next greedy step, so the sync is inherent
            ft = int((votes(rebuild(trial)) != want).sum())  # mafl: allow[host-sync]
            if best is None or ft < best[1]:
                best = (act, ft, trial)
        applied.add(best[0])
        flips, plans = best[1], best[2]
    return plans


def _demote_uneconomic(ensemble: Any, plans: list) -> list:
    """A quantized leaf whose encoded form ends up no smaller than raw
    (outlier rows + promoted slots ate the savings) ships raw instead —
    exactness is free and the artifact never grows past its f32 twin."""
    leaves = [np.asarray(l) for l in jax.tree.flatten(ensemble)[0]]
    return [
        {"codec": CODEC_RAW}
        if p["codec"] != CODEC_RAW
        and encoded_nbytes(p, l.shape, l.dtype) >= l.nbytes
        else p
        for l, p in zip(leaves, plans)
    ]


def _quantized_payload(ensemble: Any, plans: list) -> bytes:
    leaves = [np.asarray(l) for l in jax.tree.flatten(ensemble)[0]]
    if len(leaves) != len(plans):
        raise ValueError(f"{len(plans)} leaf plans for {len(leaves)} leaves")
    return b"".join(encode_leaf(l, p) for l, p in zip(leaves, plans))


def _maybe_quantize(
    spec, ensemble, quantize: Optional[str], calibrate, committee_size
):
    """Returns (payload, leaf_codecs): the payload as byte buffers whose
    concatenation is the file's payload; leaf_codecs is None unquantized.

    Unquantized, the buffers are the leaves themselves, fetched from the
    device in one transfer and checksummed and written in place: the
    bytes of ``serialize(ensemble, packed=True)`` without its joined copy
    (a rolling checkpoint of a large capacity spends most of its time
    copying)."""
    if quantize is None:
        leaves = jax.tree.leaves(jax.device_get(ensemble))
        return [np.ascontiguousarray(l).reshape(-1).view(np.uint8) for l in leaves], None
    ensembles = list(ensemble) if isinstance(spec, HeterogeneousSpec) else [ensemble]
    plans = _plan_ensembles(ensembles, quantize)
    if calibrate is not None:
        plans = _calibrate_plans(spec, ensemble, plans, calibrate, committee_size)
    plans = _demote_uneconomic(ensemble, plans)
    return [_quantized_payload(ensemble, plans)], plans


def _payload_fields(payload: list) -> dict:
    crc = 0
    for chunk in payload:
        crc = zlib.crc32(chunk, crc)
    return {"payload_bytes": sum(len(chunk) for chunk in payload), "payload_crc32": crc}


def save_artifact(
    path: str | Path,
    spec: LearnerSpec | HeterogeneousSpec,
    ensemble: Any,
    *,
    committee_size: int | None = None,
    extra: dict | None = None,
    quantize: str | None = None,
    calibrate: Any = None,
) -> Path:
    """Write a single-file serving artifact; returns the path.

    ``spec`` selects the artifact flavour: a ``LearnerSpec`` writes the
    v1 homogeneous manifest, a ``HeterogeneousSpec`` (with ``ensemble``
    the matching per-group tuple) writes the v2 heterogeneous one.  For
    heterogeneous committees (DistBoost.F) ``committee_size`` is the
    FEDERATION size — each slot stores one seat block per group.

    ``quantize`` ("bf16" or "int8") writes a v3 artifact whose payload
    leaves are individually encoded (the manifest records each leaf's
    codec + promoted slots; scales travel inside the payload).  With
    ``calibrate`` (an [n, d] row matrix), the saver verifies the
    dequantized ensemble's votes against the f32 ensemble on those rows
    and stores raw any member slot whose votes quantization would flip —
    the committed artifact serves bit-identical votes on the
    calibration set, and tree-structured learners are exact for ALL
    inputs (argmax repair preserves every leaf row's winner)."""
    if isinstance(spec, HeterogeneousSpec):
        return _save_hetero(
            Path(path), spec, ensemble, committee_size=committee_size, extra=extra,
            quantize=quantize, calibrate=calibrate,
        )
    path = Path(path)
    template = _ensemble_template(spec, ensemble.alpha.shape[0], committee_size)
    got, want = ensemble_signature(ensemble), ensemble_signature(template)
    if got != want:
        raise ValueError(
            f"ensemble does not match the {spec.name!r} template: {got} != {want}"
        )
    payload, plans = _maybe_quantize(
        spec, ensemble, quantize, calibrate, committee_size
    )
    manifest = {
        "format_version": HOMOGENEOUS_VERSION if plans is None else QUANTIZED_VERSION,
        "learner": spec.name,
        "n_features": spec.n_features,
        "n_classes": spec.n_classes,
        "hparams": dict(spec.hparams),
        "ensemble_capacity": int(ensemble.alpha.shape[0]),
        "ensemble_count": int(ensemble.count),
        "committee_size": committee_size,
        **_payload_fields(payload),
    }
    if plans is not None:
        manifest["quantize"] = quantize
        manifest["leaf_codecs"] = plans
    return _write(path, manifest, payload, extra)


def _write(path: Path, manifest: dict, payload: list, extra: dict | None) -> Path:
    overlap = set(extra or {}) & set(manifest)
    if overlap:
        raise ValueError(f"extra manifest keys shadow required fields: {sorted(overlap)}")
    manifest.update(extra or {})
    blob = json.dumps(manifest, sort_keys=True).encode()
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<I", len(blob)))
        f.write(blob)
        for chunk in payload:
            f.write(chunk)
    return path


def _save_hetero(
    path: Path,
    hspec: HeterogeneousSpec,
    ensemble: hetero.HeteroEnsemble,
    *,
    committee_size: int | None,
    extra: dict | None,
    quantize: str | None = None,
    calibrate: Any = None,
) -> Path:
    if committee_size is not None and committee_size != hspec.n_collaborators:
        raise ValueError(
            f"heterogeneous committees span the whole federation: committee_size "
            f"must be {hspec.n_collaborators} (or None), got {committee_size}"
        )
    committee = committee_size is not None
    T = int(ensemble[0].alpha.shape[0])
    template = _hetero_template(hspec, T, committee)
    got, want = ensemble_signature(ensemble), ensemble_signature(template)
    if got != want:
        raise ValueError(
            f"ensemble does not match the heterogeneous template for groups "
            f"{hspec.names}: {got} != {want}"
        )
    counts = [int(e.count) for e in ensemble]
    if committee:
        if len(set(counts)) != 1:
            raise ValueError(f"committee group counts must move in lockstep: {counts}")
        # every used member is one mixed committee: one seat per collaborator
        seat_names = [hspec.specs[g].name for g in hspec.assignment]
        member_learners: list = [seat_names] * counts[0]
    else:
        member_learners = [
            hspec.specs[g].name for g in range(hspec.n_groups) for _ in range(counts[g])
        ]
    payload, plans = _maybe_quantize(
        hspec, ensemble, quantize, calibrate, committee_size
    )
    manifest = {
        "format_version": HETERO_VERSION if plans is None else QUANTIZED_VERSION,
        "learner": HETERO_LEARNER,
        "n_features": hspec.n_features,
        "n_classes": hspec.n_classes,
        "hparams": {},  # per-group hparams live in "groups"
        "groups": [
            {
                "learner": s.name,
                "hparams": dict(s.hparams),
                "members": list(hspec.members(g)),
                "count": counts[g],
            }
            for g, s in enumerate(hspec.specs)
        ],
        "assignment": list(hspec.assignment),
        "member_learners": member_learners,
        "ensemble_capacity": T,
        "ensemble_count": hetero.hetero_count(ensemble, committee=committee),
        "committee_size": committee_size,
        **_payload_fields(payload),
    }
    if plans is not None:
        manifest["quantize"] = quantize
        manifest["leaf_codecs"] = plans
    return _write(path, manifest, payload, extra)


_MANIFEST_KEYS = (
    "format_version", "learner", "n_features", "n_classes", "hparams",
    "ensemble_capacity", "ensemble_count", "committee_size",
    "payload_bytes", "payload_crc32",
)


def _decode_payload(payload: bytes, template: Any, manifest: dict, path) -> Any:
    """Pour a payload back into the template pytree — per-leaf codec
    decode for quantized (v3) artifacts, packed deserialize otherwise."""
    plans = manifest.get("leaf_codecs")
    if plans is None:
        return deserialize([payload], wire_format(template), packed=True)
    leaves, treedef = jax.tree.flatten(template)
    if len(plans) != len(leaves):
        raise ValueError(
            f"{path}: manifest lists {len(plans)} leaf codecs "
            f"for {len(leaves)} payload leaves"
        )
    out, off = [], 0
    for leaf, plan in zip(leaves, plans):
        shape, dtype = tuple(leaf.shape), np.dtype(str(leaf.dtype))
        try:
            n = encoded_nbytes(plan, shape, dtype)
            out.append(decode_leaf(payload[off : off + n], plan, shape, dtype))
        except ValueError as e:
            raise ValueError(f"{path}: {e}") from e
        off += n
    if off != len(payload):
        raise ValueError(
            f"{path}: quantized payload length mismatch ({len(payload)} != {off})"
        )
    return jax.tree.unflatten(treedef, out)


def load_artifact(path: str | Path) -> LoadedArtifact:
    data = Path(path).read_bytes()
    header = len(MAGIC) + 4  # magic + u32 manifest length
    # validate lengths BEFORE unpacking: a file truncated inside the
    # header must raise the documented ValueError, not a raw struct.error
    if len(data) < header:
        raise ValueError(
            f"{path}: truncated header ({len(data)} < {header} bytes)"
        )
    if data[: len(MAGIC)] != MAGIC:
        raise ValueError(f"{path}: not a MAFL serving artifact (bad magic)")
    (mlen,) = struct.unpack("<I", data[len(MAGIC) : header])
    if len(data) < header + mlen:
        raise ValueError(
            f"{path}: truncated manifest ({len(data) - header} < {mlen} bytes)"
        )
    try:
        manifest = json.loads(data[header : header + mlen].decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise ValueError(f"{path}: corrupt manifest: {e}") from e
    if not isinstance(manifest, dict):
        raise ValueError(f"{path}: manifest is not a JSON object")
    missing = [k for k in _MANIFEST_KEYS if k not in manifest]
    if missing:
        raise ValueError(f"{path}: manifest missing required keys {missing}")
    payload = data[header + mlen :]
    if manifest["format_version"] > MANIFEST_VERSION:
        raise ValueError(
            f"{path}: artifact format v{manifest['format_version']} is newer "
            f"than this reader (v{MANIFEST_VERSION})"
        )
    if len(payload) != manifest["payload_bytes"]:
        raise ValueError(
            f"{path}: truncated payload ({len(payload)} != {manifest['payload_bytes']} bytes)"
        )
    if zlib.crc32(payload) != manifest["payload_crc32"]:
        raise ValueError(f"{path}: payload checksum mismatch")
    if manifest["learner"] == HETERO_LEARNER:
        return _load_hetero(path, manifest, payload)
    spec = LearnerSpec(
        manifest["learner"],
        manifest["n_features"],
        manifest["n_classes"],
        dict(manifest["hparams"]),
    )
    template = _ensemble_template(
        spec, manifest["ensemble_capacity"], manifest["committee_size"],
        context=str(path),
    )
    ensemble = _decode_payload(payload, template, manifest, path)
    ensemble = jax.tree.map(jax.numpy.asarray, ensemble)
    return LoadedArtifact(
        learner=get_learner(spec.name),
        spec=spec,
        ensemble=ensemble,
        committee_size=manifest["committee_size"],
        manifest=manifest,
    )


def _load_hetero(path, manifest: dict, payload: bytes) -> LoadedArtifact:
    for k in ("groups", "assignment"):
        if k not in manifest:
            raise ValueError(f"{path}: heterogeneous manifest missing {k!r}")
    specs = tuple(
        LearnerSpec(
            g["learner"], manifest["n_features"], manifest["n_classes"],
            dict(g["hparams"]),
        )
        for g in manifest["groups"]
    )
    try:
        hspec = HeterogeneousSpec(specs=specs, assignment=tuple(manifest["assignment"]))
    except ValueError as e:
        raise ValueError(f"{path}: invalid heterogeneous manifest: {e}") from e
    committee = manifest["committee_size"] is not None
    template = _hetero_template(
        hspec, manifest["ensemble_capacity"], committee, context=str(path)
    )
    ensemble = _decode_payload(payload, template, manifest, path)
    ensemble = jax.tree.map(jax.numpy.asarray, ensemble)
    return LoadedArtifact(
        learner=None,
        spec=hspec,
        ensemble=ensemble,
        committee_size=manifest["committee_size"],
        manifest=manifest,
    )


# ---------------------------------------------------------------------------
# Rolling checkpoint stream — the federation→serving handoff
# ---------------------------------------------------------------------------

LATEST = "LATEST"


def publish_artifact(
    publish_dir: str | Path,
    spec: LearnerSpec | HeterogeneousSpec,
    ensemble: Any,
    *,
    version: int,
    committee_size: int | None = None,
    extra: dict | None = None,
    quantize: str | None = None,
    calibrate: Any = None,
) -> Path:
    """One checkpoint of a still-training federation: write a fresh
    versioned artifact, then atomically repoint ``LATEST`` at it.

    The version lands in the manifest (``publish_version``) and the file
    name, so consumers can both poll :func:`latest_artifact` and replay
    the full checkpoint history in order.  The pointer swap is an
    ``os.replace`` — a concurrent reader sees the old complete artifact
    or the new complete artifact, never a partial write."""
    publish_dir = Path(publish_dir)
    path = publish_dir / f"ensemble_v{version:06d}.mafl"
    save_artifact(
        path, spec, ensemble, committee_size=committee_size,
        extra={"publish_version": int(version), **(extra or {})},
        quantize=quantize, calibrate=calibrate,
    )
    tmp = publish_dir / (LATEST + ".tmp")
    tmp.write_text(path.name)
    tmp.replace(publish_dir / LATEST)
    return path


def _resolve_latest(pointer: Path) -> Path | None:
    if not pointer.exists():
        return None
    name = pointer.read_text().strip()
    return (pointer.parent / name) if name else None


def latest_artifact(publish_dir: str | Path) -> Path | None:
    """Resolve the ``LATEST`` pointer; None when nothing is published.

    Hardened against torn reads: ``publish_artifact`` writes the version
    file before swapping the pointer, but a consumer on another
    filesystem view (or racing a publisher that died mid-publish) can
    observe a pointer naming a not-yet-visible file.  One short
    re-resolve absorbs the benign interleaving; a pointer that STILL
    names a missing file is corruption and raises ``ValueError`` rather
    than masquerading as "nothing published"."""
    pointer = Path(publish_dir) / LATEST
    path = _resolve_latest(pointer)
    if path is not None and not path.exists():  # torn read: retry once
        time.sleep(0.05)
        path = _resolve_latest(pointer)
        if path is not None and not path.exists():
            raise ValueError(
                f"{pointer}: names artifact {pointer.read_text().strip()!r} "
                f"which does not exist (torn or corrupt publish)"
            )
    return path
