"""The round program's share of its roofline, in percent: the least time
one chip needs for a round's work (``bench/work/<algorithm>_<learner>.py``:
bytes read at their narrowest exact width, and operations, per chip)
at the chip's peaks (``bench/peaks.json``), over the device busy time per
round. A device kind missing from the peaks table is an error."""
import importlib
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


def read(obs):
    if obs.trace is None or not obs.rounds:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)["devices"]
    if obs.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {obs.device_kind!r} in {PEAKS}")
    peak = peaks[obs.device_kind]
    fed = obs.config["federation"]
    work = importlib.import_module(f"bench.work.{fed['algorithm']}_{fed['learner']}")
    w = work.round_work(obs.config, obs.chips)
    t_bytes = w["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = w["ops"] / peak["ops_per_s"]
    busy_s = obs.trace.busy_s / obs.rounds
    obs.notes.append(
        f"round_roofline: {w['bytes']:.6g} bytes and {w['ops']:.6g} ops per chip per round; "
        f"bound by {'bytes' if t_bytes >= t_ops else 'operations'} "
        f"({max(t_bytes, t_ops):.6g} s against {busy_s:.6g} s busy)"
    )
    return 100.0 * max(t_bytes, t_ops) / busy_s
