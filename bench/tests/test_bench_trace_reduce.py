"""The trace reduction: busy-interval union, idle share, device time per
round, collective time, and the top ops and gaps of ``breakdown`` — on a
hand-built trace whose numbers are worked out below, and on a slice of a
trace recorded on one TPU v5e (``data/trace_sample_tpu_v5e.json``: 60 ms
of an ``adult_c8.train`` window, kept by ``bench/record_trace.py``)."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.metrics import collective_ms_per_round, round_device_ms, train_idle_share

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    # window [1000, 11000) ns; device 0: ops [2000, 4000) and [3000, 5000)
    # overlap -> busy [2000, 5000), plus all-reduce [8000, 9000): busy 4000 ns.
    # device 1: [1000, 2000) inside, [10500, 12000) clipped to [10500, 11000):
    # busy 1500 ns; device 2 runs nothing inside and is left out. Host: a
    # checkpoint hook over [5000, 8000) holds the first gap of device 0
    # ([5000, 8000), 3000 ns).
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 1000.0, 10000.0],
            ["bench.checkpoint_hook", 5000.0, 3000.0],
        ]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_round", 2000.0, 7000.0]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 2000.0, 2000.0], ["scatter.2", 3000.0, 2000.0],
                ["all-reduce.3", 8000.0, 1000.0],
            ]},
        ]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 1000.0, 1000.0], ["fusion.1", 10500.0, 1500.0],
        ]}]},
        # a chip the cell holds but runs nothing on inside the window
        {"name": "/device:TPU:2", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 12000.0, 500.0],
        ]}]},
    ]}


def test_merge_and_gaps():
    busy = tr.merge([(2, 4), (3, 5), (8, 9), (0, 1)], 1, 11)
    assert busy == [(2, 5), (8, 9)]
    assert tr.gaps(busy, 1, 11) == [(1, 2), (5, 8), (9, 11)]


def test_summary_of_the_hand_built_trace():
    s = tr.summarize(_trace())
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(10000e-9)
    assert s.busy_s == pytest.approx((4000 + 1500) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(1 - 2750 / 10000)
    assert s.collective_s == pytest.approx(1000 / 2 * 1e-9)
    ops = dict(s.top_ops)
    assert ops["fusion.1"] == pytest.approx((2000 + 1000 + 500) / 2 * 1e-9)
    assert ops["scatter.2"] == pytest.approx(1000e-9)
    assert s.top_ops[0][0] == "fusion.1"
    # device 0's gaps: [1000, 2000) program, [5000, 8000) hook, [9000, 11000) program
    assert s.top_gaps[0] == ["bench.checkpoint_hook", pytest.approx(3000e-9)]
    assert s.top_gaps[1] == ["program", pytest.approx(2000e-9)]


def test_readers_on_the_hand_built_trace():
    obs = tr.Observations(window_s=1.0, rounds=5, chips=2, compiles_in_window=0,
                          trace=tr.summarize(_trace()), counters={}, config={}, traffic={},
                          device_kind="TPU v5 lite", notes=[])
    assert train_idle_share.read(obs) == pytest.approx(0.725)
    assert round_device_ms.read(obs) == pytest.approx(2750e-9 * 1e3 / 5)
    assert collective_ms_per_round.read(obs) == pytest.approx(500e-9 * 1e3 / 5)
    obs.trace = None
    assert train_idle_share.read(obs) is None
    assert round_device_ms.read(obs) is None


def test_no_device_plane_reads_nothing():
    trace = _trace()
    trace["planes"] = trace["planes"][:1]
    assert tr.summarize(trace) is None


def test_recorded_tpu_trace():
    trace = json.loads((DATA / "trace_sample_tpu_v5e.json").read_text())
    s = tr.summarize(trace)
    assert s is not None and s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert 0.0 < s.idle_share < 1.0
    assert len(s.top_ops) == tr.TOP and s.top_ops[0][1] >= s.top_ops[-1][1]
    assert s.top_gaps and all(g[1] > 0 for g in s.top_gaps)
    assert sum(t for _, t in s.top_ops) <= s.busy_s * (1 + 1e-9) + sum(
        t for _, t in s.top_ops[1:])
