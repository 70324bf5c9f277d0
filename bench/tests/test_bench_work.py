"""The work model and the peaks table."""
import json

import pytest

from bench import run as bench_run
from bench.metrics import round_roofline
from bench.trace_reduce import Observations, Summary
from bench.work import adaboost_f_decision_tree as work


def _adult():
    return bench_run.load_json(bench_run.BENCH / "configs" / "adult_c8.json")


def test_adult_round_work_by_hand():
    # n = 32561 // 8 = 4070 rows a collaborator, 32560 on the chip; d = 14,
    # K = 2, D = 4, B = 16: a bin index, a leaf index (up to 16 leaves) and a
    # label fit a byte each, a weight is 4 bytes.
    # fit: level 0 reads 32560 * (14 + 1 + 4) = 618,640 bytes, levels 1-3
    #   32560 * (14 + 1 + 1 + 4) = 651,200 each; the leaves 32560 * 6 = 195,360
    #   -> 2,767,600 bytes; 4 * 32560 * 14 + 32560 = 1,855,920 ops.
    # score: 32560 * 14 + 32560 * 5 = 618,640 bytes; 8 * 32560 * (4 + 1)
    #   = 1,302,400 ops.  update: 32560 * 10 = 325,600 bytes, 65,120 ops.
    w = work.round_work(_adult(), chips=1)
    assert w == {"bytes": 3_711_840, "ops": 3_223_440}


def test_work_is_per_chip():
    cfg = bench_run.load_json(bench_run.BENCH / "configs" / "covtype_c4.json")
    one, four = work.round_work(cfg, 1), work.round_work(cfg, 4)
    assert four["bytes"] * 4 == one["bytes"] and four["ops"] * 4 == one["ops"]


def test_nbytes():
    assert [work.nbytes(v) for v in (1, 2, 17, 256, 257, 65536, 65537)] == [1, 1, 1, 1, 2, 2, 3]


def _obs(kind):
    s = Summary(window_s=1.0, busy_s=0.5, n_devices=1, collective_s=0.0, top_ops=[],
                top_gaps=[])
    return Observations(window_s=1.0, rounds=100, chips=1, compiles_in_window=0, trace=s,
                        counters={}, config=_adult(), traffic={}, device_kind=kind, notes=[])


def test_roofline_share_from_the_peaks_table():
    obs = _obs("TPU v5 lite")
    peaks = json.loads(round_roofline.PEAKS.read_text())["devices"]["TPU v5 lite"]
    assert peaks["ops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    # bytes bound it: 3,711,840 / 819e9 s against 5 ms busy a round
    assert round_roofline.read(obs) == pytest.approx(100 * 3_711_840 / 819e9 / 5e-3)
    assert "bound by bytes" in obs.notes[0]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        round_roofline.read(_obs("TPU v99"))
