"""The harness: cells, mixes and per-layer metrics are found by name from
new files alone; the open-loop schedule follows the seed; the command
refuses the CPU; ``BENCHMARK.json`` keeps to its own rules."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np

from bench import run as bench_run
from bench.drivers import serve

ROOT = bench_run.ROOT


def test_schedule_follows_the_seed():
    a_due, a_rows = serve.schedule(2000.0, 2.0, 16281, 5)
    b_due, b_rows = serve.schedule(2000.0, 2.0, 16281, 5)
    c_due, c_rows = serve.schedule(2000.0, 2.0, 16281, 6)
    assert np.array_equal(a_due, b_due) and np.array_equal(a_rows, b_rows)
    assert not np.array_equal(a_due, c_due) and not np.array_equal(a_rows, c_rows)
    # every seed offers the same gaps in another order: the same load
    assert len(a_due) == len(c_due) == 4000
    assert np.sort(np.diff(a_due)).tolist() != np.diff(a_due).tolist()
    assert abs(a_due[-1] - c_due[-1]) < 2 / 2000
    assert abs(a_due[-1] - 2.0) < 0.05
    assert np.all(np.diff(a_due) > 0)
    # seeds past 32 bits work too
    big_due, _ = serve.schedule(2000.0, 2.0, 16281, 2**33 + 1)
    assert len(big_due) == 4000


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "adult_c8.train", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_cell_mix_and_metric_found_from_new_files(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix and a
    per-layer metric as new files, and a cell naming them: the command
    (with its chip check stubbed) runs the cell and reports the metric."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench/configs/adult_c8.json").read_text())
    config["data"].update(n_train=3000, n_test=1000)
    config["federation"]["collaborators"] = 3
    (tmp_path / "bench/configs/tiny_c3.json").write_text(json.dumps(config))
    (tmp_path / "bench/traffic/fused_eval2_pub4.json").write_text(json.dumps(
        {"driver": "fused", "eval_every": 2, "publish_every": 4, "reference_rounds": 2}))
    shutil.copy(ROOT / "bench/limits/adult_c8.train.json", tmp_path / "bench/limits/tiny_c3.train.json")
    (tmp_path / "bench/metrics/rounds_in_window.py").write_text(
        "def read(obs):\n    return float(obs.rounds)\n")
    benchmark["workloads"].append({"name": "tiny_c3.train", "config": "tiny_c3",
                                   "traffic": "fused_eval2_pub4", "chips": 1, "why": "test"})
    benchmark["per_layer"].append({"name": "rounds_in_window", "unit": "count",
                                   "better": "higher", "source": "program_counter",
                                   "layer": "federation loop", "moves": "round_s",
                                   "workloads": ["tiny_c3.train"]})
    benchmark["end_to_end"][0]["workloads"].append("tiny_c3.train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{str(tmp_path)!r}, {str(ROOT / 'src')!r}]
        import jax
        from bench import run
        assert run.ROOT.samefile({str(tmp_path)!r})
        run.require_chips = lambda n: jax.devices()
        run.use_compile_cache = lambda: None
        sys.exit(run.main(sys.argv[1:]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    lines = {}
    for trace in ("0", "1"):
        proc = subprocess.run(
            [sys.executable, "-c", code, "--workload", "tiny_c3.train", "--seed", "4",
             "--seconds", "1", "--trace", trace],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines[trace] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert lines["0"]["correct"], lines["0"]["checks"]
    assert set(lines["0"]["metrics"]) == {"round_s", "setup_s"}
    rounds = lines["1"]["metrics"]["rounds_in_window"]
    assert rounds["unit"] == "count" and rounds["value"] >= 4
    assert set(lines["1"]["metrics"]) == {"rounds_in_window"}


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


def test_benchmark_json_keeps_to_its_rules():
    import re

    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]]
    names += [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names)) and all(re.match(NAME, n) for n in names)
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in b["workloads"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for w in b["workloads"]:
        assert (ROOT / f"bench/traffic/{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
        reported = [m for m in b["end_to_end"] if "workloads" not in m or w["name"] in m["workloads"]]
        assert len(reported) >= 2
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(b["workloads"]) // 2)
    for m in b["per_layer"]:
        assert (ROOT / f"bench/metrics/{m['name']}.py").is_file()
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
