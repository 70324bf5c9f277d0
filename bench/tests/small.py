"""Small cells for the CPU tests: the committed configurations and mixes,
cut to a few thousand rows, driven in-process past the chip check."""
from __future__ import annotations

import copy

from bench import run as bench_run


# The serving cell's driver, mix, limits and readers wait for an entry in
# BENCHMARK.json until its rate is set from a knee swept on its own host;
# its tests run it from here meanwhile.
PENDING = {
    "adult_c8.serve": (
        {"name": "adult_c8.serve", "config": "adult_c8", "traffic": "serve_poisson", "chips": 1},
        [{"name": "serve_p99_ms", "unit": "ms"}, {"name": "serve_p50_ms", "unit": "ms"},
         {"name": "serve_rows_per_s", "unit": "rows/s"}],
    ),
}


def cell(name: str, *, n_train: int = 4000, n_test: int = 2000, **traffic_over):
    """(benchmark, cell, config, traffic) of ``name`` at a small size."""
    benchmark = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    if name in PENDING:
        entry, metrics = PENDING[name]
        benchmark["workloads"].append(entry)
        benchmark["end_to_end"] += [dict(m, workloads=[name]) for m in metrics]
    c = bench_run.find_cell(name, benchmark)
    config = copy.deepcopy(bench_run.load_json(bench_run.BENCH / "configs" / f"{c['config']}.json"))
    traffic = dict(bench_run.load_json(bench_run.BENCH / "traffic" / f"{c['traffic']}.json"))
    config["data"].update(n_train=n_train, n_test=n_test)
    traffic.update(traffic_over)
    return benchmark, c, config, traffic


def run(name: str, *, seconds: float = 1.0, seed: int = 2**31 + 7, trace: bool = False,
        n_train: int = 4000, n_test: int = 2000, **traffic_over) -> dict:
    import jax

    benchmark, c, config, traffic = cell(name, n_train=n_train, n_test=n_test, **traffic_over)
    return bench_run.run_cell(benchmark, c, seed=seed, seconds=seconds, trace=trace,
                              devices=jax.devices(), config=config, traffic=traffic)
