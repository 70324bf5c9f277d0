"""Run one benchmark cell on the accelerator this process finds.

  python3 bench/run.py --workload adult_c8.train --seed 7 --seconds 10 --trace 0

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<mix>.json``); the mix names the driver
(``bench/drivers/<driver>.py``) that sets the program up, warms it and
drives its window, and whose outputs ``check`` compares with the plain
reference under the cell's limits (``bench/limits/<cell>.json``). With ``--trace 0`` the
result line carries the cell's end-to-end metrics; with ``--trace 1``
the window runs under the JAX profiler and the line carries its
per-layer metrics, each computed by ``bench/metrics/<metric>.py``.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. The last stdout line is the result JSON;
the numbers compared for ``correct`` end stderr, each beside its limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, trace_reduce  # noqa: E402

BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, benchmark: dict) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"unknown workload {name!r}; have {[c['name'] for c in benchmark['workloads']]}")


def cell_metrics(cell: str, entries: List[dict]) -> List[dict]:
    """The metrics of ``entries`` that cell ``cell`` reports."""
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def require_chips(n: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"needs {n} chips; JAX found {len(devices)}")
    return devices


def use_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program
    so that only a checkout's first run of a cell compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs compiled or fetched from the persistent cache, from
    ``jax.monitoring`` events."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.count += 1


class Run:
    """What a driver gets: the cell's files, its seed and window, and the
    hooks that open and close the measured window."""

    def __init__(self, cell: dict, config: dict, traffic: dict, *, seed: int,
                 seconds: float, trace: bool, compiles: Optional[CompileCounter]):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.compiles = compiles
        self.window: Dict[str, float] = {}
        self.notes: List[str] = []
        self._profile_dir: Optional[str] = None
        self._window_annotation = None
        self.trace_summary: Optional[trace_reduce.Summary] = None
        self.trace_data: Optional[dict] = None  # the window's trace as plain data

    # -- seeds and data -------------------------------------------------------
    def key(self, purpose: str):
        """A key per purpose, all from ``--seed``."""
        import zlib

        import jax

        from bench import datagen

        return jax.random.fold_in(datagen.seed_key(self.seed), zlib.crc32(purpose.encode()))

    def data(self):
        """(X_train, y_train, X_test, y_test) of the configuration, on the device."""
        from bench import datagen

        return datagen.make_classification(self.config["data"], self.key("data"))

    # -- host spans on the profiler's clock -----------------------------------
    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    # -- the window -----------------------------------------------------------
    def open_window(self, now: Optional[float] = None) -> float:
        import jax

        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self._profile_dir = tempfile.mkdtemp(prefix="bench_profile_")
            jax.profiler.start_trace(self._profile_dir, profiler_options=opts)
            self._window_annotation = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._window_annotation.__enter__()
        t = time.perf_counter() if now is None or self.trace else now
        self.window["start"] = t
        self.window["compiles_at_start"] = self.compiles.count if self.compiles else 0
        return t

    def close_window(self, now: Optional[float] = None) -> float:
        import jax

        t = time.perf_counter() if now is None else now
        self.window["end"] = t
        self.window["compiles"] = (
            (self.compiles.count if self.compiles else 0) - self.window["compiles_at_start"]
        )
        if self.trace and self._profile_dir is not None:
            self._window_annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            import shutil

            try:
                self.trace_data = trace_reduce.load_profile(self._profile_dir)
                self.trace_summary = trace_reduce.summarize(self.trace_data)
                if self.trace_summary is None:
                    self.notes.append("trace: no TPU device plane; no device metric read")
            finally:
                shutil.rmtree(self._profile_dir, ignore_errors=True)
                self._profile_dir = None
        return t

    @property
    def window_s(self) -> float:
        return self.window["end"] - self.window["start"]


def _load_module(kind: str, name: str):
    return importlib.import_module(f"bench.{kind}.{name}")


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(benchmark: dict, cell: dict, **kw) -> dict:
    """Set up, warm, measure and check one cell; returns the result line
    (the keywords are :func:`measure`'s)."""
    return measure(benchmark, cell, **kw)[0]


def measure(benchmark: dict, cell: dict, *, seed: int, seconds: float, trace: bool,
            devices: list, config: Optional[dict] = None,
            traffic: Optional[dict] = None) -> Tuple[dict, Run]:
    """(the result line, the cell's :class:`Run` with its trace data).
    ``config`` and ``traffic`` default to the cell's files."""
    config = config or load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = traffic or load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    driver = _load_module("drivers", traffic["driver"])
    limits = load_json(BENCH / "limits" / f"{cell['name']}.json")
    run = Run(cell, config, traffic, seed=seed, seconds=seconds, trace=trace,
              compiles=CompileCounter())

    out = driver.run(run)
    setup_s = out["setup_end"] - T_PROCESS
    used = devices[: cell["chips"]]
    peak = _peak_bytes(used)
    end_to_end = dict(out["end_to_end"], setup_s=setup_s)
    obs = trace_reduce.Observations(
        window_s=run.window_s, rounds=out.get("rounds"), chips=cell["chips"],
        compiles_in_window=run.window["compiles"], trace=run.trace_summary,
        counters=out.get("counters", {}), config=config, traffic=traffic,
        device_kind=used[0].device_kind, notes=run.notes,
    )
    attempted, failed, outputs = out["attempted"], out["failed"], out["outputs"]
    del out
    # the numbers compared are those the cell's limits name
    checks = compare.finite(driver.check(run, outputs))
    correct = all(checks.get(k) is not None and checks[k] <= limits[k] for k in limits)

    if trace:
        metrics = {}
        for m in cell_metrics(cell["name"], benchmark["per_layer"]):
            value = _load_module("metrics", m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {
            m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(cell["name"], benchmark["end_to_end"])
        }
    d = used[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
              "metrics": metrics, "device": device}
    if trace and run.trace_summary is not None:
        s = run.trace_summary
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        result["breakdown"] = {"device_ops": s.top_ops, "idle_gaps": s.top_gaps}
    for note in run.notes:
        print(note, file=sys.stderr)
    for k in sorted(limits):
        print(f"check {k} = {checks.get(k)!r} (limit {limits[k]!r})", file=sys.stderr)
    result["checks"] = {k: {"value": checks.get(k), "limit": limits[k]} for k in sorted(limits)}
    return result, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    benchmark = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(args.workload, benchmark)
    try:
        devices = require_chips(cell["chips"])
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    use_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    result = run_cell(benchmark, cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
