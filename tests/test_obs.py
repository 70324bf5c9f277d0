"""Observability layer: span tracer, metrics registry, and their
integration with the federation's fused round."""
import json
import threading
import tracemalloc

import jax
import numpy as np
import pytest

from repro.obs import metrics as obs_metrics, trace
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import NOOP_SPAN, Tracer


# -- tracer ------------------------------------------------------------------

def test_span_nesting_records_parent_chain():
    tr = Tracer()
    tr.enable()
    with tr.span("round", round=0):
        with tr.span("round.fit"):
            pass
        with tr.span("round.score"):
            pass
    evs = {e["name"]: e for e in tr.events()}
    assert set(evs) == {"round", "round.fit", "round.score"}
    rid = evs["round"]["args"]["span_id"]
    assert evs["round"]["args"]["parent_id"] is None
    assert evs["round.fit"]["args"]["parent_id"] == rid
    assert evs["round.score"]["args"]["parent_id"] == rid
    # children close before the parent, so the parent's interval covers them
    for kid in ("round.fit", "round.score"):
        assert evs[kid]["ts"] >= evs["round"]["ts"]
        assert evs[kid]["ts"] + evs[kid]["dur"] <= (
            evs["round"]["ts"] + evs["round"]["dur"] + 1e-3
        )


def test_span_set_attaches_attributes():
    tr = Tracer()
    tr.enable()
    with tr.span("registry.refresh", tenant="a") as sp:
        sp.set(outcome="swap")
    (e,) = tr.events()
    assert e["args"]["tenant"] == "a"
    assert e["args"]["outcome"] == "swap"


def test_spans_are_thread_safe_with_per_thread_stacks():
    tr = Tracer()
    tr.enable()

    def worker(i):
        with tr.span("outer", thread=i):
            with tr.span("inner", thread=i):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == 16
    inner = [e for e in evs if e["name"] == "inner"]
    outer = {e["args"]["thread"]: e for e in evs if e["name"] == "outer"}
    ids = [e["args"]["span_id"] for e in evs]
    assert len(set(ids)) == len(ids)  # globally unique ids under contention
    for e in inner:
        # each inner span's parent is ITS thread's outer span, never a
        # sibling thread's (per-thread stacks)
        assert e["args"]["parent_id"] == outer[e["args"]["thread"]]["args"]["span_id"]
        assert e["tid"] == outer[e["args"]["thread"]]["tid"]


def test_chrome_trace_round_trips_through_json(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("round", round=3):
        with tr.span("round.fit"):
            pass
    path = tmp_path / "trace.json"
    tr.export(path)
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 2
    for e in doc["traceEvents"]:
        # the complete-event shape Perfetto/chrome://tracing require
        assert e["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["dur"] >= 0


def test_disabled_span_is_shared_noop_singleton():
    tr = Tracer()
    assert tr.span("x") is NOOP_SPAN
    assert tr.span("y", a=1) is tr.span("z")  # one shared object, always
    assert trace.TRACER.enabled is False  # process default starts disabled
    assert trace.span("anything", k="v") is NOOP_SPAN
    with trace.span("still.noop") as sp:
        sp.set(ignored=True)
    assert trace.events() == []  # nothing recorded


def test_disabled_span_retains_no_memory():
    # the disabled fast path must be allocation-free net of the call
    # itself: nothing may accumulate across a hot loop
    for _ in range(64):  # warm caches outside the measurement
        with trace.span("hot", i=0):
            pass
    tracemalloc.start()
    for i in range(2000):
        with trace.span("hot", i=i):
            pass
    current, _peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert current < 4096, f"disabled tracing retained {current} bytes"


def test_summary_aggregates_per_name():
    tr = Tracer()
    tr.enable()
    for _ in range(3):
        with tr.span("round"):
            pass
    s = tr.summary()
    assert s["round"]["count"] == 3
    assert s["round"]["total_s"] >= 0
    table = tr.format_summary("test table")
    assert "round" in table and "test table" in table


# -- histogram ---------------------------------------------------------------

def test_histogram_quantiles_match_exact_within_error_bound():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-6.0, sigma=1.0, size=5000)  # latency-shaped
    h = Histogram()
    for x in xs:
        h.observe(x)
    for p in (10, 50, 90, 99):
        exact = float(np.percentile(xs, p))
        got = h.percentile(p)
        # bucket growth 1.1 -> relative error <= sqrt(1.1)-1 ~ 4.9%,
        # plus rank discretisation: 6% covers it
        assert abs(got - exact) / exact < 0.06, (p, got, exact)
    assert h.quantile(0.0) == float(xs.min())  # extremes are exact
    assert h.quantile(1.0) == float(xs.max())
    assert h.count == len(xs)
    assert abs(h.sum - xs.sum()) < 1e-6 * xs.sum()


def test_histogram_is_deque_compatible():
    h = Histogram()
    assert len(h) == 0
    assert np.isnan(h.percentile(50))
    h.append(0.25)  # old call sites append() into the latency window
    h.append(0.5)
    assert len(h) == 2
    assert h.min == 0.25 and h.max == 0.5


def test_histogram_merge_combines_distributions():
    a, b = Histogram(), Histogram()
    xs = np.linspace(1e-3, 1e-2, 500)
    ys = np.linspace(1e-1, 1.0, 1500)
    for x in xs:
        a.observe(x)
    for y in ys:
        b.observe(y)
    a.merge(b)
    assert a.count == 2000
    both = np.concatenate([xs, ys])
    p50 = a.percentile(50)
    assert abs(p50 - np.percentile(both, 50)) / np.percentile(both, 50) < 0.06
    with pytest.raises(ValueError):
        a.merge(Histogram(growth=1.5))  # shape mismatch must be loud


def test_histogram_memory_is_bounded():
    h = Histogram()
    n_buckets = len(h._counts)
    for x in np.random.default_rng(1).exponential(0.01, size=20_000):
        h.observe(x)
    assert len(h._counts) == n_buckets  # fixed storage, any sample count
    assert n_buckets < 250


def test_histogram_reads_never_tear_under_concurrent_writes():
    """Regression: the read properties (count/sum/min/max/mean, len) take
    the lock.  Every sample is exactly 1.0, so an unlocked reader pairing
    a fresh _sum with a stale _count would compute mean != 1.0."""
    h = Histogram()
    h.observe(1.0)  # non-empty before readers start
    n_per_writer, n_writers = 2000, 4
    stop = threading.Event()
    torn = []

    def write():
        for _ in range(n_per_writer):
            h.observe(1.0)

    def read():
        while not stop.is_set():
            if h.count and h.mean != 1.0:
                torn.append((h.count, h.sum))
            if not (h.min == h.max == 1.0):
                torn.append(("minmax", h.min, h.max))

    readers = [threading.Thread(target=read) for _ in range(2)]
    writers = [threading.Thread(target=write) for _ in range(n_writers)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not torn, torn[:3]
    assert h.count == 1 + n_per_writer * n_writers  # no lost updates either


# -- metrics registry --------------------------------------------------------

def test_registry_reregistration_returns_same_metric():
    reg = MetricsRegistry()
    c1 = reg.counter("mafl_test_total", "help one")
    c2 = reg.counter("mafl_test_total", "redeclared elsewhere")
    assert c1 is c2  # modules declare at import time without coordination
    with pytest.raises(ValueError):
        reg.gauge("mafl_test_total")  # kind mismatch must be loud
    with pytest.raises(ValueError):
        reg.counter("mafl_test_total", labels=("trigger",))  # labels too


def test_labeled_family_children():
    reg = MetricsRegistry()
    fam = reg.counter("mafl_dispatches_total", "by trigger", labels=("trigger",))
    fam.labels(trigger="full").inc()
    fam.labels(trigger="deadline").inc(2)
    assert fam.labels(trigger="full").value == 1
    assert fam.labels(trigger="deadline").value == 2
    with pytest.raises(ValueError):
        fam.labels(wrong="x")


def test_prometheus_text_parses_and_buckets_are_cumulative():
    reg = MetricsRegistry()
    reg.counter("mafl_requests_total", "requests").inc(7)
    reg.gauge("mafl_queue_depth", "depth").set(3)
    h = reg.histogram("mafl_latency_seconds", "latency")
    for x in (0.001, 0.002, 0.002, 0.5):
        h.observe(x)
    reg.counter("mafl_by_kind_total", "labeled", labels=("kind",)).labels(
        kind="a"
    ).inc()
    text = reg.prometheus_text()

    seen_types, last_cum, inf_seen = {}, None, False
    for line in text.strip().splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            seen_types[name] = kind
            continue
        if line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        float(value)  # every sample line ends in a parseable number
        if name_part.startswith("mafl_latency_seconds_bucket"):
            cum = float(value)
            assert last_cum is None or cum >= last_cum  # cumulative
            last_cum = cum
            if 'le="+Inf"' in name_part:
                inf_seen = True
                assert cum == 4
    assert seen_types == {
        "mafl_requests_total": "counter",
        "mafl_queue_depth": "gauge",
        "mafl_latency_seconds": "histogram",
        "mafl_by_kind_total": "counter",
    }
    assert inf_seen
    assert 'mafl_by_kind_total{kind="a"} 1.0' in text
    assert "mafl_latency_seconds_sum" in text
    assert "mafl_latency_seconds_count 4" in text


def test_registry_dump_and_reset(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("mafl_things_total", "things")
    c.inc(5)
    p = tmp_path / "metrics.prom"
    reg.dump(p)
    assert "mafl_things_total 5.0" in p.read_text()
    reg.reset()
    assert c.value == 0  # zeroed, family still registered
    assert reg.counter("mafl_things_total") is c


# -- integration: fused round + federation history ---------------------------

@pytest.fixture(scope="module")
def tiny():
    from repro.data import get_dataset
    from repro.fl.partition import iid_partition
    from repro.learners import LearnerSpec

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    dspec, (Xtr, ytr, Xte, yte) = get_dataset("vehicle", k1)
    Xs, ys, masks = iid_partition(Xtr, ytr, 4, k2)
    lspec = LearnerSpec("decision_tree", dspec.n_features, dspec.n_classes,
                        {"depth": 3, "n_bins": 8})
    return Xs, ys, masks, Xte, yte, lspec, k3


def test_staged_round_equals_fused_round(tiny):
    """jitting each stage separately (the traced path) must produce the
    same state and metrics as the one fused jit of the composition."""
    from repro.core import boosting
    from repro.learners import get_learner

    Xs, ys, masks, _, _, lspec, key = tiny
    learner = get_learner(lspec.name)
    state = boosting.init_boost_state(learner, lspec, 3, masks, key, X=Xs)

    fused = jax.jit(
        lambda s: boosting.adaboost_f_round(learner, lspec, s, Xs, ys, masks)
    )
    staged = [
        (n, jax.jit(f)) for n, f in boosting.adaboost_f_stages(learner, lspec)
    ]

    s_f, s_s = state, state
    for _ in range(3):
        s_f, m_f = fused(s_f)
        carry = {}
        for _, sfn in staged:
            s_s, carry = sfn(s_s, carry, Xs, ys, masks)
        m_s = carry["metrics"]
        np.testing.assert_allclose(
            np.asarray(s_f.weights), np.asarray(s_s.weights), rtol=1e-6
        )
        for k in m_f:
            np.testing.assert_allclose(
                np.asarray(m_f[k]), np.asarray(m_s[k]), rtol=1e-6
            )


def test_traced_federation_emits_phase_spans_and_history_extras(tiny, tmp_path):
    """Tracing adds host spans and changes nothing else: a traced and an
    untraced run of one seed end in the same bits, because both call the
    one jitted round (fit / score / aggregate are named scopes inside
    it, not host phases)."""
    from repro.core.plan import adaboost_plan
    from repro.fl.federation import Federation

    Xs, ys, masks, Xte, yte, lspec, key = tiny

    def run(traced: bool, out: str):
        fed = Federation(adaboost_plan(rounds=4), Xs, ys, masks, Xte, yte, lspec, key)
        if traced:
            trace.enable()
            trace.reset()
        try:
            hist = fed.run(eval_every=2, publish_every=2, publish_dir=str(tmp_path / out))
        finally:
            trace.disable()
        return fed, hist

    fed_off, hist_off = run(False, "off")
    fed, hist = run(True, "on")
    # satellite: history rows carry wall-clock and comm deltas
    for h in hist:
        assert h["round_seconds"] > 0
        assert h["comm_bytes"] > 0
    assert fed.comm_bytes == sum(h["comm_bytes"] for h in hist)

    evs = trace.events()
    rounds = {e["args"]["span_id"] for e in evs if e["name"] == "round"}
    assert len(rounds) == 4
    kid_names = {
        e["name"] for e in evs if e["args"].get("parent_id") in rounds
    }
    # the fused path's host spans: eval and publish inside each round
    assert kid_names == {"round.eval", "round.publish"}
    assert {e["name"] for e in evs} == {"round", "round.eval", "round.publish"}
    trace.reset()

    # the traced path runs the same program: bit-identical state and history
    for a, b in zip(jax.tree.leaves(fed._fused_state), jax.tree.leaves(fed_off._fused_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(hist) == len(hist_off)
    for h, h_off in zip(hist, hist_off):
        assert h.keys() == h_off.keys()
        for k in h:
            if k != "round_seconds":  # host wall clock
                assert h[k] == h_off[k], (k, h[k], h_off[k])


def test_enabled_span_lands_on_the_profiler_host_plane(tmp_path):
    """An enabled span is also a ``jax.profiler.TraceAnnotation``: a JAX
    profiler capture shows it, under its name, on the host plane — the
    clock the device's operations are on."""
    import glob
    import os

    from jax.profiler import ProfileData

    tr = Tracer()
    tr.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("round.publish", round=7):
            jax.block_until_ready(jax.numpy.ones(8) * 2)
        with Tracer().span("round.never"):  # disabled: a no-op, not an annotation
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    host = {
        ev.name: ev
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }
    assert "round.publish" in host
    assert "round.never" not in host
    (e,) = tr.events()  # still in the span buffer for the Chrome-trace export
    assert e["name"] == "round.publish" and e["args"]["round"] == 7
    assert host["round.publish"].duration_ns > 0


def test_untraced_federation_records_nothing(tiny):
    from repro.core.plan import adaboost_plan
    from repro.fl.federation import Federation

    Xs, ys, masks, Xte, yte, lspec, key = tiny
    assert not trace.TRACER.enabled
    n0 = len(trace.events())
    fed = Federation(
        adaboost_plan(rounds=2), Xs, ys, masks, Xte, yte, lspec, key
    )
    hist = fed.run(eval_every=2)
    assert len(trace.events()) == n0  # spans are free when disabled
    assert hist[-1]["round_seconds"] > 0  # history extras need no tracer


def test_engine_stats_histograms_are_bounded(tiny):
    """Satellite: EngineStats no longer grows with traffic — its latency
    stores are fixed-memory histograms with the percentile API."""
    from repro.core import boosting
    from repro.learners import get_learner
    from repro.serve import ServeEngine

    Xs, ys, masks, Xte, _, lspec, key = tiny
    learner = get_learner(lspec.name)
    state = boosting.init_boost_state(learner, lspec, 2, masks, key, X=Xs)
    rfn = jax.jit(
        lambda s: boosting.adaboost_f_round(learner, lspec, s, Xs, ys, masks)
    )
    for _ in range(2):
        state, _ = rfn(state)
    engine = ServeEngine(learner, lspec, state.ensemble, batch_size=64)
    Xte_np = np.asarray(Xte)[:200]
    n = Xte_np.shape[0]
    ids = engine.submit(Xte_np)  # the latency-recording path
    engine.flush()
    assert len(ids) == n
    lat = engine.stats.request_latencies
    assert isinstance(lat, Histogram)
    assert isinstance(engine.stats.batch_seconds, Histogram)
    assert len(lat) == n
    assert lat.percentile(99) >= lat.percentile(50) > 0
