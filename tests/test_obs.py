"""Observability layer (docs/observability.md): metrics registry +
exporters, Chrome-trace spans, and fp8 quant-health telemetry.

The load-bearing acceptance test is `test_health_off_is_free`: with
REPRO_QUANT_HEALTH off and tracing unset, the decode/verify jaxprs
must be BYTE-IDENTICAL to an obs-free build (building and tracing a
health-enabled step in between must not leak into them), and the
delayed-scale decode graph keeps ZERO quantization reductions.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.actscale import ActScale, calibrate_act_scales
from repro.core.formats import QuantConfig, fp8_max
from repro.core.introspect import count_quant_reductions
from repro.core.quant import quant_excursions
from repro.models.layers import init_tree
from repro.models.transformer import init_caches, model_defs
from repro.obs.metrics import (
    DRIFT_BUCKETS,
    LATENCY_BUCKETS_S,
    RATE_BUCKETS,
    Registry,
    compiles_between,
    count_compiles,
    get_registry,
)
from repro.obs.quant_health import (
    DRIFT_THRESHOLD,
    HealthAggregator,
    TaggedScale,
    site_stats,
)
from repro.obs.trace import Tracer
from repro.serving.scheduler import Request, Scheduler
from repro.train.steps import (
    make_decode_step,
    make_verify_step,
    prequantize_params,
)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_inc_and_set_total():
    reg = Registry()
    c = reg.counter("events_total", help="h")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # set_total adopts an external running total, max-wise: calling
    # stats() repeatedly must not double count or move backwards
    c2 = reg.counter("engine_preemptions_total")
    c2.set_total(4)
    c2.set_total(4)
    c2.set_total(2)               # stale read never decreases
    assert c2.value() == 4.0


def test_gauge_and_labels():
    reg = Registry()
    g = reg.gauge("pages_in_use")
    g.set(7, labels={"pool": "kv"})
    g.set(3, labels={"pool": "host"})
    assert g.value(labels={"pool": "kv"}) == 7.0
    assert g.value(labels={"pool": "host"}) == 3.0


def test_histogram_bucket_counts_exact():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 2.0, 100.0):
        h.observe(v)
    snap = reg.snapshot()["lat"]["series"][""]
    # per-bucket: <=0.1 gets 0.05 and 0.1; <=1.0 gets 0.5; <=10 gets
    # 2.0; overflow gets 100
    assert snap["counts"] == [2, 1, 1, 1]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(102.65)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 1.0, 2.0))


def test_registry_kind_mismatch_is_error():
    reg = Registry()
    reg.counter("x")
    assert reg.counter("x") is reg.counter("x")   # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_snapshot_and_json_never_nan():
    reg = Registry()
    reg.gauge("g").set(float("nan"))
    reg.gauge("g2").set(float("inf"))
    snap = reg.snapshot()
    assert snap["g"]["series"][""] is None
    assert snap["g2"]["series"][""] is None
    # json.dumps(allow_nan=False) would raise on NaN/Inf leakage
    json.loads(reg.to_json())


def test_prometheus_text_format():
    reg = Registry()
    reg.counter("req_total", help="requests").inc(3)
    h = reg.histogram("ttft", buckets=(0.1, 1.0),
                      help="time to first token")
    h.observe(0.05, labels={"site": "a"})
    h.observe(5.0, labels={"site": "a"})
    text = reg.to_prometheus()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert "req_total 3" in text
    assert "# TYPE ttft histogram" in text
    # cumulative buckets + the +Inf catch-all, sum and count
    assert 'ttft_bucket{site="a",le="0.1"} 1' in text
    assert 'ttft_bucket{site="a",le="1"} 1' in text
    assert 'ttft_bucket{site="a",le="+Inf"} 2' in text
    assert 'ttft_sum{site="a"} 5.05' in text
    assert 'ttft_count{site="a"} 2' in text


# ---------------------------------------------------------------------------
# Chrome-trace spans
# ---------------------------------------------------------------------------


def test_trace_span_schema_and_save(tmp_path):
    t = Tracer()
    t.enable(path=str(tmp_path / "trace.json"))
    with t.span("engine.step", rows=3):
        with t.span("decode"):
            pass
    t.instant("preempt", rid=7)
    evs = t.events()
    assert [e["name"] for e in evs] == ["decode", "engine.step",
                                       "preempt"]
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        assert isinstance(e["ts"], int)
    x0, x1, inst = evs
    assert x0["ph"] == "X" and x1["ph"] == "X" and inst["ph"] == "i"
    assert x0["dur"] >= 0 and x1["dur"] >= x0["dur"]  # nesting
    assert x1["args"] == {"rows": 3} and inst["args"] == {"rid": 7}
    # the saved file is a Chrome-trace JSON array Perfetto accepts
    path = t.save()
    loaded = json.load(open(path))
    assert loaded == evs


def test_trace_ring_buffer_bounds_memory():
    t = Tracer(capacity=4)
    t.enable()
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 4
    assert [e["name"] for e in t.events()] == ["s6", "s7", "s8", "s9"]


def test_trace_disabled_is_shared_noop():
    t = Tracer()
    a, b = t.span("x"), t.span("y", k=1)
    assert a is b                 # one shared null context manager
    with a:
        pass
    assert len(t) == 0
    t.instant("z")
    assert len(t) == 0


def test_span_lands_in_the_profiler_trace(tmp_path):
    """An enabled span is a host event ``repro.<name>`` of the JAX
    profiler's trace, beside the device's ops and on their clock."""
    import glob
    import time

    t = Tracer()
    t.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("engine.step", rows=2):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    spans = [d for n, d in events if n == "repro.engine.step"]
    assert len(spans) == 1 and spans[0] >= 1e7
    assert [e["name"] for e in t.events()] == ["engine.step"]


def test_compile_counter_counts_each_new_executable():
    import time

    count_compiles()
    count_compiles()                 # once per process: no second listener
    reg = get_registry()
    f = jax.jit(lambda a: a * 3.0 + 1.0)
    x = jnp.arange(7.0)
    n0 = reg.counter("jax_compiles_total").value()
    s0 = reg.counter("jax_compile_seconds_total").value()
    t0 = time.monotonic()
    f(x).block_until_ready()
    t1 = time.monotonic()
    assert reg.counter("jax_compiles_total").value() == n0 + 1
    assert reg.counter("jax_compile_seconds_total").value() > s0
    assert compiles_between(t0, t1) == 1
    f(x).block_until_ready()         # a cache hit builds nothing
    assert reg.counter("jax_compiles_total").value() == n0 + 1
    assert compiles_between(t1, time.monotonic()) == 0


# ---------------------------------------------------------------------------
# Quant health: exact stats on crafted tensors
# ---------------------------------------------------------------------------

_PT = QuantConfig(mode="per_tensor")


def test_quant_excursions_exact():
    fmax = fp8_max("e4m3")        # 448
    scale = jnp.float32(1.0 / fmax)   # representable max = 1.0
    x = jnp.abs(jnp.asarray(
        [2.0, 1.5, 1.0, 0.5, 1e-6, 0.0, 0.25, 0.125], jnp.float32))
    sat, under, nonzero = quant_excursions(x, scale, "e4m3")
    # 2.0 and 1.5 clip; 1.0 is exactly representable; 1e-6/scale =
    # 4.48e-4 < e4m3's rounding floor (2^-10) so it quantizes to 0;
    # the true 0.0 is not an underflow (it was never information)
    assert float(sat) == 2.0
    assert float(under) == 1.0
    assert float(nonzero) == 7.0


def test_site_stats_exact_per_tensor():
    fmax = fp8_max("e4m3")
    a = ActScale(s=jnp.float32(1.0 / fmax), sub=jnp.zeros((), jnp.int8))
    x = jnp.asarray([[2.0, -1.5, 1.0, 0.5, 1e-6, 0.0, -0.25, 0.125]],
                    jnp.float32)
    st = {k: float(v) for k, v in site_stats(x, a, _PT).items()}
    assert st["n"] == 8.0
    assert st["sat"] == 2.0
    assert st["underflow"] == 1.0
    assert st["nonzero"] == 7.0
    assert st["amax"] == 2.0
    # drift = amax / (s * fmax) = 2.0 / 1.0
    assert st["drift"] == pytest.approx(2.0)


def test_site_stats_healthy_drift_below_threshold():
    # a calibration-style scale (margin 1.25 over the live amax) puts
    # drift at exactly 1/margin — comfortably under the threshold
    fmax = fp8_max("e4m3")
    margin = 1.25
    a = ActScale(s=jnp.float32(margin * 2.0 / fmax),
                 sub=jnp.zeros((), jnp.int8))
    x = jnp.full((4, 8), 2.0, jnp.float32)
    st = site_stats(x, a, _PT)
    assert float(st["drift"]) == pytest.approx(1 / margin)
    assert float(st["drift"]) < DRIFT_THRESHOLD
    assert float(st["sat"]) == 0.0


def test_health_aggregator_rates_and_refresh_flag():
    reg = Registry()
    agg = HealthAggregator(registry=reg)
    # stacked-(layers,) stats as the scan emits them: 2 layers
    healthy = {"blocks/ffn/w1": {
        "n": np.asarray([8.0, 8.0]), "sat": np.asarray([0.0, 0.0]),
        "underflow": np.asarray([0.0, 0.0]),
        "nonzero": np.asarray([8.0, 8.0]),
        "amax": np.asarray([1.0, 1.0]),
        "drift": np.asarray([0.8, 0.7])}}
    agg.ingest(healthy)
    assert not agg.refresh_recommended
    bad = {"blocks/ffn/w1": {
        "n": np.asarray([8.0, 8.0]), "sat": np.asarray([2.0, 0.0]),
        "underflow": np.asarray([1.0, 0.0]),
        "nonzero": np.asarray([7.0, 8.0]),
        "amax": np.asarray([2.0, 1.0]),
        "drift": np.asarray([2.0, 0.7])}}
    agg.ingest(bad)
    assert agg.refresh_recommended
    assert reg.gauge("quant_health_refresh_recommended").value() == 1.0
    rep = agg.report()["blocks/ffn/w1"]
    assert rep["saturation_rate"] == pytest.approx(2 / 32)
    assert rep["underflow_rate"] == pytest.approx(1 / 31)
    assert rep["drift_max"] == pytest.approx(2.0)
    assert rep["steps"] == 2
    # histograms got one observation per ingest per site
    snap = reg.snapshot()
    series = snap["quant_health_drift_ratio"]["series"]
    assert series['{site="blocks/ffn/w1"}']["count"] == 2
    agg.ingest({})                # empty step tree is a no-op
    assert agg.report()["blocks/ffn/w1"]["steps"] == 2


# ---------------------------------------------------------------------------
# Quant health end-to-end: the step functions
# ---------------------------------------------------------------------------


def _serving_build(cfg):
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    pq = prequantize_params(cfg, params)
    act = calibrate_act_scales(cfg, pq.qweights, pq.scales)
    return pq.qweights, pq.scales, act


def test_health_step_reports_sites_and_stale_scale_trips_flag():
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    qw, scales, act = _serving_build(cfg)
    caches = init_caches(cfg, 2, 16, per_slot=True)
    feed = jnp.ones((2, 1), jnp.int32)
    step = jax.jit(make_decode_step(cfg, scales=scales, act_scales=act,
                                    quant_health=True))
    logits, caches2, tree = step(qw, caches, feed)
    assert tree, "health-enabled decode returned no site stats"
    assert all("/" in tag for tag in tree)      # path_tag site keys
    stacked = [t for t, st in tree.items() if st["drift"].ndim == 1]
    assert stacked, "no scan-stacked (layers,) site stats"
    # numerically identical logits to the health-off step
    step_off = jax.jit(make_decode_step(cfg, scales=scales,
                                        act_scales=act))
    logits_off, _ = step_off(qw, caches, feed)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(logits_off))
    reg = Registry()
    agg = HealthAggregator(registry=reg)
    agg.ingest(tree)
    calibrated_drift = max(s["drift_max"]
                           for s in agg.report().values())
    # a deliberately STALE ActScale — live amax far beyond calibrated
    # × margin — must drive drift over the threshold and recommend a
    # refresh (the Engine.refresh_act_scales runbook)
    stale = {tag: ActScale(s=jax.tree.map(lambda v: v * 0.25, a.s),
                           sub=a.sub) for tag, a in act.items()}
    step_stale = jax.jit(make_decode_step(cfg, scales=scales,
                                          act_scales=stale,
                                          quant_health=True))
    _, _, tree_stale = step_stale(qw, caches, feed)
    agg2 = HealthAggregator(registry=Registry())
    agg2.ingest(tree_stale)
    assert agg2.refresh_recommended
    stale_drift = max(s["drift_max"] for s in agg2.report().values())
    assert stale_drift == pytest.approx(4 * calibrated_drift, rel=1e-3)


def test_tagged_scale_is_pytree_with_static_tag():
    ts = TaggedScale("blocks/attn/wq",
                     ActScale(s=jnp.ones((3,)), sub=jnp.zeros((3,),
                                                              jnp.int8)))
    leaves, treedef = jax.tree_util.tree_flatten(ts)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.tag == "blocks/attn/wq"
    # scan-style slicing keeps the tag and slices the arrays
    sliced = jax.tree.map(lambda x: x[0], ts)
    assert sliced.tag == ts.tag and sliced.scale.s.shape == ()


# ---------------------------------------------------------------------------
# THE acceptance contract: telemetry off is free
# ---------------------------------------------------------------------------


def test_health_off_is_free(monkeypatch):
    """With REPRO_QUANT_HEALTH=0 and REPRO_TRACE unset the decode and
    verify jaxprs are byte-identical to an obs-free build — tracing a
    health-enabled step in between must not leak (module-collector
    state, TaggedScale wrapping) into later off builds — and the
    delayed-scale decode graph keeps ZERO quantization reductions."""
    monkeypatch.delenv("REPRO_QUANT_HEALTH", raising=False)
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    cfg = get_config("phi3-mini-3.8b", smoke=True).replace(
        kv_cache_dtype="bf16")    # fp8 cache keeps its 2 storage amaxes
    qw, scales, act = _serving_build(cfg)
    caches = init_caches(cfg, 2, 16, per_slot=True)
    feed1 = jnp.zeros((2, 1), jnp.int32)
    feedk = jnp.zeros((2, 4), jnp.int32)

    def jaxprs():
        dec = jax.make_jaxpr(make_decode_step(
            cfg, scales=scales, act_scales=act))(qw, caches, feed1)
        ver = jax.make_jaxpr(make_verify_step(
            cfg, scales=scales, act_scales=act))(qw, caches, feedk)
        return dec, ver

    dec0, ver0 = jaxprs()
    assert count_quant_reductions(dec0) == 0
    assert count_quant_reductions(ver0) == 0
    # build AND trace health-enabled steps (the leak hazard)
    jax.make_jaxpr(make_decode_step(cfg, scales=scales, act_scales=act,
                                    quant_health=True))(qw, caches,
                                                        feed1)
    jax.make_jaxpr(make_verify_step(cfg, scales=scales, act_scales=act,
                                    quant_health=True))(qw, caches,
                                                        feedk)
    dec1, ver1 = jaxprs()
    assert str(dec0) == str(dec1), "decode jaxpr changed after a " \
        "health-enabled build — telemetry off is not free"
    assert str(ver0) == str(ver1)


def test_health_on_adds_no_quant_reductions():
    """The health stats are element-wise compares + small max
    reductions that never feed an fp8 cast: count_quant_reductions
    stays 0 even with telemetry ON (bf16 cache isolates the KV
    storage amaxes away)."""
    cfg = get_config("phi3-mini-3.8b", smoke=True).replace(
        kv_cache_dtype="bf16")
    qw, scales, act = _serving_build(cfg)
    caches = init_caches(cfg, 2, 16, per_slot=True)
    jx = jax.make_jaxpr(make_decode_step(
        cfg, scales=scales, act_scales=act, quant_health=True))(
        qw, caches, jnp.zeros((2, 1), jnp.int32))
    assert count_quant_reductions(jx) == 0


# ---------------------------------------------------------------------------
# Scheduler summary: NaN-free JSON + registry routing
# ---------------------------------------------------------------------------


def test_scheduler_summary_empty_is_valid_json():
    s = Scheduler().summary()
    for key in ("tok_per_s", "mean_ttft_s", "mean_tpot_s", "p50_ttft_s",
                "p99_tpot_s", "spec_accept_rate"):
        assert s[key] is None, f"{key} should be None with no data"
    text = json.dumps(s, allow_nan=False)   # raises on NaN leakage
    assert "NaN" not in text


def test_scheduler_publishes_latency_histograms():
    get_registry().reset()
    state = {"t": 0.0}
    sched = Scheduler(clock=lambda: state["t"])
    req = Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2)
    sched.submit([req])
    sched.pop()
    state["t"] = 0.3
    sched.on_token(req, 1)
    state["t"] = 0.35
    assert sched.on_token(req, 2) and req.done
    snap = get_registry().snapshot()
    assert snap["sched_ttft_seconds"]["series"][""]["count"] == 1
    assert snap["sched_ttft_seconds"]["series"][""]["sum"] == \
        pytest.approx(0.3)
    assert snap["sched_tpot_seconds"]["series"][""]["count"] == 1
    s = sched.summary()
    assert snap is not None and s["requests"] == 1
    assert get_registry().counter(
        "sched_tokens_generated_total").value() == 2.0
