"""Observability subsystem tests: tracer semantics, zero-cost disabled
path, deterministic exports, metrics registry, and the serving engine's
unified metrics surface.

The load-bearing pins:

* **bit-identity** — tracing on vs off changes NO query result, ledger
  entry, or virtual-clock timing (the per-triple sweep lives in
  ``test_matrix.test_ledger_span_coverage_every_triple``; here the
  serving engine's responses are pinned end-to-end);
* **zero-cost disabled path** — with no tracer active the module-level
  helpers return the shared no-op handle, no spans are recorded, and a
  traced run leaves every stage jit cache untouched (instrumentation is
  host-side only — it can never grow a jit cache);
* **no sync, two sinks** — traced paths never wait on the device; under
  a ``jax.profiler`` trace the spans land in the profile, nested as the
  engine runs them, and each stage program names its ``fatrq.*`` scopes;
* **deterministic exports** — the same seeded serving trace exports a
  byte-identical wall-stripped JSONL and Chrome-trace JSON across runs,
  and the Chrome trace shows batch N+1's front overlapping batch N's
  refine on the virtual clock.
"""

import json
import warnings

import jax
import numpy as np
import pytest

from repro.anns import (Database, PipelineConfig, QueryPlan, StreamingConfig,
                        StreamingIndex, build)
from repro.data import make_dataset
from repro.memory.tiers import TABLE_I, QueryCost, Tier, Traffic
from repro.obs import export, metrics, trace
from repro.serving import Request, ResultCache, ServingEngine, TenantQoS


@pytest.fixture(scope="module")
def ds():
    return make_dataset(jax.random.PRNGKey(0), n=1500, d=32, n_queries=8,
                        k_gt=20, clusters=8)


@pytest.fixture(scope="module")
def index(ds):
    cfg = PipelineConfig(dim=32, pq_m=4, pq_k=32, nlist=16, nprobe=4,
                         final_k=5, refine_budget=20, trq_levels=2)
    return build(jax.random.PRNGKey(1), ds.x, cfg)


def _requests(ds, n=24, seed=0):
    # ~40 µs mean inter-arrival: fast enough that consecutive batches
    # queue behind the virtual pipeline units, which is what makes the
    # front/refine overlap visible in the exported trace
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(40.0, size=n))
    pool = np.asarray(ds.queries)
    picks = rng.integers(0, pool.shape[0], size=n)
    return [Request(query=pool[picks[i]],
                    tenant="busy" if i % 3 == 0 else "t0",
                    arrival_us=float(arrivals[i]), rid=i)
            for i in range(n)]


def _engine(index, tracer=None):
    return ServingEngine(index, max_batch=4, max_wait_us=100.0,
                         qos={"busy": TenantQoS(rate_rps=2000.0, burst=2)},
                         cache=ResultCache(capacity=64), tracer=tracer)


# ----------------------------------------------------------- trace core


def test_span_nesting_and_sids():
    tr = trace.Tracer()
    with trace.use(tr):
        with trace.span("a") as ha:
            with trace.span("b"):
                trace.event("e", x=1)
            with trace.span("c"):
                pass
    a, b, e, c = tr.spans
    assert [s.sid for s in tr.spans] == [0, 1, 2, 3]
    assert (a.parent, b.parent, e.parent, c.parent) == (None, 0, 1, 0)
    assert ha.span is a
    assert e.attrs == {"x": 1}
    assert e.wall_start_s == e.wall_end_s           # zero-duration
    assert a.wall_s >= b.wall_s >= 0.0
    assert [s.sid for s in tr.children(0)] == [1, 3]
    assert tr.by_name("b") == [b]


def test_set_attr_after_exit_and_wall_prefix_stripping():
    tr = trace.Tracer()
    with trace.use(tr):
        with trace.span("s", keep=1) as h:
            pass
        h.set_attr("wall_lag_s", 3.5)
        h.set_attrs(ledger_bytes=2.0)
    rec = tr.spans[0].to_record(include_wall=False)
    assert rec["attrs"] == {"keep": 1, "ledger_bytes": 2.0}
    assert "wall_start_s" not in rec
    full = tr.spans[0].to_record(include_wall=True)
    assert full["attrs"]["wall_lag_s"] == 3.5


def test_virtual_clock_stamping():
    now = {"t": 100.0}
    tr = trace.Tracer(virtual_clock=lambda: now["t"])
    with trace.use(tr):
        with trace.span("s"):
            now["t"] = 250.0
        ev = tr.event("e", virtual_us=999.0)
    s = tr.spans[0]
    assert (s.virtual_start_us, s.virtual_end_us) == (100.0, 250.0)
    assert s.virtual_us == 150.0
    assert ev.virtual_start_us == ev.virtual_end_us == 999.0
    ex = tr.add_span("x", virtual_start_us=10.0, virtual_end_us=20.0)
    assert ex.virtual_us == 10.0 and ex.wall_s is None


def test_disabled_path_is_noop():
    assert trace.active() is None
    assert trace.span("anything", attr=1) is trace.NOOP_SPAN
    assert trace.event("anything") is None
    with trace.span("x") as h:            # no-op context manager
        h.set_attr("a", 1)
        h.set_attrs(b=2)
    assert h.span is None


def test_traced_run_does_not_grow_jit_caches(ds, index):
    """Instrumentation is host-side only: a traced query must not add a
    single jit-cache entry beyond what the untraced warmup compiled."""
    from repro.anns import stages
    db = Database.wrap(index)
    db.query(ds.queries, k=5)             # warm every stage jit untraced
    sizes = (stages._ivf_candidates._cache_size(),
             stages._reference_refine._cache_size(),
             stages._rerank_survivors._cache_size())
    tr = trace.Tracer()
    with trace.use(tr):
        db.query(ds.queries, k=5)
    assert (stages._ivf_candidates._cache_size(),
            stages._reference_refine._cache_size(),
            stages._rerank_survivors._cache_size()) == sizes
    assert tr.by_name("execute") and tr.by_name("refine.l1")


# -------------------------------------------------------------- metrics


def test_counter_gauge_histogram_semantics():
    reg = metrics.MetricsRegistry()
    c = reg.counter("c_total", "a counter", labelnames=("t",))
    c.labels(t="x").inc()
    c.labels(t="x").inc(2.0)
    with pytest.raises(ValueError):
        c.labels(t="x").inc(-1.0)
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.inc()                            # labeled metric, unlabeled use
    g = reg.gauge("g")
    g.set(4.5)
    g._default_child().inc(0.5)
    h = reg.histogram("h", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert reg.flat() == {'c_total{t="x"}': 3.0, "g": 5.0,
                          "h_count": 3, "h_sum": 55.5}
    with pytest.raises(ValueError):        # conflicting redeclaration
        reg.gauge("c_total")
    assert reg.counter("c_total", labelnames=("t",)) is c   # idempotent


def test_registry_collectors_and_context():
    reg = metrics.MetricsRegistry()
    reg.add_collector(lambda: reg.gauge("snap").set(7.0))
    assert metrics.active() is metrics.default_registry()
    with metrics.use(reg):
        assert metrics.active() is reg
    assert metrics.active() is metrics.default_registry()
    assert reg.flat()["snap"] == 7.0       # collector ran at export


def test_prometheus_exposition_format():
    reg = metrics.MetricsRegistry()
    reg.counter("req_total", "requests", labelnames=("t",)) \
        .labels(t="a").inc(3)
    h = reg.histogram("lat_us", "latency", buckets=(1.0, 10.0))
    for v in (0.5, 0.7, 5.0, 500.0):
        h.observe(v)
    text = export.prometheus_text(reg)
    lines = text.strip().splitlines()
    assert "# TYPE req_total counter" in lines
    assert '"a"' in text and "req_total{t=\"a\"} 3" in lines
    # histogram buckets are CUMULATIVE, +Inf equals _count
    assert 'lat_us_bucket{le="1"} 2' in lines
    assert 'lat_us_bucket{le="10"} 3' in lines
    assert 'lat_us_bucket{le="+Inf"} 4' in lines
    assert "lat_us_count 4" in lines
    assert "lat_us_sum 506.2" in lines


def test_tierspec_seconds_matches_ledger_fold():
    cost = QueryCost()
    cost.record("refine", Tier.CXL, 1000, 64)
    t = cost.ledger["refine:cxl"]
    assert cost.tier_seconds(Tier.CXL) == \
        TABLE_I[Tier.CXL].seconds(t.accesses, t.bytes)
    assert TABLE_I[Tier.SSD].seconds(0, 0) == 0.0


# ------------------------------------------------- serving, end to end


def test_serving_bit_identical_with_tracing(ds, index):
    r_off = _engine(index).run(_requests(ds))
    tr = trace.Tracer()
    r_on = _engine(index, tracer=tr).run(_requests(ds))
    assert len(r_off) == len(r_on) > 0
    for a, b in zip(r_off, r_on):
        assert a.rid == b.rid
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.distances, b.distances)
        assert (a.done_us, a.admit_us, a.degraded, a.cache_hit) == \
            (b.done_us, b.admit_us, b.degraded, b.cache_hit)
    assert tr.spans


def test_serving_trace_exports_byte_identical(ds, index, tmp_path):
    paths = []
    for run in range(2):
        tr = trace.Tracer()
        _engine(index, tracer=tr).run(_requests(ds))
        p = tmp_path / f"spans_{run}.jsonl"
        export.write_jsonl(tr.spans, str(p), include_wall=False)
        c = tmp_path / f"chrome_{run}.json"
        export.write_chrome_trace(tr.spans, str(c))
        paths.append((p.read_bytes(), c.read_bytes()))
    assert paths[0] == paths[1]


def test_chrome_trace_schema_and_overlap(ds, index):
    tr = trace.Tracer()
    _engine(index, tracer=tr).run(_requests(ds))
    doc = export.chrome_trace(tr.spans)
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in events if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
    tids = {e["args"]["name"]: e["tid"] for e in meta
            if e["name"] == "thread_name"}
    assert {"sched", "unit:front", "unit:refine", "query"} <= set(tids)
    for e in events:
        assert e["ph"] in ("M", "X", "i")
        if e["ph"] == "X":
            assert e["dur"] > 0 and e["ts"] >= 0
        if e["ph"] != "M":
            assert "sid" in e["args"]
    json.dumps(doc)                        # schema is JSON-serializable
    # double-buffering: some batch's front interval overlaps another
    # batch's refine interval on the virtual clock
    fronts = [(e["ts"], e["ts"] + e["dur"]) for e in events
              if e["name"] == "serve.front"]
    refines = [(e["ts"], e["ts"] + e["dur"]) for e in events
               if e["name"] == "serve.refine"]
    assert len(fronts) >= 2 and len(refines) >= 2
    assert any(f[0] < r[1] and r[0] < f[1]
               for f in fronts for r in refines), \
        "no front/refine overlap visible in the exported trace"


def test_serving_metrics_unified_flat_dict(ds, index):
    tr = trace.Tracer()
    eng = _engine(index, tracer=tr)
    eng.run(_requests(ds))
    flat = eng.metrics()
    assert flat['serving_requests_total{tenant="busy"}'] > 0
    assert flat['serving_throttled_total{tenant="busy"}'] > 0
    assert flat['serving_stats{field="requests"}'] == eng.stats.requests
    assert flat['serving_stats{field="batches"}'] == eng.stats.batches
    assert flat['serving_cache{field="misses"}'] == eng.cache.stats.misses
    assert flat["serving_queue_wait_us_count"] > 0
    assert flat["serving_batch_occupancy_count"] == eng.stats.batches
    text = export.prometheus_text(eng.registry)
    for series in ("serving_queue_wait_us", "serving_batch_occupancy",
                   "serving_cache", "serving_stats"):
        assert series in text


def test_streaming_mutation_events_and_metrics(ds, index):
    st = StreamingIndex(index, StreamingConfig(auto_compact=False))
    reg = metrics.MetricsRegistry()
    tr = trace.Tracer()
    with metrics.use(reg), trace.use(tr):
        gids = st.insert(ds.x[:40])
        st.delete(gids[:10])
        st.compact()
    names = [s.name for s in tr.spans]
    assert {"index.insert", "index.delete", "index.compact"} <= set(names)
    ins = tr.by_name("index.insert")[0]
    assert ins.attrs["n"] == 40 and "tombstone_frac" in ins.attrs
    flat = reg.flat()
    assert flat['streaming_mutations_total{op="insert"}'] == 1.0
    assert flat['streaming_mutations_total{op="compact"}'] == 1.0
    assert flat["streaming_tombstone_frac"] == 0.0   # compact dropped them


def test_cache_events(ds, index):
    tr = trace.Tracer()
    eng = _engine(index, tracer=tr)
    q0, q1 = np.asarray(ds.queries[0]), np.asarray(ds.queries[1])
    # q1's dispatch retires q0's in-flight batch (double buffering), so
    # q0's result is cached by the time its repeat arrives at t=5000
    eng.run([Request(query=q0, arrival_us=0.0, rid=0),
             Request(query=q1, arrival_us=300.0, rid=1),
             Request(query=q0, arrival_us=5000.0, rid=2)])
    assert len(tr.by_name("cache.miss")) == 2
    assert len(tr.by_name("cache.hit")) == 1
    assert len(tr.by_name("serve.cache_hit")) == 1


def test_compile_cache_span(ds, index):
    db = Database(index)                   # fresh handle: empty plan cache
    tr = trace.Tracer()
    with trace.use(tr):
        db.query(ds.queries, k=5)
        db.query(ds.queries, k=5)
    probes = tr.by_name("plan.compile")
    assert [p.attrs["cache_hit"] for p in probes] == [False, True]
    assert len(tr.by_name("plan.compile.build")) == 1


# ------------------------------------------- profiler sink and device scopes


_SCOPES = {
    "ivf_candidates": ("fatrq.front.probe", "fatrq.front.adc"),
    "pallas_refine": ("fatrq.refine.gather", "fatrq.refine.kernel"),
    "rerank_survivors": ("fatrq.rerank",),
}


def _stage_program(name, ds, index):
    """The stage jit ``name`` lowered for one 8-query micro-batch."""
    from repro.anns import stages
    q = ds.queries[:8]
    cfg = index.config
    args = (index.ivf, index.codebook, index.pq_codes, q, None)
    if name == "ivf_candidates":
        return stages._ivf_candidates.lower(*args, nprobe=cfg.nprobe)
    ids, valid, d0, _ = stages._ivf_candidates(*args, nprobe=cfg.nprobe)
    if name == "pallas_refine":
        return stages._pallas_refine.lower(
            q, d0, ids, valid, None, index.trq, k=5, bound=cfg.bound,
            z=cfg.z, block_c=128)
    return stages._rerank_survivors.lower(index.x, q, ids, d0, valid, k=5,
                                          budget=20)


@pytest.mark.parametrize("program", sorted(_SCOPES))
def test_stage_programs_name_their_scopes(ds, index, program):
    """Each layer's device work carries its named scope in the lowered
    module's locations and in the compiled HLO's op names — the op path a
    profile gives every device op."""
    lowered = _stage_program(program, ds, index)
    text = lowered.as_text(debug_info=True)
    compiled = lowered.compile().as_text()
    for scope in _SCOPES[program]:
        assert f"/{scope}/" in text, scope
        assert f"/{scope}/" in compiled, scope


def _host_spans(log_dir):
    """fatrq.* host spans of a profile: [(name, start, end, stats, line)]."""
    import glob
    import os
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    with warnings.catch_warnings():        # the stats' type lacks a module
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("fatrq."):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats), line.name))
    return out


def test_serve_spans_reach_the_profiler_nested(ds, index, tmp_path):
    """Without a Tracer, a profile of ``serve`` holds the program's spans
    at every layer boundary, nested as the engine runs them: each batch's
    dispatch (with its front) and retire (with refine, rerank, fold) under
    the call, and every wait for the device inside the call.  The engine's
    spans carry the batch id ``bid``."""
    eng = ServingEngine(index, max_batch=4)
    eng.serve(ds.queries)                  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve(ds.queries)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)

    def inside(child, parents):
        return any(p[4] == child[4] and p[1] <= child[1] and child[2] <= p[2]
                   for p in parents)

    serve = by["fatrq.serve"]
    assert len(serve) == 1
    for name in ("fatrq.serve.requests", "fatrq.serve.admit",
                 "fatrq.serve.dispatch", "fatrq.serve.retire", "fatrq.wait"):
        assert by[name] and all(inside(s, serve) for s in by[name]), name
    assert all(inside(s, by["fatrq.serve.dispatch"]) for s in by["fatrq.front"])
    for name in ("fatrq.refine", "fatrq.rerank", "fatrq.fold"):
        assert by[name] and all(inside(s, by["fatrq.serve.retire"])
                                for s in by[name]), name
    assert any(inside(s, by["fatrq.fold"]) for s in by["fatrq.wait"])
    bids = {s[3]["bid"] for s in by["fatrq.serve.dispatch"]}
    assert bids == {s[3]["bid"] for s in by["fatrq.serve.retire"]}
    assert len(bids) == len(by["fatrq.serve.dispatch"]) == 2
    # the executor's spans take their batch from the engine span holding
    # them: one front in each dispatch, one finish in each retire
    for outer, inner in (("fatrq.serve.dispatch", "fatrq.front"),
                         ("fatrq.serve.retire", "fatrq.finish")):
        assert sorted(sum(inside(s, [p]) for s in by[inner])
                      for p in by[outer]) == [1, 1], inner


def test_traced_paths_never_block_on_the_device(ds, index, monkeypatch):
    """With a Tracer active, queries and serving run with
    ``jax.block_until_ready`` unusable and answer bit-identically to the
    untraced run: a span measures host time and never syncs."""
    db = Database.wrap(index)
    plan = QueryPlan(backend="pallas", micro_batch=4, k=5)
    want = db.query(ds.queries, plan=plan)
    want_served = ServingEngine(index, max_batch=4).serve(ds.queries)

    def refuse(*_, **__):
        raise AssertionError("block_until_ready called while tracing")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    tr = trace.Tracer()
    with trace.use(tr):
        got = db.query(ds.queries, plan=plan)
    served = ServingEngine(index, max_batch=4, tracer=trace.Tracer()) \
        .serve(ds.queries)
    assert {"front", "refine", "rerank", "fold", "wait"} <= \
        {s.name for s in tr.spans}
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids))
    assert np.array_equal(np.asarray(got.distances),
                          np.asarray(want.distances))
    for a, b in zip(served, want_served):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.distances, b.distances)
