"""Observability layer tests.

Three levels: (a) model-free — ring-buffer tracer semantics (capacity,
wraparound, Chrome export format), log-binned histogram percentiles
against a numpy reference, registry snapshot/restore and prefix drop,
SLO accounting; (b) the no-overhead contract — the disabled path costs
only no-op method calls, bounded analytically at well under 1% of any
plausible serving wall; (c) with models — the 4-feed / 9-query gated +
pipelined serving workload produces bitwise-identical per-query outputs
with observability enabled vs the ``NULL_OBS`` default, and the server's
``queue_depth`` / ``inflight`` stats entries stay truthful gauges across
``reset_stats()``.
"""
import dataclasses
import json
import time

import numpy as np
import pytest

from repro.obs import (
    NULL_OBS,
    NULL_TRACER,
    Histogram,
    Metrics,
    Observability,
    PHASES,
    SLOTracker,
    Tracer,
    resolve_obs,
)


@pytest.fixture(scope="module")
def ctx(stream_ctx):
    return stream_ctx


# ---------------------------------------------------------------------------
# (a) tracer: ring buffer, wraparound, export
# ---------------------------------------------------------------------------

def test_tracer_records_spans_instants_counters():
    tr = Tracer(capacity=16)
    t0 = tr.now()
    tr.span("prefix:skip", "prefix", t0, t0 + 1000, track="feed:a", n=16)
    tr.instant("gate:hit", "gate", track="feed:a", n=3)
    tr.counter("inflight", 2)
    evs = tr.events()
    assert [e["kind"] for e in evs] == ["X", "i", "C"]
    assert evs[0]["name"] == "prefix:skip" and evs[0]["n"] == 16
    assert evs[0]["t1_ns"] - evs[0]["t0_ns"] == 1000
    assert evs[2]["n"] == 2 and evs[2]["track"] == "counters"
    assert tr.recorded == 3 and tr.dropped == 0
    tr.reset()
    assert tr.events() == [] and tr.recorded == 0


def test_tracer_ring_wraparound_keeps_newest():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.span(f"s{i}", "prefix", i, i + 1)
    assert tr.recorded == 20 and tr.dropped == 12
    evs = tr.events()
    assert len(evs) == 8
    # oldest surviving first, newest last — overwrite, never shift
    assert [e["name"] for e in evs] == [f"s{i}" for i in range(12, 20)]


def test_chrome_export_is_perfetto_loadable_json(tmp_path):
    tr = Tracer()
    t0 = tr.now()
    tr.span("forward[big]", "forward", t0, t0 + 5_000_000, track="device",
            n=32)
    tr.span("queue_wait", "queue", t0, t0 + 1_000_000, track="feed:a",
            n=16)
    tr.instant("gate:miss", "gate", track="feed:a", n=1)
    tr.counter("inflight", 1)
    path = tmp_path / "trace.json"
    assert tr.export_chrome(str(path)) == 4
    data = json.loads(path.read_text())
    evs = data["traceEvents"]
    # thread-name metadata for every track + the process name
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"device", "feed:a", "counters", "repro-serving"} <= names
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == 2
    fwd = next(e for e in spans if e["name"] == "forward[big]")
    assert fwd["dur"] == pytest.approx(5000.0)      # µs
    assert fwd["args"]["n"] == 32
    assert all("ts" in e and "pid" in e and "tid" in e
               for e in evs if e["ph"] != "M")
    assert data["otherData"]["dropped_events"] == 0


# ---------------------------------------------------------------------------
# (a) metrics: histogram percentiles vs numpy, snapshot/restore, drop
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy_within_bin_width():
    rng = np.random.default_rng(7)
    # lognormal spans ~3 decades — the shape latencies actually have
    vals = rng.lognormal(mean=2.0, sigma=1.0, size=20_000)
    h = Histogram()
    for v in vals:
        h.record(float(v))
    rel = h.growth - 1.0                 # one bin's relative width
    for p in (50, 90, 95, 99):
        ref = np.percentile(vals, p)
        assert h.percentile(p) == pytest.approx(ref, rel=3 * rel + 1e-3)
    assert h.mean() == pytest.approx(vals.mean(), rel=1e-6)
    assert h.percentile(0) >= h.vmin and h.percentile(100) <= h.vmax


def test_histogram_weighted_and_clamped():
    h = Histogram()
    h.record(10.0, n=99)
    h.record(1e9, n=1)                   # beyond the binned range: clamps
    assert h.count == 100
    assert h.percentile(50) == pytest.approx(10.0, rel=0.05)
    assert h.percentile(99.9) <= h.vmax
    h2 = Histogram()
    h2.record(1e-9)                      # below lo: bin 0, clamped to vmin
    assert h2.percentile(50) == pytest.approx(1e-9)


def test_metrics_snapshot_restore_drops_later_metrics():
    m = Metrics()
    m.inc("requests", 5)
    m.set_gauge("wall_s", 1.5)
    m.observe("lat_ms/a", 3.0, 4)
    snap = m.snapshot()
    m.inc("requests", 100)
    m.observe("lat_ms/a", 50.0)
    m.inc("created_later")
    m.restore(snap)
    assert m.counter("requests").value == 5
    assert m.gauge("wall_s").value == 1.5
    assert m.histogram("lat_ms/a").count == 4
    assert "created_later" not in m._counters
    rows = {r["name"]: r for r in m.to_rows()}
    assert rows["lat_ms/a"]["p50"] == pytest.approx(3.0, rel=0.05)


def test_metrics_drop_prefix():
    m = Metrics()
    m.observe("queue_wait_ms/a", 1.0)
    m.observe("queue_wait_ms/b", 2.0)
    m.observe("forward_ms", 3.0)
    m.inc("forwards")
    m.drop("queue_wait_ms")
    m.drop("forward_ms")
    names = {r["name"] for r in m.to_rows()}
    assert names == {"forwards"}         # exact name + prefix/ both drop


def test_slo_tracker_rows_and_combined():
    m = Metrics()
    slo = SLOTracker(m, target_ms=100.0)
    slo.set_target("b", 10.0)
    for _ in range(90):
        slo.record("a", 50.0)
    for _ in range(10):
        slo.record("a", 400.0, staleness_ms=500.0)
    slo.record("b", 20.0, n=10)          # over b's tighter target
    ra = slo.row("a")
    assert ra["frames"] == 100 and ra["violations"] == 10
    assert ra["attainment"] == pytest.approx(0.9)
    assert ra["p50_ms"] == pytest.approx(50.0, rel=0.05)
    assert ra["p99_ms"] == pytest.approx(400.0, rel=0.05)
    rb = slo.row("b")
    assert rb["violations"] == 10 and rb["attainment"] == 0.0
    c = slo.combined()
    assert c["frames"] == 110 and c["violations"] == 20
    assert "ALL" in slo.table() and "a" in slo.table()


def test_observability_resolution_and_null():
    assert resolve_obs(None, None) is NULL_OBS
    o = Observability(tracer=NULL_TRACER)
    assert resolve_obs(None, o) is o
    assert NULL_OBS.now() == 0 and not NULL_OBS.enabled
    assert o.now() > 0                   # metrics-only mode keeps a clock
    assert o.tracer.events() == []


# ---------------------------------------------------------------------------
# (b) the no-overhead contract
# ---------------------------------------------------------------------------

def test_disabled_path_overhead_bounded_under_one_percent():
    # the disabled serving path executes only `obs.enabled` checks,
    # NULL_OBS.now() and NullTracer no-op calls; measure their cost and
    # bound the total against a deliberately pessimistic serving profile
    reps = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(reps):
        NULL_OBS.now()
        NULL_TRACER.span("x", "prefix", 0, 0)
    per_site_ns = (time.perf_counter_ns() - t0) / reps
    assert per_site_ns < 10_000          # ~100ns each in practice
    # pessimistic profile: 40 instrumented sites per frame, serving at
    # 200 frames/s (5ms/frame — far faster than this stack goes on CPU)
    overhead = (40 * per_site_ns) / 5e6
    assert overhead < 0.01


def test_span_site_without_session_bounded_under_one_percent():
    # with no profiler session and NULL_OBS a span site is one call that
    # checks both sinks and returns the falsy NULL_SPAN; bound it against
    # a pessimistic profile: 16 span sites per frame (one micro-batch's
    # ingest, prefix ops, queue wait, resume, tails and launch, as if
    # every frame were its own micro-batch) at 200 frames/s
    from repro.obs.spans import NULL_SPAN, span
    assert span(NULL_OBS, "ingest", "ingest", "feed:a") is NULL_SPAN
    reps = 100_000
    t0 = time.perf_counter_ns()
    for i in range(reps):
        with span(NULL_OBS, "prefix:skip", "prefix", "feed:a", n=16,
                  feed="a", mb=i) as s:
            if s:
                s.set(n_out=16)
    per_site_ns = (time.perf_counter_ns() - t0) / reps
    assert (16 * per_site_ns) / 5e6 < 0.01


# ---------------------------------------------------------------------------
# (c) with models: bitwise identity + server gauges
# ---------------------------------------------------------------------------

#: the benchmark workload in miniature: 4 feeds, 9 queries
_FEEDS = (
    ("tb0", "tollbooth", 3, ("Q2", "Q6", "Q8")),
    ("tb1", "tollbooth", 11, ("Q1", "Q5")),
    ("tb2", "tollbooth", 7, ("Q3", "Q9")),
    ("vb0", "volleyball", 3, ("Q12", "Q13")),
)


def _run_ms(ctx, obs=None, frames=32):
    from repro.data import TollBoothStream, VolleyballStream
    from repro.queries import get_query
    from repro.scheduler import Feed, MultiStreamRuntime
    from repro.semantic import GateConfig, SemanticGate

    if obs is not None:
        ctx = dataclasses.replace(ctx, obs=obs)
    feeds = []
    for name, ds, seed, qids in _FEEDS:
        stream = TollBoothStream(seed=seed) if ds == "tollbooth" \
            else VolleyballStream(seed=seed)
        feeds.append(Feed(name, stream,
                          [get_query(q).naive_plan() for q in qids]))
    ms = MultiStreamRuntime(feeds, ctx, micro_batch=16,
                            gate=SemanticGate(GateConfig(threshold=0.06)))
    return ms.run(frames)


def test_observed_serving_bitwise_identical_and_traces_lifecycle(ctx):
    base = _run_ms(ctx)                  # NULL_OBS default
    obs = Observability(slo_target_ms=10_000.0)
    traced = _run_ms(ctx, obs=obs)
    for name, _, _, qids in _FEEDS:
        for q in qids:
            assert traced.feeds[name].per_query[q].outputs == \
                base.feeds[name].per_query[q].outputs
            assert traced.feeds[name].per_query[q].window_results == \
                base.feeds[name].per_query[q].window_results
    # the trace carries the lifecycle: >= 6 distinct span phases
    cats = {e["cat"] for e in obs.tracer.events()}
    assert len(cats & set(PHASES)) >= 6, sorted(cats)
    assert {"ingest", "prefix", "gate", "queue", "forward",
            "resume"} <= cats
    # SLO accounting saw every feed and every ingested frame
    assert sorted(obs.slo.feeds()) == sorted(f[0] for f in _FEEDS)
    for name, _, _, _ in _FEEDS:
        r = obs.slo.row(name)
        assert r["frames"] == 32 and r["p50_ms"] > 0
        assert r["stale_p50_ms"] >= 0
    # unified surfaces: server stats landed in the registry
    assert obs.metrics.counter("server/forwards").value == \
        traced.server_stats["forwards"]
    assert obs.metrics.gauge("run/wall_s").value > 0


def test_metrics_only_mode_records_without_tracing(ctx):
    obs = Observability(tracer=NULL_TRACER, slo_target_ms=10_000.0)
    _run_ms(ctx, obs=obs)
    assert obs.tracer.events() == []     # no spans recorded...
    assert obs.slo.combined()["frames"] == 32 * len(_FEEDS)   # ...but SLO is
    assert obs.metrics.histogram("forward_ms").count > 0


def test_server_stats_gauges_truthful_across_reset(ctx):
    # satellite fix: queue_depth / inflight are recomputed-on-read gauges,
    # not frozen counters — reset_stats() must not leave stale values
    from repro.data import TollBoothStream
    from repro.scheduler import SharedExtractServer

    srv = SharedExtractServer(ctx, max_batch=4, max_inflight=2)
    frames = TollBoothStream(seed=3).batch(4)[0].astype(np.float32)
    for _ in range(3):
        srv.submit("big", frames, feed="a")
    assert srv.stats["queue_depth"] == 3 and srv.stats["inflight"] == 0
    srv.dispatch()
    assert srv.stats["queue_depth"] == 1 and srv.stats["inflight"] == 2
    srv.reset_stats()
    # the gauges still reflect live state, not the fresh-stats zeros
    assert srv.stats["queue_depth"] == 1 and srv.stats["inflight"] == 2
    srv.drain()
    assert srv.stats["queue_depth"] == 0 and srv.stats["inflight"] == 0


def test_warmup_histograms_dropped_on_reset(ctx):
    from repro.data import TollBoothStream
    from repro.scheduler import SharedExtractServer

    obs = Observability(tracer=NULL_TRACER)
    srv = SharedExtractServer(ctx, obs=obs)
    frames = TollBoothStream(seed=3).batch(4)[0].astype(np.float32)
    srv.submit("big", frames, feed="a")
    srv.drain()
    assert obs.metrics.histogram("forward_ms").count == 1
    srv.reset_stats()                    # e.g. after warmup
    assert obs.metrics.histogram("forward_ms").count == 0
    assert obs.metrics.histogram("queue_wait_ms/a").count == 0


# ---------------------------------------------------------------------------
# (d) the served path's spans on the profiler's clock
# ---------------------------------------------------------------------------

#: span names the served path writes (prefix/dispatch/forward by prefix)
_SPANS = ("ingest", "queue_wait", "staging", "block", "harvest", "resume",
          "tail")
_SPAN_PREFIXES = ("prefix:", "dispatch[", "forward[")
_CROP = (64, 0, 64, 256)


def _ours(name):
    return name in _SPANS or name.startswith(_SPAN_PREFIXES)


def _prefixed(qid):
    """A catalog plan behind the benchmark's prefix: Skip, then the fused
    crop/downscale/normalize."""
    from repro.queries import get_query
    from repro.streaming.operators import (FusedPreprocessOp, MLLMExtractOp,
                                           SkipOp)
    plan = get_query(qid).naive_plan()
    plan.insert_after_source(SkipOp(amount=3, roi=_CROP, regions=(4, 8)))
    plan.insert_before(MLLMExtractOp, FusedPreprocessOp(crop=_CROP,
                                                        factor=2))
    return plan


def _serve_prefixed(ctx, obs=None, frames=32):
    from repro.data import TollBoothStream, VolleyballStream
    from repro.queries import get_query
    from repro.scheduler import Feed, MultiStreamRuntime

    if obs is not None:
        ctx = dataclasses.replace(ctx, obs=obs)
    feeds = [Feed("tb0", TollBoothStream(seed=5),
                  [_prefixed(q) for q in ("Q2", "Q6")]),
             Feed("vb0", VolleyballStream(seed=5),
                  [get_query("Q12").naive_plan()])]
    ms = MultiStreamRuntime(feeds, ctx, micro_batch=16)
    return ms, ms.run(frames)


def _outputs(res):
    return {(f, q): (r.outputs, r.window_results)
            for f, fr in res.feeds.items() for q, r in fr.per_query.items()}


def _profiled(ctx, trace_dir, obs=None):
    """Serve under a profiler session; the program's host spans from the
    ``.xplane.pb`` as (name, start, end, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        ms, res = _serve_prefixed(ctx, obs)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if _ours(ev.name):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return ms, res, spans


def test_served_spans_reach_the_profiler_trace_with_their_ids(ctx,
                                                              tmp_path):
    base = _outputs(_serve_prefixed(ctx)[1])
    obs = Observability(slo_target_ms=10_000.0)
    ms, res, spans = _profiled(ctx, tmp_path, obs)
    assert _outputs(res) == base          # both sinks on: same answers
    by = {}
    for name, a, b, st in spans:
        by.setdefault(name.split("[")[0] if name.startswith(
            ("dispatch[", "forward[")) else name, []).append((a, b, st))
    skip = "prefix:skip[3,no_car]"
    pre = f"prefix:fused_preprocess[{_CROP},/2]"
    assert {"ingest", skip, pre, "queue_wait", "staging", "dispatch",
            "forward", "block", "harvest", "resume", "tail"} <= set(by)
    assert all(n in _SPANS + ("dispatch", "forward")
               or n.startswith("prefix:") for n in by)
    want = {"ingest": {"feed", "mb", "n"},
            skip: {"feed", "mb", "n", "n_out", "h2d_bytes", "d2h_bytes"},
            pre: {"feed", "mb", "n", "n_out", "h2d_bytes", "d2h_bytes"},
            "queue_wait": {"feed", "mb", "req", "n", "fwd"},
            "staging": {"fwd", "variant", "bucket", "frames", "n",
                        "h2d_bytes"},
            "dispatch": {"fwd", "variant", "bucket", "frames", "n"},
            "block": {"fwd", "n"}, "harvest": {"fwd", "n"},
            "resume": {"feed", "mb", "fwd", "n", "d2h_bytes"},
            "tail": {"feed", "mb", "query", "n"}}
    for name, keys in want.items():
        for _, _, st in by[name]:
            assert set(st) == keys, (name, st)
    # the pull of every micro-batch, and Skip's upload of each frame and
    # of its predecessor
    frame = 3 * 128 * 256
    assert sorted(st["mb"] for _, _, st in by["ingest"]
                  if st["feed"] == "tb0") == [0, 16]
    for _, _, st in by[skip]:
        assert st["h2d_bytes"] == 2 * st["n"] * frame
        assert st["d2h_bytes"] == st["n"] * 4 * 8 * 4
    for _, _, st in by[pre]:
        assert st["h2d_bytes"] == st["n"] * frame
        assert st["d2h_bytes"] == st["n_out"] * 3 * 32 * 128 * 4
    # each queue wait ends at the launch that carries it
    launches = {st["fwd"]: (a, b, st) for a, b, st in by["dispatch"]}
    assert len(launches) == len(by["dispatch"])
    carried = {}
    for a, b, st in by["queue_wait"]:
        la, lb, lst = launches[st["fwd"]]
        assert a <= la and b >= lb
        carried[st["fwd"]] = carried.get(st["fwd"], 0) + st["n"]
    assert carried == {f: st["frames"] for f, (_, _, st) in launches.items()}
    for name in ("staging", "block", "harvest"):
        assert {st["fwd"] for _, _, st in by[name]} <= set(launches)
    assert {st["fwd"] for _, _, st in by["harvest"]} == set(launches)
    assert {st["query"] for _, _, st in by["tail"]} == {"Q2", "Q6", "Q12"}
    # the ring buffer holds the same spans, under the same names
    ring = sorted(e["name"] for e in obs.tracer.events()
                  if e["kind"] == "X" and _ours(e["name"]))
    assert ring == sorted(name for name, _, _, _ in spans)
    moved = sum(st.get(k, 0) for _, _, _, st in spans
                for k in ("h2d_bytes", "d2h_bytes"))
    assert obs.metrics.counter("link_bytes/h2d").value + \
        obs.metrics.counter("link_bytes/d2h").value == moved


def test_unobserved_unprofiled_serving_records_no_span(ctx, tmp_path,
                                                        monkeypatch):
    """No session and ``NULL_OBS``: no span object is made and the answers
    are bitwise those of a profiled run; a profiled run under
    ``NULL_OBS`` never takes the device probe."""
    import repro.obs.spans as spans_mod
    made = []

    class Counted(spans_mod.Span):
        __slots__ = ()

        def __init__(self, *a):
            made.append(a[1])
            super().__init__(*a)

    ms, profiled, spans = _profiled(ctx, tmp_path)
    assert spans and ms.server._probe_seq == 0
    monkeypatch.setattr(spans_mod, "Span", Counted)
    _, plain = _serve_prefixed(ctx)
    assert made == []
    assert _outputs(plain) == _outputs(profiled)
