"""The readers of the program's spans: the window clips what they read,
the queue wait counts each request once per frame, the k-th extract
module pairs with the k-th launch, the runtime's waits are a union, and
the link bytes are a plain sum over frames ingested."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import layout  # noqa: E402
import spans  # noqa: E402
from spans import Event  # noqa: E402

MS = 1_000_000


def _span(name, a, b, **stats):
    return Event("span", name, a * MS, b * MS, stats)


def _marks(lo=100, hi=1100):
    return [Event("mark", "bench:window_start", lo * MS, lo * MS, {}),
            Event("mark", "bench:window_end", hi * MS, hi * MS, {})]


def _module(name, a, b, plane="/device:TPU:0"):
    return Event("module", name, a * MS, b * MS, {"plane": plane})


def test_window_keeps_what_overlaps_the_markers():
    evs = _marks() + [
        _span("queue_wait", 10, 90, n=4, req=1),       # before: dropped
        _span("ingest", 95, 120, n=16, mb=0),          # straddles lo: kept
        _span("tail", 1090, 1200, mb=0, query="Q2"),   # straddles hi: kept
        _span("resume", 1150, 1160, mb=16),            # after: dropped
        _module("jit__extract(1)", 50, 60),            # before: dropped
        _module("jit__extract(1)", 500, 510),
    ]
    w = spans.window(evs)
    assert (w.lo, w.hi) == (100 * MS, 1100 * MS)
    assert [e.name for e in w.spans] == ["ingest", "tail"]
    assert [e.start for e in w.modules] == [500 * MS]


def test_no_markers_or_no_program_spans_read_nothing():
    """A trace without markers, and one from a program that writes no
    spans (the device ops alone), give every reader None."""
    no_marks = spans.window([_span("ingest", 0, 10, n=16)])
    bare = spans.window(_marks() + [_module("jit__extract(1)", 500, 510)])
    for w in (no_marks, bare):
        assert spans.queue_wait_p95_ms(w) is None
        assert spans.harvest_delay_mean_ms(w) is None
        assert spans.runtime_busy_share(w) is None
        assert spans.link_bytes_per_frame(w) is None


def test_queue_wait_counts_each_request_once_per_frame():
    # 30 frames waited 10 ms, one frame waited 500 ms: per request the
    # p95 would lie near 500 ms, per frame it is 10 ms
    evs = _marks() + [
        _span("queue_wait", 200, 210, n=30, req=1, fwd=1),
        _span("queue_wait", 200, 700, n=1, req=2, fwd=2),
    ]
    assert spans.queue_wait_p95_ms(spans.window(evs)) == pytest.approx(10.0)
    evs[-2] = _span("queue_wait", 200, 210, n=1, req=1, fwd=1)
    assert spans.queue_wait_p95_ms(spans.window(evs)) == pytest.approx(
        np.percentile([10, 500], 95))


def test_kth_extract_module_pairs_with_kth_launch():
    evs = _marks() + [
        _span("dispatch[big]", 200, 201, fwd=7),
        _span("dispatch[big]", 300, 301, fwd=8),
        # other programs, and a plane that runs no forward, do not pair
        _module("jit_frame_diff(3)", 190, 191),
        _module("jit__extract(5)", 100, 110, plane="/device:CUSTOM:0"),
        _module("jit__extract(5)", 202, 250),
        _module("jit__extract(5)", 302, 400),
        # harvests come in any order; fwd 7 waited for a later poll
        _span("harvest", 401, 402, fwd=8),
        _span("harvest", 450, 451, fwd=7),
    ]
    w = spans.window(evs)
    assert spans.harvest_delays_ns(w) == [200 * MS, 1 * MS]
    assert spans.harvest_delay_mean_ms(w) == pytest.approx(100.5)
    # a launch whose module is missing breaks the pairing: read nothing
    w = spans.window(evs[:-4] + evs[-3:])
    assert spans.harvest_delay_mean_ms(w) is None


def test_runtime_busy_share_is_one_minus_the_union_of_waits():
    evs = _marks(0, 1000) + [
        _span("ingest", -50, 100, n=16, mb=0),      # clipped to 0..100
        _span("ingest", 300, 500, n=16, mb=16),
        _span("block", 450, 600, fwd=1),            # overlaps: 300..600
        _span("block", 550, 580, fwd=2),            # inside the union
        _span("prefix:skip[25,no_car]", 100, 300, n=16),   # host work
    ]
    w = spans.window(evs)
    assert spans.runtime_busy_share(w) == pytest.approx(
        100.0 * (1 - (100 + 300) / 1000))


def test_link_bytes_sum_both_ways_over_frames_ingested():
    evs = _marks() + [
        _span("ingest", 100, 110, n=16, mb=0),
        _span("ingest", 600, 610, n=16, mb=16),
        _span("prefix:skip[25,no_car]", 110, 111, n=16,
              h2d_bytes=3_000_000, d2h_bytes=2048),
        _span("prefix:fused_preprocess", 111, 112, n=12,
              h2d_bytes=1_000, d2h_bytes=500),
        _span("staging", 120, 121, fwd=1, h2d_bytes=40_000),
        _span("resume", 130, 131, fwd=1, d2h_bytes=96),
        _span("tail", 131, 132, query="Q2"),
    ]
    w = spans.window(evs)
    total = 3_000_000 + 2048 + 1_000 + 500 + 40_000 + 96
    assert spans.link_bytes_per_frame(w) == pytest.approx(total / 32)


def test_metric_files_read_the_run_window():
    evs = _marks() + [_span("ingest", 100, 200, n=16, mb=0),
                      _span("queue_wait", 150, 250, n=16, req=1, fwd=1)]
    run = {"program_spans": spans.window(evs)}
    read = {m: layout.metric_reader(m).read(run) for m in (
        "extract_queue_wait_p95_ms", "extract_queue_wait_p95_ms.over",
        "runtime_busy_share", "runtime_busy_share.over",
        "link_bytes_per_frame", "harvest_delay_mean_ms")}
    assert read["extract_queue_wait_p95_ms"] == pytest.approx(100.0)
    assert read["extract_queue_wait_p95_ms.over"] == pytest.approx(100.0)
    assert read["runtime_busy_share"] == pytest.approx(90.0)
    assert read["runtime_busy_share.over"] == pytest.approx(90.0)
    assert read["link_bytes_per_frame"] == 0.0
    assert read["harvest_delay_mean_ms"] is None


def test_events_reads_spans_and_stats_from_a_profiler_trace(tmp_path):
    """The program's spans as the profiler writes them (here on the CPU,
    which has no device plane): names, ids and counts come back, other
    host events do not."""
    import jax
    from repro.obs import NULL_OBS
    from repro.obs.spans import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window_start"):
            pass
        qw = span(NULL_OBS, "queue_wait", "queue", "feed:a", n=3,
                  feed="a", req=1)
        with span(NULL_OBS, "prefix:skip[25,no_car]", "prefix", "feed:a",
                  n=16, feed="a", mb=0) as s:
            s.set(n_out=3, h2d_bytes=100, d2h_bytes=10)
        with jax.profiler.TraceAnnotation("not_ours"):
            pass
        qw.close(fwd=1, mb=0)
        with jax.profiler.TraceAnnotation("bench:window_end"):
            pass
    finally:
        jax.profiler.stop_trace()
    import trace_reduce
    w = spans.window(spans.events(trace_reduce.find_xplane(str(tmp_path))))
    got = {e.name: e.stats for e in w.spans}
    assert got == {
        "queue_wait": {"n": 3, "feed": "a", "req": 1, "fwd": 1, "mb": 0},
        "prefix:skip[25,no_car]": {"n": 16, "feed": "a", "mb": 0,
                                   "n_out": 3, "h2d_bytes": 100,
                                   "d2h_bytes": 10}}
    assert spans.link_bytes_per_frame(w) is None      # nothing ingested
