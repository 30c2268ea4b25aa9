"""Due-time latency arithmetic: every result is timed from its frame's
due time, so a stall anywhere on the path moves the tail.  And the knee
that a sweep of feed counts reads from the backlog's trend."""
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import fleet  # noqa: E402
import window  # noqa: E402

MS = 1_000_000
FPS = 25.0                      # 40 ms between frames


class _Sink:
    def __init__(self, log):
        self.log = log


class _Fleet:
    def __init__(self, sources, sinks):
        self.sources, self.sinks = sources, sinks


def _fleet(stall_ms: float = 0.0):
    """One feed, 8 micro-batches of 16 frames, each handed over when its
    last frame is due and answered 5 ms later; batch 3 goes through the
    extract and is ``stall_ms`` late."""
    clock = fleet.Clock()
    clock.t0 = 1_000 * MS
    src = fleet.PacedSource(np.zeros((128, 1, 1, 1), np.uint8), FPS, 0.0,
                            clock)
    log = []
    for k in range(8):
        first = 16 * k
        due_last = int(src.due_ns(first + 15))
        src.pulls.append((due_last, first, 16))
        extract = k % 2 == 1
        t = due_last + 5 * MS + (int(stall_ms * MS) if k == 3 else 0)
        log.append((t, extract, np.arange(first, first + 16)[:2 * extract],
                    0))
    # resumed batches arrive after the synchronous ones pulled later
    log.sort(key=lambda c: (c[1], c[0]))
    return _Fleet({"f": src}, {("f", "q"): _Sink(log)})


def test_each_frame_is_timed_from_its_due_time():
    r = window.results(_fleet(), {})
    lat = np.sort(r["latency_ns"]) / MS
    # within a micro-batch the first frame waited 15 frame times
    assert len(lat) == 128 and r["unanswered"] == 0
    assert np.isclose(lat.min(), 5.0) and np.isclose(lat.max(), 605.0)
    assert np.allclose(r["ingest_lag_ns"], 0)


def test_an_injected_stall_moves_p95():
    base = np.percentile(window.results(_fleet(), {})["latency_ns"], 95)
    stalled = np.percentile(
        window.results(_fleet(400.0), {})["latency_ns"], 95)
    assert stalled - base > 100 * MS


def test_window_results_are_timed_from_their_last_frame():
    f = _fleet()
    sink = f.sinks[("f", "q")]
    t, a, idx, _ = sink.log[-1]
    sink.log[-1] = (t, a, idx, 128)        # two 64-frame windows closed
    lat = window.results(f, {("f", "q"): 64})["latency_ns"]
    assert len(lat) == 130
    src = f.sources["f"]
    assert (t - int(src.due_ns(63))) in set(lat.tolist())
    assert (t - int(src.due_ns(127))) in set(lat.tolist())


def test_calls_map_to_their_micro_batches():
    f = _fleet()
    ks = window.match_calls(f.sources["f"].pulls, f.sinks[("f", "q")].log)
    assert sorted(ks) == list(range(8))


def test_knee_is_the_largest_count_whose_backlog_holds():
    import sweep
    assert sweep.knee({1: -0.1, 2: 3.0, 3: 12.0, 4: 5.0}, 10.0) == 2
    assert sweep.knee({4: 1.0, 2: 40.0}, 10.0) == 0
