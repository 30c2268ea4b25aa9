"""Cross-stream shared-MLLM serving tests.

Covers the scheduler subsystem's contract: (a) the SharingTreePlanner
groups plans by signature-prefix subsets — including workloads whose
*global* common prefix is empty — under a cost model that can also refuse
to share; (b) the SharedExtractServer coalesces cross-stream requests into
shape-bucketed batched forwards whose per-row results match the op's solo
path bitwise; (c) the MultiStreamRuntime serves K feeds with strictly
fewer forwards than independent execution while every query's outputs stay
bitwise identical — plus a hypothesis property test over random catalog
subsets (the conventions of ``test_properties.py``).
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.core.multiquery import share_key
from repro.data import TollBoothStream, VolleyballStream
from repro.queries import QUERIES, get_query
from repro.scheduler import (
    Feed,
    MultiStreamRuntime,
    SharedExtractServer,
    SharingTreePlanner,
)
from repro.streaming.operators import (
    MLLMExtractOp,
    OpContext,
    SinkOp,
    SkipOp,
    SourceOp,
)
from repro.streaming.plan import Plan
from repro.streaming.runtime import StreamRuntime


@pytest.fixture(scope="module")
def ctx(stream_ctx):
    # the session-scoped model stack from conftest.py (trained once)
    return stream_ctx


def _skip_plan(qid, amount=3):
    """A catalog plan with a Skip in front — a divergent signature prefix."""
    q = get_query(qid)
    ops = [SourceOp(stream_name=q.dataset), SkipOp(amount=amount),
           MLLMExtractOp(tasks=q.tasks, model="big")]
    ops += q.tail()
    ops.append(SinkOp())
    return Plan(ops, query=f"{qid}s")


def _indep(qid, ctx, stream, n):
    rt = StreamRuntime(get_query(qid).naive_plan(), ctx, micro_batch=16)
    return rt.run(stream, n)


# ---------------------------------------------------------------------------
# (a) sharing-tree planner (model-free)
# ---------------------------------------------------------------------------

def test_share_key_groups_by_prefix_and_merge_identity():
    assert share_key(get_query("Q2").naive_plan()) == \
        share_key(get_query("Q8").naive_plan())          # same model, mergeable
    assert share_key(get_query("Q2").naive_plan()) != \
        share_key(_skip_plan("Q2"))                      # Skip diverges
    assert share_key(get_query("Q2").naive_plan()) != \
        share_key(get_query("Q12").naive_plan())         # different stream


def test_planner_shares_subsets_when_global_prefix_empty():
    # tollbooth + volleyball sources: no op is common to all four plans,
    # yet each per-stream pair still factors into a shared group
    plans = [get_query(q).naive_plan() for q in ("Q2", "Q6", "Q12", "Q13")]
    assert plans[0].common_prefix(plans[2]) == 0         # truly empty
    forest = SharingTreePlanner().plan(plans)
    assert set(forest.streams) == {"tollbooth", "volleyball"}
    by_stream = {s: sorted(g.execution.queries for g in gs)
                 for s, gs in forest.streams.items()}
    assert by_stream["tollbooth"] == [["Q2", "Q6"]]
    assert by_stream["volleyball"] == [["Q12", "Q13"]]
    assert all(g.is_shared and g.saving_us > 0 for g in forest.groups())
    assert forest.n_queries == 4
    assert "global common prefix is empty" in " ".join(forest.notes)


def test_planner_splits_divergent_prefixes_within_one_stream():
    # Q2/Q6 share a plain extract; Q5s/Q9s share a Skip-prefixed one; the
    # global prefix within the stream is just the source (worthless), so
    # the tree holds two separately-shared subsets
    plans = [get_query("Q2").naive_plan(), get_query("Q6").naive_plan(),
             _skip_plan("Q5"), _skip_plan("Q9")]
    forest = SharingTreePlanner().plan(plans)
    groups = forest.streams["tollbooth"]
    assert sorted(g.execution.queries for g in groups) == \
        [["Q2", "Q6"], ["Q5s", "Q9s"]]
    skip_group = next(g for g in groups if g.execution.queries[0] == "Q5s")
    assert any(isinstance(op, SkipOp) for op in skip_group.execution.prefix)
    assert forest.describe().count("shared") == 2


def test_planner_cost_model_can_refuse_to_share():
    plans = [get_query("Q2").naive_plan(), get_query("Q6").naive_plan()]
    forest = SharingTreePlanner(min_saving_us=1e9).plan(plans)
    groups = forest.streams["tollbooth"]
    assert [g.n_queries for g in groups] == [1, 1]
    assert not any(g.is_shared for g in groups)
    assert any("-> independent" in n for n in forest.notes)


def test_planner_singleton_and_mixed_models():
    # different physical models never share an extract: separate groups
    p_big = get_query("Q2").naive_plan()
    p_small = get_query("Q6").naive_plan()
    p_small.ops[1] = MLLMExtractOp(tasks=("present", "color"), model="small")
    forest = SharingTreePlanner().plan([p_big, p_small])
    assert [g.n_queries for g in forest.streams["tollbooth"]] == [1, 1]


# ---------------------------------------------------------------------------
# (b) shared extract server
# ---------------------------------------------------------------------------

def test_server_backpressure_accounting_model_free():
    # submit/pending bookkeeping needs no models — drain is never called
    srv = SharedExtractServer(OpContext(), max_batch=32)
    f = np.zeros((5, 3, 8, 8), np.float32)
    srv.submit("big", f, feed="a")
    srv.submit("big", f, feed="a")
    srv.submit("small", f, feed="b")
    assert srv.pending_requests() == 3
    assert srv.pending_requests("a") == 2
    assert srv.pending_frames() == 15 and srv.pending_frames("b") == 5
    with pytest.raises(AssertionError):
        srv.submit("adaptive", f)        # caller must resolve the variant
    with pytest.raises(AssertionError):
        srv.submit("big", np.zeros((0, 3, 8, 8), np.float32))


def test_server_coalesces_and_matches_solo_path(ctx):
    srv = SharedExtractServer(ctx, max_batch=64)
    s1, s2 = TollBoothStream(seed=3), TollBoothStream(seed=11)
    f1, _ = s1.batch(5)
    f2, _ = s2.batch(9)
    r1 = srv.submit("big", f1.astype(np.float32), feed="a")
    r2 = srv.submit("big", f2.astype(np.float32), feed="b")
    assert not r1.done
    assert srv.drain() == 1              # one coalesced forward for both
    assert r1.done and r2.done
    assert srv.stats["coalesced_batches"] == 1
    assert srv.stats["frames"] == 14 and srv.stats["padded_frames"] == 2

    # solo path: the op's own jitted program on each stream separately
    for frames, req in ((f1, r1), (f2, r2)):
        op = MLLMExtractOp(tasks=("present", "color", "plate"), model="big")
        op.open(ctx)
        out = op.process({"frames": frames.astype(np.float32),
                          "idx": np.arange(frames.shape[0])})
        for task in ("present", "color", "plate"):
            assert np.array_equal(out["attrs"][task], req.result[task])


def test_server_dispatch_poll_protocol_and_inflight_accounting(ctx):
    # dispatch() launches async forwards up to max_inflight and returns
    # immediately; poll()/wait() retire them; the running pending counters
    # drop at dispatch (they track queued-not-dispatched work)
    srv = SharedExtractServer(ctx, max_batch=4, max_inflight=2)
    frames = TollBoothStream(seed=3).batch(4)[0].astype(np.float32)
    reqs = [srv.submit("big", frames, feed="a") for _ in range(3)]
    assert srv.pending_requests() == 3 and srv.pending_frames() == 12
    launched = srv.dispatch()
    assert launched == 2                 # max_inflight caps dispatch-ahead
    assert srv.inflight == 2
    assert srv.pending_requests() == 1 and srv.pending_frames() == 4
    assert reqs[2].result is None        # still queued
    assert srv.wait() >= 1               # blocks for the oldest forward
    assert reqs[0].done
    assert srv.drain() >= 1              # runs the remaining request
    assert all(r.done for r in reqs)
    assert srv.inflight == 0 and srv.pending_requests() == 0
    assert srv.stats["forwards"] == 3
    assert srv.stats["dispatches"] >= 2
    assert srv.stats["max_inflight_seen"] == 2
    # exact-fit single requests skip the staging copy entirely
    assert srv.stats["staging_skipped"] == 3
    # lazy materialization: all three requests saw identical frames
    for task in ("present", "color", "plate"):
        assert np.array_equal(reqs[0].result[task], reqs[1].result[task])
        assert np.array_equal(reqs[0].result[task], reqs[2].result[task])


def test_server_staging_buffers_reused_without_stale_leakage(ctx):
    srv = SharedExtractServer(ctx, max_batch=8, max_inflight=1)
    s = TollBoothStream(seed=5)
    f1 = s.batch(6)[0].astype(np.float32)     # bucket 8 -> staged + padded
    f2 = s.batch(6)[0].astype(np.float32)
    srv.submit("big", f1)
    srv.drain()
    assert srv.stats["staging_allocated"] == 1
    assert srv.stats["staging_reused"] == 0
    r2 = srv.submit("big", f2)                # same bucket: reuses buffer
    srv.drain()
    assert srv.stats["staging_allocated"] == 1
    assert srv.stats["staging_reused"] == 1
    # a reused (stale) staging buffer must not perturb results: rows match
    # the op's solo path bitwise (padding rows re-zeroed on reuse)
    op = MLLMExtractOp(tasks=("present", "color", "plate"), model="big")
    op.open(ctx)
    out = op.process({"frames": f2, "idx": np.arange(6)})
    for task in ("present", "color", "plate"):
        assert np.array_equal(out["attrs"][task], r2.result[task])
    # an exactly-full request bypasses staging
    f8 = s.batch(8)[0].astype(np.float32)
    srv.submit("big", f8)
    srv.drain()
    assert srv.stats["staging_skipped"] == 1
    assert srv.stats["staging_allocated"] == 1


def test_server_dispatch_defers_partial_buckets_while_device_fed(ctx):
    # a padded partial chunk is deferred while a forward is in flight (it
    # usually grows into a full bucket by the next dispatch), but launches
    # when the device would otherwise idle
    srv = SharedExtractServer(ctx, max_batch=8, max_inflight=2)
    s = TollBoothStream(seed=7)
    full = s.batch(8)[0].astype(np.float32)   # bucket 8: full
    part = s.batch(6)[0].astype(np.float32)   # bucket 8: padded partial
    srv.submit("big", full)
    srv.submit("big", part)
    assert srv.dispatch() == 1                # full launches, partial waits
    assert srv.pending_requests() == 1
    srv.drain()                               # barrier flushes the partial
    assert srv.stats["forwards"] == 2
    # with nothing in flight, a lone partial launches immediately
    srv.submit("big", part)
    assert srv.dispatch() == 1
    srv.drain()
    # budget bounds a single dispatch call
    srv.submit("big", full)
    srv.submit("big", full)
    assert srv.dispatch(budget=1) == 1
    assert srv.pending_requests() == 1
    srv.drain()
    # the deferral is bounded: a partial whose bucket never fills launches
    # after MAX_PARTIAL_DEFERS dispatch calls even while the device is fed
    srv.submit("big", full)
    srv.submit("big", part)
    assert srv.dispatch() == 1                # full in flight, partial deferred
    for _ in range(srv.MAX_PARTIAL_DEFERS - 1):
        assert srv.dispatch() == 0            # still deferred, counted
    assert srv.dispatch() == 1                # overdue: launches despite inflight
    srv.drain()


def test_server_buckets_by_shape_and_respects_max_batch(ctx):
    srv = SharedExtractServer(ctx, max_batch=8)
    full, _ = TollBoothStream(seed=1).batch(6)
    crop = full[:, :, 64:128, :]         # different (C,H,W): its own bucket
    srv.submit("big", full.astype(np.float32))
    srv.submit("big", crop.astype(np.float32))
    assert srv.drain() == 2              # shape buckets never mix
    # max_batch splits one variant+shape group into several forwards
    srv.reset_stats()
    for _ in range(3):
        srv.submit("big", full.astype(np.float32))
    srv.drain()
    assert srv.stats["forwards"] == 3    # 6+6 > 8 -> no 2-request chunk fits
    assert srv.stats["frames"] == 18


def test_cheap_color_and_detect_normalize_per_frame(ctx):
    # raw-vs-normalized is a per-frame decision (the make_extract_fn
    # convention): a mixed-stage batch must score each row exactly as a
    # uniform batch of that row's stage would
    from repro.streaming.operators import CheapColorFilterOp, DetectOp

    raw = TollBoothStream(seed=1).batch(4)[0].astype(np.float32)
    normed = (raw / 255.0 - 0.5) / 0.25
    mixed = np.concatenate([raw[:2], normed[2:]], axis=0)

    color = CheapColorFilterOp(color="red")
    color.open(ctx)
    import jax.numpy as jnp
    got = np.asarray(color._frac(jnp.asarray(mixed)))
    assert np.array_equal(got[:2],
                          np.asarray(color._frac(jnp.asarray(raw)))[:2])
    assert np.array_equal(got[2:],
                          np.asarray(color._frac(jnp.asarray(normed)))[2:])

    det = DetectOp()
    det.open(ctx)
    got = np.asarray(det._run(jnp.asarray(mixed)))
    assert np.array_equal(got[:2],
                          np.asarray(det._run(jnp.asarray(raw)))[:2])
    assert np.array_equal(got[2:],
                          np.asarray(det._run(jnp.asarray(normed)))[2:])


# ---------------------------------------------------------------------------
# (c) multi-stream runtime: bitwise equivalence + fewer forwards
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multistream_matches_independent_bitwise(ctx):
    feeds = [
        Feed("tb0", TollBoothStream(seed=42),
             [get_query(q).naive_plan() for q in ("Q2", "Q6")]),
        Feed("tb1", TollBoothStream(seed=7),
             [get_query("Q8").naive_plan()]),
        Feed("vb0", VolleyballStream(seed=5),
             [get_query(q).naive_plan() for q in ("Q12", "Q13")]),
    ]
    ms = MultiStreamRuntime(feeds, ctx, micro_batch=16)
    res = ms.run(64)
    assert res.n_feeds == 3 and res.n_queries == 5

    makers = {"tb0": lambda: TollBoothStream(seed=42),
              "tb1": lambda: TollBoothStream(seed=7),
              "vb0": lambda: VolleyballStream(seed=5)}
    indep_forwards = 0
    for fname, qids in (("tb0", ("Q2", "Q6")), ("tb1", ("Q8",)),
                        ("vb0", ("Q12", "Q13"))):
        for qid in qids:
            plan = get_query(qid).naive_plan()
            rt = StreamRuntime(plan, ctx, micro_batch=16)
            ind = rt.run(makers[fname](), 64)
            indep_forwards += sum(op.forwards for op in plan.ops
                                  if isinstance(op, MLLMExtractOp))
            shared_q = res.feeds[fname].per_query[qid]
            assert shared_q.outputs == ind.outputs
            assert shared_q.window_results == ind.window_results
            assert get_query(qid).evaluate(shared_q) == \
                get_query(qid).evaluate(ind)
    # the serving claim: coalescing makes forwards strictly cheaper than
    # the sum of independent runs (and even than one forward per feed
    # micro-batch: 3 feeds * 4 micro-batches = 12)
    assert res.server_stats["forwards"] < indep_forwards
    assert res.server_stats["forwards"] < 12
    assert res.server_stats["coalesced_batches"] >= 1
    # model load counts union extracts once per feed frame
    assert res.mllm_frames == 3 * 64


@pytest.mark.slow
def test_pipelined_serving_matches_synchronous_drain(ctx):
    # the pipelined dispatch-ahead loop (default) and the lock-step
    # barrier drain produce bitwise-identical per-query results; the
    # pipelined run actually overlaps (>= 2 in-flight forwards seen)
    def feeds():
        return [
            Feed("tb0", TollBoothStream(seed=42),
                 [get_query(q).naive_plan() for q in ("Q2", "Q6")]),
            Feed("tb1", TollBoothStream(seed=7),
                 [get_query("Q8").naive_plan()]),
            Feed("tb2", TollBoothStream(seed=11),
                 [get_query("Q1").naive_plan()]),
            Feed("vb0", VolleyballStream(seed=5),
                 [get_query(q).naive_plan() for q in ("Q12", "Q13")]),
        ]

    sync = MultiStreamRuntime(feeds(), ctx, micro_batch=16,
                              pipelined=False).run(48)
    pipe = MultiStreamRuntime(feeds(), ctx, micro_batch=16).run(48)
    for fname in ("tb0", "tb1", "tb2", "vb0"):
        for qid, sq in sync.feeds[fname].per_query.items():
            pq = pipe.feeds[fname].per_query[qid]
            assert pq.outputs == sq.outputs
            assert pq.window_results == sq.window_results
    assert pipe.mllm_frames == sync.mllm_frames
    assert pipe.server_stats["max_inflight_seen"] >= 2
    assert pipe.server_stats["dispatches"] >= 1


@pytest.mark.slow
def test_multistream_run_is_repeatable(ctx):
    # warmup=1 rewinds streams and resets ops/sinks/accumulators: a second
    # run() is a fresh measurement, not an accumulation over the first
    feeds = [Feed("a", TollBoothStream(seed=2),
                  [get_query(q).naive_plan() for q in ("Q2", "Q6")])]
    ms = MultiStreamRuntime(feeds, ctx, micro_batch=16)
    r1 = ms.run(32)
    r2 = ms.run(32)
    for q in ("Q2", "Q6"):
        assert r2.feeds["a"].per_query[q].outputs == \
            r1.feeds["a"].per_query[q].outputs
        assert r2.feeds["a"].per_query[q].window_results == \
            r1.feeds["a"].per_query[q].window_results
    assert r2.mllm_frames == r1.mllm_frames == 32
    assert len(r2.feeds["a"].per_query["Q2"].labels) == 32


@pytest.mark.slow
def test_multistream_heterogeneous_frame_budgets(ctx):
    feeds = [
        Feed("a", TollBoothStream(seed=2), [get_query("Q2").naive_plan()]),
        Feed("b", TollBoothStream(seed=9), [get_query("Q6").naive_plan()]),
    ]
    ms = MultiStreamRuntime(feeds, ctx, micro_batch=16, max_pending=1)
    res = ms.run({"a": 48, "b": 16})
    assert res.feeds["a"].n_frames == 48 and res.feeds["b"].n_frames == 16
    ind_a = _indep("Q2", ctx, TollBoothStream(seed=2), 48)
    ind_b = _indep("Q6", ctx, TollBoothStream(seed=9), 16)
    assert res.feeds["a"].per_query["Q2"].outputs == ind_a.outputs
    assert res.feeds["b"].per_query["Q6"].outputs == ind_b.outputs
    assert res.feeds["b"].per_query["Q6"].window_results == \
        ind_b.window_results


# ---------------------------------------------------------------------------
# the sharing-tree equivalence property (hypothesis drives this over random
# subsets in test_properties.py; here it runs on fixed adversarial subsets
# so the property is exercised even where hypothesis is unavailable)
# ---------------------------------------------------------------------------

PROP_FRAMES = 48


def assert_sharing_tree_equals_independent(ctx, qids, seed,
                                           n_frames=PROP_FRAMES):
    """For ANY subset of the catalog — including mixed tollbooth+volleyball
    subsets whose global common prefix is empty — executing the sharing
    tree over one feed per dataset yields bitwise the outputs of N
    independent runs, and every query lands in exactly one tree group."""
    qids = sorted(qids)
    datasets = sorted({QUERIES[q].dataset for q in qids})

    def make_stream(ds):
        return TollBoothStream(seed=seed) if ds == "tollbooth" \
            else VolleyballStream(seed=seed)

    forest = SharingTreePlanner().plan(
        [get_query(q).naive_plan() for q in qids])
    placed = sorted(q for g in forest.groups() for q in g.execution.queries)
    assert placed == qids                 # exactly-once partition

    feeds = [Feed(ds, make_stream(ds),
                  [get_query(q).naive_plan() for q in qids
                   if QUERIES[q].dataset == ds])
             for ds in datasets]
    ms = MultiStreamRuntime(feeds, ctx, micro_batch=16)
    res = ms.run(n_frames)
    for q in qids:
        ds = QUERIES[q].dataset
        ind = _indep(q, ctx, make_stream(ds), n_frames)
        shared_q = res.feeds[ds].per_query[q]
        assert shared_q.outputs == ind.outputs
        assert shared_q.window_results == ind.window_results


@pytest.mark.slow
@pytest.mark.parametrize("qids,seed", [
    (("Q2", "Q12"), 101),                # no global prefix, two singletons
    (("Q3", "Q7", "Q9", "Q13"), 77),     # plate trio shares; Q13 alone
])
def test_sharing_tree_equivalence_fixed_subsets(ctx, qids, seed):
    assert_sharing_tree_equals_independent(ctx, qids, seed)


# ---------------------------------------------------------------------------
# (d) the source hand-over: each feed pulled on a thread of its own
# ---------------------------------------------------------------------------

class _PacedStream:
    """Frames rendered up front and handed over at camera rate:
    ``batch(n)`` sleeps until the last of the ``n`` frames is due, on a
    clock shared by the feeds of a run that starts at the first paced
    pull.  Before the first ``reset()`` (the runtime's warm-up) it hands
    frames over at once.  ``handed`` logs every paced hand-over as
    ``(t_ns, first frame, ns after the last frame was due)``."""

    def __init__(self, stream, n, fps, phase_s, clock):
        self.frames, self.labels = stream.batch(n)
        self.fps, self.phase_s, self.clock = fps, phase_s, clock
        self.i = 0
        self.paced = False
        self.handed = []

    def due_ns(self, i):
        return self.clock["t0"] + int((self.phase_s + i / self.fps) * 1e9)

    def batch(self, n):
        i = self.i
        if self.paced:
            self.clock.setdefault("t0", time.perf_counter_ns())
            wait = self.due_ns(i + n - 1) - time.perf_counter_ns()
            if wait > 0:
                time.sleep(wait / 1e9)
            t = time.perf_counter_ns()
            self.handed.append((t, i, t - self.due_ns(i + n - 1)))
        self.i = i + n
        return self.frames[i:i + n], self.labels[i:i + n]

    def reset(self):
        self.i = 0
        self.paced = True


@dataclasses.dataclass
class _StampSink(SinkOp):
    """The sink, logging the clock of every call on a micro-batch (the
    end-of-stream flush carries no frames of the stream's shape); the
    warm-up's calls come before the clock's ``t0``."""

    def __post_init__(self):
        super().__post_init__()
        self.calls = []

    def process(self, batch):
        if batch["frames"].ndim == 4 and batch["frames"].shape[1] > 1:
            self.calls.append(time.perf_counter_ns())
        return super().process(batch)


def _stamped(qid):
    plan = get_query(qid).naive_plan()
    return Plan(plan.ops[:-1] + [_StampSink()], query=qid)


def _calls(ms, feed, clock):
    """Per query, the clock of each sink call of the measured run."""
    return {q: [t for t in tail[-1].calls if t >= clock["t0"]]
            for g in ms.forests[feed].groups()
            for q, tail in zip(g.execution.queries, g.execution.tails)}


def _warm_buckets(ms, shape=(3, 128, 256)):
    """Compile the extract at every bucket a run may coalesce into, so no
    compile stalls the paced window (the runtime's warm-up compiles one
    micro-batch per feed)."""
    for b in (8, 16, 32):
        ms.server.submit("big", np.zeros((b,) + shape, np.uint8))
        ms.server.drain()


def _handover_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("handover:") and t.is_alive()]


@pytest.mark.slow
def test_one_paced_feed_is_answered_before_its_next_micro_batch(ctx):
    # at one feed the loop no longer sleeps in the next pull while a
    # micro-batch waits: each micro-batch's forward launches and its
    # answers reach every sink while the next one is still being filled
    # (8-frame micro-batches every 0.6 s; a CPU forward of 8 takes ~0.1 s).
    # The loop waits idle for every micro-batch, and each wait's ``ingest``
    # span lies on the feed's track
    from repro.obs import Observability

    clock = {}
    src = _PacedStream(TollBoothStream(seed=42), 32, fps=8 / 0.6,
                       phase_s=0.0, clock=clock)
    obs = Observability(slo_target_ms=10_000.0)
    ms = MultiStreamRuntime(
        [Feed("tb0", src, [_stamped(q) for q in ("Q2", "Q6")])],
        dataclasses.replace(ctx, obs=obs), micro_batch=8)
    res = ms.run(32)
    assert [i for _, i, _ in src.handed] == [0, 8, 16, 24]
    for qid, calls in _calls(ms, "tb0", clock).items():
        assert len(calls) == 4, qid
        for k in range(3):
            assert calls[k] < src.handed[k + 1][0], (qid, k)
        ind = StreamRuntime(get_query(qid).naive_plan(), ctx,
                            micro_batch=8).run(TollBoothStream(seed=42), 32)
        assert res.feeds["tb0"].per_query[qid].outputs == ind.outputs
    # one forward per micro-batch: none waited for the next to coalesce
    assert res.server_stats["forwards"] == 4
    ingest = [e for e in obs.tracer.events() if e["name"] == "ingest"]
    assert [(e["track"], e["n"]) for e in ingest] == [("feed:tb0", 8)] * 4
    assert not _handover_threads()


@pytest.mark.slow
def test_two_paced_feeds_are_each_taken_at_their_own_rate(ctx):
    # a fast feed and one at half its rate: the loop takes each as it is
    # handed over, so neither is paced by the other's pulls (pulled in
    # rounds, the fast feed would fall 0.6 s further behind every round);
    # every (frame, query) is answered and the outputs equal independent
    # execution
    clock = {}
    fast = _PacedStream(TollBoothStream(seed=7), 48, fps=8 / 0.6,
                        phase_s=0.05, clock=clock)
    slow = _PacedStream(VolleyballStream(seed=5), 24, fps=8 / 1.2,
                        phase_s=0.35, clock=clock)
    feeds = [Feed("tb", fast, [_stamped(q) for q in ("Q2", "Q8")]),
             Feed("vb", slow, [_stamped(q) for q in ("Q12", "Q13")])]
    ms = MultiStreamRuntime(feeds, ctx, micro_batch=8)
    _warm_buckets(ms)
    res = ms.run({"tb": 48, "vb": 24})
    for src, n in ((fast, 48), (slow, 24)):
        assert [i for _, i, _ in src.handed] == list(range(0, n, 8))
        period = int(8 / src.fps * 1e9)
        assert max(late for _, _, late in src.handed) < period
    for name, make, n in (("tb", lambda: TollBoothStream(seed=7), 48),
                          ("vb", lambda: VolleyballStream(seed=5), 24)):
        for qid, calls in _calls(ms, name, clock).items():
            assert len(calls) == n // 8, qid
            ind = StreamRuntime(get_query(qid).naive_plan(), ctx,
                                micro_batch=8).run(make(), n)
            shared = res.feeds[name].per_query[qid]
            assert shared.outputs == ind.outputs
            assert shared.window_results == ind.window_results
    assert not _handover_threads()


class _Broken(RuntimeError):
    pass


class _FailingStream(TollBoothStream):
    """Raises on the pull of frame ``fail_at`` once the run is under way."""

    def __init__(self, fail_at, **kw):
        super().__init__(**kw)
        self.fail_at = fail_at
        self.paced = False

    def batch(self, n):
        if self.paced and self._index >= self.fail_at:
            raise _Broken(f"source broke at frame {self._index}")
        return super().batch(n)

    def reset(self, seed=None):
        super().reset(seed)
        self.paced = True


@pytest.mark.slow
def test_no_handover_thread_outlives_a_run_that_raises(ctx, monkeypatch):
    from repro.faults import ExtractStallError

    # a source that throws: re-raised on the loop thread, threads joined
    ms = MultiStreamRuntime(
        [Feed("tb0", _FailingStream(32, seed=2),
              [get_query("Q2").naive_plan()]),
         Feed("vb0", VolleyballStream(seed=5),
              [get_query("Q12").naive_plan()])], ctx, micro_batch=16)
    with pytest.raises(_Broken, match="frame 32"):
        ms.run(64)
    assert not _handover_threads()

    # the server's watchdog firing mid-run, while a paced pull sleeps
    clock = {}
    src = _PacedStream(TollBoothStream(seed=42), 64, fps=40.0, phase_s=0.0,
                       clock=clock)
    ms = MultiStreamRuntime(
        [Feed("tb0", src, [get_query("Q2").naive_plan()])], ctx,
        micro_batch=16)
    pumped = []

    def stalled(*a, **kw):
        pumped.append(1)
        raise ExtractStallError("forced")

    monkeypatch.setattr(ms.server, "pump", stalled)
    with pytest.raises(ExtractStallError, match="forced"):
        ms.run(64)
    assert pumped == [1]
    assert not _handover_threads()


def test_handover_hands_every_frame_over_once_in_order():
    # model-free stress: sixteen hand-over threads (more than a small host
    # has cores), the interpreter switching threads as often as it can,
    # the loop taking feeds in a shuffled order — every frame of every
    # feed arrives once, in order, and no thread outlives close()
    import sys

    from repro.scheduler.multistream import _Handover

    class Counting:
        def __init__(self):
            self.i = 0

        def batch(self, n):
            out = np.arange(self.i, self.i + n)
            self.i += n
            return out, [None] * n

    rng = np.random.default_rng(0)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        cond = threading.Condition()
        hos = [_Handover(f"s{k}", Counting(), cond)
               for k in range(16)]
        got = [[] for _ in hos]
        deadline = time.monotonic() + 20
        rounds = 0
        while rounds < 200 and time.monotonic() < deadline:
            for k in rng.permutation(len(hos)):
                hos[k].request(int(rng.integers(1, 5)))
                if rng.random() < 0.7:
                    got[k].append(hos[k].take()[0])
            rounds += 1
        for k, ho in enumerate(hos):
            if ho.pending:
                got[k].append(ho.take()[0])
            ho.close()
    finally:
        sys.setswitchinterval(old)
    assert rounds > 10
    for frames in got:
        seen = np.concatenate(frames)
        assert np.array_equal(seen, np.arange(len(seen)))
    assert not _handover_threads()


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("trip", [
    # a corrupt delivery past the retry budget (nothing pulled ahead)
    ("source", "corrupt", 3),
    # a forward failing past its retry budget, while the feed's next
    # micro-batch is being pulled or waits in the hand-over
    ("forward", "error", 1),
])
def test_paced_feed_under_faults_serves_every_frame_once(ctx, trip):
    # the waiting path under fault injection: a paced feed stalls once,
    # then a fault trips its breaker; it probes, replays from its
    # snapshot and serves the rest of the stream.  Every served answer
    # equals independent execution at the same frame, no frame is
    # answered twice, the frames partition exactly, the healthy paced
    # feed never notices, and no hand-over thread is left
    from repro.faults import FaultInjector, FaultRule

    site, kind, event = trip
    clock = {}
    tb = _PacedStream(TollBoothStream(seed=42), 48, fps=8 / 0.3,
                      phase_s=0.0, clock=clock)
    vb = _PacedStream(VolleyballStream(seed=5), 48, fps=8 / 0.3,
                      phase_s=0.1, clock=clock)
    inj = FaultInjector(seed=11, rules=[
        FaultRule(site="source", kind="stall", feed="tb0", start=1,
                  count=1),
        FaultRule(site=site, kind=kind, feed="tb0", start=event,
                  count=1 if site == "forward" else 2, param=99)])
    ms = MultiStreamRuntime(
        [Feed("tb0", tb, [get_query("Q2").naive_plan()]),
         Feed("vb0", vb, [get_query("Q12").naive_plan()])],
        ctx, micro_batch=8, faults=inj, breaker_cooldown=1)
    res = ms.run(48)
    fired = {(e["feed"], e["kind"], e["event"]) for e in inj.log}
    assert {("tb0", "stall", 1), ("tb0", kind, event)} <= fired
    fr = res.feeds["tb0"]
    assert fr.breaker["trips"] == 1 and fr.breaker["recoveries"] >= 1
    assert fr.served + fr.degraded + fr.dropped == 48
    assert 0 < fr.degraded + fr.dropped <= 24
    for name, make, qid in (("tb0", lambda: TollBoothStream(seed=42), "Q2"),
                            ("vb0", lambda: VolleyballStream(seed=5),
                             "Q12")):
        ind = StreamRuntime(get_query(qid).naive_plan(), ctx,
                            micro_batch=8).run(make(), 48)
        want = {r["idx"]: r for r in ind.outputs}
        got = res.feeds[name].per_query[qid].outputs
        assert len({r["idx"] for r in got}) == len(got)
        assert all(want[r["idx"]] == r for r in got)
    assert res.feeds["vb0"].per_query["Q12"].outputs == \
        StreamRuntime(get_query("Q12").naive_plan(), ctx,
                      micro_batch=8).run(VolleyballStream(seed=5), 48).outputs
    assert res.feeds["vb0"].served == 48
    assert not _handover_threads()
