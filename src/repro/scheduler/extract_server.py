"""Shared MLLM extract server: one model, many feeds — pipelined.

Every ``MLLMExtractOp`` used to own a private jitted program, so K feeds
(and, before multi-query sharing, N queries) each paid their own forward
and their own compilation.  The server inverts the ownership: it holds one
jitted union-task extract program per *physical backbone variant*
(big / small / pruned — the same resolution ``MLLMExtractOp.open`` does,
with "adaptive" resolved by the op's density tracker before submission),
and coalesces extract requests from different streams into batched
forwards.

Coalescing is shape-bucketed and padded: requests whose frames agree on
(C, H, W) — same preprocessing stage — concatenate into one batch, padded
to a power-of-two bucket (the ``serving.engine`` ``_bucket`` idiom) so the
number of distinct compiled shapes stays logarithmic in batch size.
Requests with different frame shapes (a cropped tollbooth feed next to a
full-frame volleyball feed) land in different buckets but still share the
compiled program cache across feeds.

Because ``make_extract_fn`` normalizes per frame and every head is
computed in one forward, each row of a coalesced batch is bitwise
identical to what the op's solo path would have produced — the server
changes *how many* forwards run, never *what* any query observes.

Pipelined serving protocol (dispatch / poll / resume)
-----------------------------------------------------
``submit()`` queues a request.  ``dispatch(budget)`` assembles
shape-bucketed chunks into *reused pre-allocated staging buffers* (no
per-chunk allocation + zero-fill), launches the jitted forwards, and
returns immediately: JAX async dispatch runs the device work in the
background while the caller keeps doing host-side stream work — source
batching, Skip/window ops, tail fan-out.  Predictions stay device-side
behind each ``ExtractRequest`` until ``poll()`` (non-blocking) or
``wait()``/``drain()`` (blocking) observes the forward's completion; the
request then reports ``done``, and materializes its per-task numpy slices
lazily on first ``result`` access — one device→host transfer per chunk,
shared by every request coalesced into it.

``max_inflight`` bounds the number of launched-but-unretired forwards
(default 2 = double buffering), which also bounds staging memory: a
staging buffer returns to the reuse pool as soon as its forward retires.
``drain()`` keeps its original synchronous contract (run everything,
block, return the forward count) and survives as the end-of-run /
checkpoint barrier.

Semantic gating (the cache-consult stage)
-----------------------------------------
With a ``repro.semantic.SemanticGate`` attached (``gate=`` or
``ctx.gate``), ``submit()`` consults the per-feed keyframe cache before
anything is queued: near-duplicate rows are answered from cached extract
outputs and only the admission's *novel* rows (plus its revalidation
hits) enter the dispatch queue — a batch whose every row hits
short-circuits dispatch entirely.  The returned ``GatedExtractRequest``
keeps the ``n``/``done``/``result`` surface, so the runtimes' suspension
protocol is unchanged; a gate with ``threshold=0`` is inert and the
ungated path stays bitwise identical.

Stats: ``forwards`` (jitted invocations), ``dispatches`` (dispatch calls
that launched work), ``max_inflight_seen`` (peak concurrent forwards),
``staging_allocated`` / ``staging_reused`` (buffer-pool misses / hits),
``staging_skipped`` (exact-fit single requests passed straight to the
jitted fn, no copy), the cache tier's ``cache_hits`` / ``cache_misses`` /
``revalidations`` / ``cache_mismatches``, plus the original ``frames`` /
``padded_frames`` / ``requests`` / ``coalesced_batches``.  ``stats`` is a
*cached view*: one dict object for the server's lifetime, updated in
place (never rebuilt per read).  Two entries are *gauges*, not counters:
``queue_depth`` (requests queued, undispatched) and ``inflight``
(forwards launched, unretired) — the view recomputes them from live
state on every read, so they stay truthful across ``reset_stats()``
instead of freezing at whatever the last in-place update wrote.

Observability (``repro.obs``): with an enabled ``Observability``
(``obs=`` or ``ctx.obs``) the server records the device half of every
frame's lifecycle — per-request ``queue_wait`` spans (submit → launch)
and a ``queue_wait_ms/<feed>`` histogram, ``staging`` / ``dispatch``
spans on the ``server`` track, a ``forward[variant]`` span per chunk on
the ``device`` track (launch → observed completion) feeding a
``forward_ms`` histogram, and ``inflight`` / ``queue_depth`` counter
samples — the occupancy timeline that shows whether double buffering
actually overlaps.  Un-observed servers pay only no-op calls.

The observed ``forward`` span is an upper bound on device time — it
includes however long the runtime took to poll the completion — so every
``device_probe_every``-th forward is additionally *probed*: the launch
thread blocks on a one-element sentinel sliced from the output and
records the launch → device-completion interval as a
``forward_device[variant]`` span and ``forward_device_ms`` /
``forward_device_ms/<variant>`` histograms (frames counted in
``forward_device_frames/<variant>``).  Probed device time is what the
cost-model reconciliation (``repro.obs.audit``) trusts; sampling keeps
the probe off the steady-state path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.faults import (
    ExtractFaultError,
    ExtractStallError,
    RetryPolicy,
    resolve_faults,
)
from repro.obs import resolve_obs
from repro.streaming.mllm import make_extract_fn, variant_models
from repro.streaming.operators import OpContext, _bucket_pad


class _InFlightChunk:
    """One launched forward: device-side predictions for a coalesced chunk
    plus the bookkeeping to fulfil its requests and recycle its staging
    buffer once the device retires it."""

    __slots__ = ("preds", "reqs", "buf_key", "buf", "completed", "_np",
                 "t_launch", "variant", "total", "delay_polls")

    def __init__(self, preds, reqs: List["ExtractRequest"],
                 buf_key=None, buf=None):
        self.preds = preds                # device arrays until materialized
        self.reqs = reqs
        self.buf_key = buf_key
        self.buf = buf                    # staging buffer, held until retire
        self.completed = False
        self._np: Optional[Dict[str, np.ndarray]] = None
        self.t_launch = 0                 # obs stamp: forward launch (ns)
        self.variant = ""
        self.total = 0
        #: injected artificial device latency: the chunk's completion is
        #: observed this many ``poll()``s late (clock-free by design)
        self.delay_polls = 0

    def ready(self) -> bool:
        return all(v.is_ready() for v in self.preds.values())

    def block(self) -> None:
        jax.block_until_ready(self.preds)

    def materialize(self) -> Dict[str, np.ndarray]:
        """One device→host transfer for the whole chunk (blocks only if the
        forward is still running); requests slice views out of it."""
        if self._np is None:
            self._np = {k: np.asarray(v) for k, v in self.preds.items()}
            self.preds = {}               # release device references
        return self._np


class GatedExtractRequest:
    """A submitted extract answered (partly or fully) by the semantic
    cache: only the admission's *model rows* entered the server queue
    (``inner``), the rest resolve from cached keyframe outputs.  Presents
    the same ``n``/``done``/``result`` surface as ``ExtractRequest``, so
    continuations and ``settle_fifo`` never distinguish the two."""

    __slots__ = ("variant", "frames", "feed", "adm", "inner")

    def __init__(self, variant: str, frames: np.ndarray, feed: str,
                 adm, inner: Optional["ExtractRequest"]):
        self.variant = variant
        self.frames = frames
        self.feed = feed
        self.adm = adm
        self.inner = inner

    @property
    def n(self) -> int:
        return int(self.frames.shape[0])

    @property
    def dispatched(self) -> bool:
        return self.inner is None or self.inner.dispatched

    @property
    def failed(self) -> bool:
        """The model rows' request exhausted its retry budget."""
        return self.inner is not None and self.inner.failed

    @property
    def done(self) -> bool:
        """The model rows' forward and every cached-row donor completed —
        ``result`` will not block."""
        return self.adm.ready

    @property
    def result(self) -> Optional[Dict[str, np.ndarray]]:
        if not self.done:
            return None
        return self.adm.assemble()


class ExtractRequest:
    """One pending union extract: ``frames`` in, per-task predictions out.

    Lifecycle: queued → dispatched (forward in flight) → ``done`` (forward
    observed complete by ``poll``/``wait``/``drain``) → ``result`` (lazy
    numpy materialization, shared per coalesced chunk, on first access)."""

    __slots__ = ("variant", "frames", "feed", "_chunk", "_offset",
                 "t_submit", "attempts", "isolate", "failed", "not_before",
                 "fault_event")

    def __init__(self, variant: str, frames: np.ndarray, feed: str = ""):
        self.variant = variant            # big | small | pruned
        self.frames = frames              # (n, C, H, W)
        self.feed = feed
        self._chunk: Optional[_InFlightChunk] = None
        self._offset = 0
        self.t_submit = 0                 # obs stamp: enqueue time (ns)
        #: retry accounting: launches attempted / earliest dispatch round
        #: the next attempt is eligible (exponential backoff) / whether a
        #: failed chunk's members must relaunch one-per-chunk so a
        #: poisoned feed's frames never exhaust chunk-mates' budgets
        self.attempts = 0
        self.not_before = 0
        self.isolate = False
        #: terminally failed (retry budget exhausted) — ``result`` raises
        self.failed = False
        #: fault-schedule event index, assigned once at enqueue so every
        #: retry of this request replays the same scheduled fault
        self.fault_event = 0

    @property
    def n(self) -> int:
        return int(self.frames.shape[0])

    @property
    def dispatched(self) -> bool:
        return self._chunk is not None

    @property
    def done(self) -> bool:
        """The forward completed — ``result`` will not block."""
        return self._chunk is not None and self._chunk.completed

    @property
    def result(self) -> Optional[Dict[str, np.ndarray]]:
        if self.failed:
            raise ExtractFaultError(
                f"extract request feed={self.feed!r} "
                f"variant={self.variant} n={self.n} failed after "
                f"{self.attempts} attempts")
        if not self.done:
            return None
        preds = self._chunk.materialize()
        return {k: v[self._offset:self._offset + self.n]
                for k, v in preds.items()}


# ---------------------------------------------------------------------------
# suspension-queue settling (shared by MultiStreamRuntime's feed queues and
# MultiQueryRuntime's pipelined path — one implementation of the resume-
# order invariant, so the two executors cannot drift)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PendingResume:
    """A suspended micro-batch: resumes past ``op_index`` once ``req``'s
    forward completes."""

    op_index: int
    batch: Any
    req: Union["ExtractRequest", "GatedExtractRequest"]
    n: int


def settle_fifo(pendings: List[Tuple[Any, PendingResume]],
                resume: Callable[[Any, PendingResume], Optional[PendingResume]],
                ) -> Tuple[List[Tuple[Any, PendingResume]], int]:
    """Resume, in FIFO order, every fulfilled continuation whose *lane* has
    no earlier outstanding one.

    Stateful post-extract ops must observe batches in stream order per
    lane (a lane = one sharing-group executor; lanes are independent), so
    a completed continuation stays parked while an older one of the same
    lane is still in flight.  ``resume(lane, pending)`` returns a
    re-suspension or None; re-suspensions keep their queue position.
    Returns ``(new queue, number resumed)``."""
    out: List[Tuple[Any, PendingResume]] = []
    blocked: set = set()
    resumed = 0
    for lane, p in pendings:
        if id(lane) not in blocked and p.req.done:
            nxt = resume(lane, p)
            resumed += 1
            if nxt is not None:
                out.append((lane, nxt))
                blocked.add(id(lane))
        else:
            out.append((lane, p))
            blocked.add(id(lane))
    return out, resumed


class SharedExtractServer:
    """Coalesces union-task extract requests across feeds into batched
    forwards per (variant, frame-shape) bucket, pipelined.

    ``max_batch`` bounds a single coalesced forward (memory / latency
    ceiling); ``max_inflight`` bounds dispatched-but-unretired forwards
    (double buffering by default)."""

    VARIANTS = ("big", "small", "pruned")

    #: consecutive dispatch calls a padded partial chunk may be deferred
    #: before it launches anyway — bounds the latency of a feed whose
    #: chunks never fill their bucket while other feeds keep the device
    #: busy (continuous-traffic starvation guard)
    MAX_PARTIAL_DEFERS = 2

    def __init__(self, ctx: OpContext, max_batch: int = 64,
                 max_inflight: int = 2, gate=None, obs=None,
                 faults=None, retry: Optional[RetryPolicy] = None,
                 drain_timeout_s: float = 120.0,
                 device_probe_every: int = 8):
        assert max_batch >= 1 and max_inflight >= 1
        self.ctx = ctx
        self.max_batch = max_batch
        self.max_inflight = max_inflight
        #: optional ``repro.semantic.SemanticGate``: the cache-consult
        #: stage in front of dispatch.  Defaults to the context's gate so
        #: one configuration point covers the solo and the served path.
        self.gate = gate if gate is not None else ctx.gate
        #: observability handle (explicit arg > ctx.obs > inert NULL_OBS)
        self.obs = resolve_obs(obs, getattr(ctx, "obs", None))
        if self.gate is not None:
            self.gate.obs = self.obs
        #: fault injection (explicit arg > ctx.faults > inert NULL_FAULTS)
        self.faults = resolve_faults(faults, getattr(ctx, "faults", None))
        #: bounded-retry policy for failed forwards (see repro.faults)
        self.retry = retry if retry is not None else RetryPolicy()
        #: watchdog deadline: ``wait()``/``drain()`` raise a descriptive
        #: ``ExtractStallError`` naming the stuck chunk/bucket after this
        #: many seconds without progress (a launch or a retirement resets
        #: it; a long first compile blocks *inside* the forward and so
        #: never trips it)
        self.drain_timeout_s = drain_timeout_s
        #: device-accurate forward timing: every Nth launched forward is
        #: *probed* — a ``block_until_ready`` on a one-element sentinel
        #: sliced from the forward output, timed launch → device
        #: completion, so the measurement excludes the poll interval the
        #: observed ``forward`` span necessarily includes.  Sampling keeps
        #: steady-state serving free (a probe serializes the host for that
        #: one forward); 0 disables probing entirely.  Active only with an
        #: enabled ``Observability`` — the un-observed path never probes.
        self.device_probe_every = device_probe_every
        self._probe_seq = 0                   # forwards since last probe
        self._dispatch_seq = 0                # retry backoff clock (rounds)
        self._defers: Dict[Tuple, int] = {}   # bucket key -> deferred calls
        self._fns: Dict[str, Any] = {}
        self._queue: List[ExtractRequest] = []
        self._inflight: List[_InFlightChunk] = []
        #: staging-buffer pool: (bucket, shape, dtype) -> free buffers
        self._staging: Dict[Tuple, List[np.ndarray]] = {}
        # running pending counters — submit/dispatch keep them exact, so
        # the per-feed backpressure checks each scheduling round are O(1)
        # instead of O(queue)
        self._pending_reqs: Dict[str, int] = {}
        self._pending_frames: Dict[str, int] = {}
        self._pending_reqs_total = 0
        self._pending_frames_total = 0
        self._stats = self._fresh_stats()

    @staticmethod
    def _fresh_stats() -> Dict[str, int]:
        return {"forwards": 0, "frames": 0, "padded_frames": 0,
                "requests": 0, "coalesced_batches": 0,
                "dispatches": 0, "max_inflight_seen": 0,
                "staging_allocated": 0, "staging_reused": 0,
                "staging_skipped": 0,
                # fault-tolerance tier: injected/observed forward faults,
                # relaunch decisions, terminal failures, latency injections
                "forward_faults": 0, "retries": 0, "retry_exhausted": 0,
                "latency_faults": 0,
                # live gauges (recomputed on read, see ``stats``)
                "queue_depth": 0, "inflight": 0,
                # cache tier (mirrors the gate's counters; stays 0 ungated)
                "cache_hits": 0, "cache_misses": 0,
                "revalidations": 0, "cache_mismatches": 0}

    @property
    def stats(self) -> Dict[str, int]:
        """The server's counters as a *cached view*: one dict object for
        the server's lifetime, updated in place (it used to be rebound on
        every reset, so holders diffed against a dead dict).  Reading the
        view syncs the semantic-cache tier's counters
        (hits/misses/revalidations/mismatches) into it and recomputes the
        ``queue_depth`` / ``inflight`` gauges from live state — they stay
        truthful across ``reset_stats()``."""
        if self.gate is not None:
            self._stats.update(self.gate.counters)
        self._stats["queue_depth"] = self._pending_reqs_total
        self._stats["inflight"] = len(self._inflight)
        return self._stats

    def reset_stats(self) -> None:
        """Drop accounting (e.g. after warmup) without dropping the
        compiled program cache, the staging pool or the semantic cache's
        keyframes — reusing those across the measured run is the whole
        point of warmup.  Warmup-polluted latency histograms (queue-wait,
        forward: compile time would swamp the measured p99) drop with it;
        gauges recompute on the next ``stats`` read."""
        self._stats.update(self._fresh_stats())
        if self.gate is not None:
            self.gate.reset_counters()
        if self.obs.enabled:
            self.obs.metrics.drop("queue_wait_ms")
            self.obs.metrics.drop("forward_ms")
            self.obs.metrics.drop("forward_device_ms")
            self.obs.metrics.drop("forward_device_frames")
            # realign probe sampling so the first *measured* forward is
            # probed — a short post-warmup run must not land between
            # sample points and finish with zero device measurements
            self._probe_seq = 0

    # ------------------------------------------------------------------
    def _fn(self, variant: str):
        if variant not in self._fns:
            mllm, params = variant_models(self.ctx)[variant]
            assert mllm is not None, f"ctx has no model for {variant!r}"
            self._fns[variant] = make_extract_fn(mllm, params)
        return self._fns[variant]

    # ------------------------------------------------------------------
    def submit(self, variant: str, frames: np.ndarray,
               feed: str = "", sig=None) -> Union[ExtractRequest,
                                                  GatedExtractRequest]:
        """Queue an extract; the returned request reports ``done`` once a
        ``dispatch``ed forward completes (observed by ``poll``/``wait``)
        or a blocking ``drain()`` runs it.  "adaptive" must be resolved by
        the caller (``MLLMExtractOp.begin_extract``) — the density EMA is
        per-op state the server has no business owning.

        With an active semantic gate, submission first consults the
        per-feed keyframe cache: near-duplicate rows are answered from
        cached extract outputs and only the admission's model rows enter
        the dispatch queue — a batch whose every row hits short-circuits
        dispatch entirely (``done`` immediately, zero queued frames).

        ``sig`` forwards a fused-prefix-computed ``(feats, emb)`` pair
        for these frames to the gate (see ``SemanticGate.admit``)."""
        assert variant in self.VARIANTS, variant
        assert frames.ndim == 4 and frames.shape[0] > 0, frames.shape
        self.stats["requests"] += 1
        if self.gate is not None and self.gate.active:
            adm = self.gate.admit(feed, variant, frames, sig=sig)
            inner = None
            if adm.n_model:
                inner = self._enqueue(variant, adm.model_frames(frames),
                                      feed)
            adm.bind(inner)
            return GatedExtractRequest(variant, frames, feed, adm, inner)
        return self._enqueue(variant, frames, feed)

    def _enqueue(self, variant: str, frames: np.ndarray,
                 feed: str) -> ExtractRequest:
        req = ExtractRequest(variant=variant, frames=frames, feed=feed)
        if self.obs.enabled:
            req.t_submit = self.obs.now()
        if self.faults.enabled:
            req.fault_event = self.faults.next_event("forward", feed)
        self._queue.append(req)
        self._pending_reqs[feed] = self._pending_reqs.get(feed, 0) + 1
        self._pending_frames[feed] = \
            self._pending_frames.get(feed, 0) + req.n
        self._pending_reqs_total += 1
        self._pending_frames_total += req.n
        return req

    def probe(self, variant: str, frames: np.ndarray,
              feed: str = "") -> ExtractRequest:
        """Enqueue an *isolated* canary extract (circuit-breaker
        half-open probe): it never coalesces with other feeds' requests,
        so a probe that faults cannot burn chunk-mates' retry budgets."""
        req = self._enqueue(variant, frames, feed)
        req.isolate = True
        return req

    def cancel(self, req: ExtractRequest) -> bool:
        """Remove a still-queued request (quarantine path: a tripped
        feed's parked submissions must not launch pointless forwards).
        Returns False when the request already dispatched or left the
        queue — its forward, if any, retires normally and is ignored."""
        if req.dispatched or req.failed:
            return False
        try:
            self._queue.remove(req)
        except ValueError:
            return False
        self._pending_reqs[req.feed] -= 1
        self._pending_frames[req.feed] -= req.n
        self._pending_reqs_total -= 1
        self._pending_frames_total -= req.n
        return True

    def pending_frames(self, feed: Optional[str] = None) -> int:
        """Frames queued and not yet dispatched (running counter)."""
        if feed is None:
            return self._pending_frames_total
        return self._pending_frames.get(feed, 0)

    def pending_requests(self, feed: Optional[str] = None) -> int:
        """Requests queued and not yet dispatched (running counter)."""
        if feed is None:
            return self._pending_reqs_total
        return self._pending_reqs.get(feed, 0)

    @property
    def inflight(self) -> int:
        """Forwards dispatched and not yet retired."""
        return len(self._inflight)

    # ------------------------------------------------------------------
    def _acquire_staging(self, key: Tuple, bucket: int, shape: Tuple,
                         dtype) -> np.ndarray:
        pool = self._staging.get(key)
        if pool:
            self.stats["staging_reused"] += 1
            return pool.pop()
        self.stats["staging_allocated"] += 1
        return np.empty((bucket,) + shape, dtype)

    def _chunk_failed(self, variant: str,
                      chunk: List[ExtractRequest]) -> None:
        """A chunk's forward faulted (injected or real): every member
        request stays queued for an *isolated* relaunch after its
        exponential backoff, or — past ``retry.max_attempts`` — turns
        terminally ``failed`` and leaves the queue (the runtime's
        circuit breaker takes over from there)."""
        obs = self.obs
        self.stats["forward_faults"] += 1
        seq = self._dispatch_seq
        for r in chunk:
            r.attempts += 1
            r.isolate = True
            if r.attempts >= self.retry.max_attempts:
                r.failed = True
                self.stats["retry_exhausted"] += 1
                # terminal: dispatch removes it from the queue below
                self._pending_reqs[r.feed] -= 1
                self._pending_frames[r.feed] -= r.n
                self._pending_reqs_total -= 1
                self._pending_frames_total -= r.n
            else:
                r.not_before = seq + self.retry.backoff_rounds(r.attempts)
                self.stats["retries"] += 1
            if obs.enabled:
                track = f"feed:{r.feed}"
                obs.tracer.instant(
                    f"fault:forward[{variant}]", "fault", track=track,
                    n=r.n)
                if r.failed:
                    obs.metrics.inc(f"faults/exhausted/{r.feed}", 1)
                else:
                    obs.tracer.instant("retry", "retry", track=track,
                                       n=r.n)
                    obs.metrics.inc(f"faults/retries/{r.feed}", 1)

    def _launch(self, variant: str, chunk: List[ExtractRequest]) -> bool:
        """Pack one chunk and launch its forward asynchronously; returns
        False when the forward faulted (members re-staged or failed)."""
        obs = self.obs
        faults = self.faults
        delay = 0
        if faults.enabled:
            for r in chunk:
                f = faults.fire("forward", r.feed, variant,
                                r.fault_event, r.attempts)
                if f is None:
                    continue
                if f[0] == "error":
                    self._chunk_failed(variant, chunk)
                    return False
                delay = max(delay, f[1])        # latency
        t_stage = obs.now() if obs.enabled else 0
        total = sum(r.n for r in chunk)
        bucket = _bucket_pad(total)
        shape = chunk[0].frames.shape[1:]
        dtype = chunk[0].frames.dtype
        if len(chunk) == 1 and chunk[0].n == bucket:
            # an exactly-full single request needs no staging copy
            dev = jnp.asarray(chunk[0].frames)
            buf_key = buf = None
            self.stats["staging_skipped"] += 1
        else:
            buf_key = (bucket,) + tuple(shape) + (dtype.str,)
            buf = self._acquire_staging(buf_key, bucket, shape, dtype)
            off = 0
            for r in chunk:
                buf[off:off + r.n] = r.frames
                off += r.n
            if bucket > total:
                # padding rows must classify as "normalized" in the jitted
                # program — a reused buffer otherwise carries stale frames
                buf[total:bucket] = 0
            dev = jnp.asarray(buf)
        t_disp = obs.now() if obs.enabled else 0
        if faults.enabled:
            # with the injector live, a real forward exception follows
            # the same retry path as an injected one; without it, errors
            # propagate exactly as before (no behavior change)
            try:
                preds = self._fn(variant)(dev)
            except AssertionError:
                raise
            except Exception:
                if buf is not None:
                    self._staging.setdefault(buf_key, []).append(buf)
                self._chunk_failed(variant, chunk)
                return False
        else:
            preds = self._fn(variant)(dev)  # async dispatch: returns now
        fl = _InFlightChunk(preds, list(chunk), buf_key, buf)
        fl.variant = variant
        fl.total = total
        if delay:
            fl.delay_polls = delay
            self.stats["latency_faults"] += 1
            if obs.enabled:
                obs.tracer.instant(f"fault:latency[{variant}]", "fault",
                                   track="device", n=total)
        if obs.enabled:
            fl.t_launch = obs.now()
            tr = obs.tracer
            tr.span("staging", "staging", t_stage, t_disp,
                    track="server", n=total)
            tr.span(f"dispatch[{variant}]", "dispatch", t_disp,
                    fl.t_launch, track="server", n=bucket)
            for r in chunk:
                if r.t_submit:
                    tr.span("queue_wait", "queue", r.t_submit, fl.t_launch,
                            track=f"feed:{r.feed}", n=r.n)
                    obs.metrics.observe(
                        f"queue_wait_ms/{r.feed}",
                        (fl.t_launch - r.t_submit) / 1e6, r.n)
            if self.device_probe_every and not delay:
                # device-accurate forward timing: every Nth forward is
                # probed — block on a one-element sentinel sliced from
                # the output, so the launch→completion interval excludes
                # the poll quantization the observed ``forward`` span
                # carries.  The probe serializes the host for this one
                # forward only; un-probed forwards are untouched.
                if self._probe_seq % self.device_probe_every == 0:
                    sentinel = next(iter(fl.preds.values()))[:1]
                    jax.block_until_ready(sentinel)
                    t_done = obs.now()
                    tr.span(f"forward_device[{variant}]", "forward",
                            fl.t_launch, t_done, track="device", n=total)
                    dev_ms = (t_done - fl.t_launch) / 1e6
                    obs.metrics.observe("forward_device_ms", dev_ms)
                    obs.metrics.observe(
                        f"forward_device_ms/{variant}", dev_ms)
                    obs.metrics.inc(
                        f"forward_device_frames/{variant}", total)
                self._probe_seq += 1
        off = 0
        for r in chunk:
            r._chunk = fl
            r._offset = off
            off += r.n
            self._pending_reqs[r.feed] -= 1
            self._pending_frames[r.feed] -= r.n
        self._pending_reqs_total -= len(chunk)
        self._pending_frames_total -= total
        self._inflight.append(fl)
        if obs.enabled:
            # occupancy timeline: sampled at every launch and retire
            obs.tracer.counter("inflight", len(self._inflight))
            obs.tracer.counter("queue_depth", self._pending_reqs_total)
        self.stats["forwards"] += 1
        self.stats["frames"] += total
        self.stats["padded_frames"] += bucket - total
        if len(chunk) > 1:
            self.stats["coalesced_batches"] += 1
        self.stats["max_inflight_seen"] = max(
            self.stats["max_inflight_seen"], len(self._inflight))
        return True

    def dispatch(self, budget: Optional[int] = None) -> int:
        """Launch queued requests as asynchronous forwards and return
        immediately; returns the number of forwards launched.

        Requests group by (variant, frame shape, dtype) and chunk greedily
        under ``max_batch`` frames per forward, exactly like the
        synchronous drain; at most ``budget`` chunks launch (None: as many
        as ``max_inflight`` allows).  Unlaunched requests stay queued in
        order, so per-feed FIFO resume order is preserved.

        Dispatch-ahead coalesces *fuller* forwards than the barrier drain:
        a chunk that exactly fills its power-of-two bucket launches
        eagerly, while a padded partial chunk is deferred — backpressured
        feeds keep filling the queue, so the partial usually grows into a
        full bucket by the next call — unless the device would otherwise
        idle (nothing in flight) or the chunk's bucket has already been
        deferred ``MAX_PARTIAL_DEFERS`` times (a feed whose chunks never
        fill a bucket must not starve behind feeds that keep the device
        busy).  ``drain()`` flushes deferred partials at the barrier,
        exactly like the synchronous path always did.

        With a live fault injector three more queue states exist:
        terminally *failed* requests leave the queue here (their owner
        sees ``failed``/``result`` raise), requests inside their backoff
        window (``not_before`` > the dispatch round counter) stay queued
        untouched, and *isolated* retry requests launch one-per-chunk
        ahead of everything else so a poisoned request can never spend a
        healthy chunk-mate's retry budget."""
        seq = self._dispatch_seq = self._dispatch_seq + 1
        room = self.max_inflight - len(self._inflight)
        if budget is not None:
            room = min(room, budget)
        if room <= 0 or not self._queue:
            return 0
        launched = 0
        taken: set = set()
        iso: List[ExtractRequest] = []
        groups: Dict[Tuple, List[ExtractRequest]] = {}
        for r in self._queue:
            if r.failed:
                taken.add(id(r))      # terminal: drop from the queue
                continue
            if r.not_before > seq:
                continue              # backing off: not eligible yet
            if r.isolate:
                iso.append(r)
                continue
            key = (r.variant, r.frames.shape[1:], r.frames.dtype.str)
            groups.setdefault(key, []).append(r)
        full: List[Tuple[Tuple, List[ExtractRequest]]] = []
        partial: List[Tuple[Tuple, List[ExtractRequest]]] = []
        for key, reqs in groups.items():
            chunk: List[ExtractRequest] = []
            size = 0
            for r in reqs:
                if chunk and size + r.n > self.max_batch:
                    (full if size == _bucket_pad(size) else partial).append(
                        (key, chunk))
                    chunk, size = [], 0
                chunk.append(r)
                size += r.n
            if chunk:
                (full if size == _bucket_pad(size) else partial).append(
                    (key, chunk))

        def launch(key: Tuple, chunk: List[ExtractRequest],
                   served: bool) -> None:
            nonlocal launched
            ok = self._launch(key[0], chunk)
            if served:
                # only a *partial* launch services the waiting bucket — a
                # full chunk of the same key must not reset the clock of
                # partial requests still parked behind it
                self._defers.pop(key, None)
            if ok:
                taken.update(id(r) for r in chunk)
                launched += 1
            else:
                # the forward faulted: members stay queued for isolated
                # retry, except those that just exhausted their budget
                taken.update(id(r) for r in chunk if r.failed)

        # isolated retries outrank everything: they are the oldest work
        # in the queue and each occupies a whole chunk by design
        for r in iso:
            if launched >= room:
                break
            launch((r.variant,), [r], served=False)
        overdue = [c for c in partial
                   if self._defers.get(c[0], 0) >= self.MAX_PARTIAL_DEFERS]
        fresh = [c for c in partial
                 if self._defers.get(c[0], 0) < self.MAX_PARTIAL_DEFERS]
        # overdue partials outrank full chunks: they have already waited
        # their bound, and full buckets can afford one call's patience
        for key, chunk in overdue:
            if launched >= room:
                break
            launch(key, chunk, served=True)
        for key, chunk in full:
            if launched >= room:
                break
            launch(key, chunk, served=False)
        for key, chunk in fresh:
            if launched >= room or self._inflight:
                break              # defer padding while the device is fed
            launch(key, chunk, served=True)
        # age every partial bucket that stayed queued — whatever the
        # reason (device fed, room exhausted by fulls) — so the deferral
        # bound holds even for a feed whose chunks never fill a bucket;
        # buckets with nothing left waiting drop their count (a partial
        # that grew into a launched full chunk must not leave a stale
        # count that would prematurely pad the bucket's next partial)
        waiting = {key for key, chunk in partial
                   if id(chunk[0]) not in taken}
        for key in waiting:
            self._defers[key] = self._defers.get(key, 0) + 1
        for key in list(self._defers):
            if key not in waiting:
                del self._defers[key]
        if not taken:
            return 0
        self._queue = [r for r in self._queue if id(r) not in taken]
        self.stats["dispatches"] += 1
        return launched

    # ------------------------------------------------------------------
    def _retire(self, fl: _InFlightChunk) -> None:
        fl.completed = True
        if fl.buf is not None:
            # the device consumed the staging input; recycle it
            self._staging.setdefault(fl.buf_key, []).append(fl.buf)
            fl.buf = None
        if fl.t_launch:
            # launch → observed completion: an upper bound on device time
            # (includes the poll interval), which is the honest quantity
            # for occupancy reasoning — the host couldn't have used the
            # result any earlier
            obs = self.obs
            t1 = obs.now()
            obs.tracer.span(f"forward[{fl.variant}]", "forward",
                            fl.t_launch, t1, track="device", n=fl.total)
            obs.metrics.observe(
                "forward_ms", (t1 - fl.t_launch) / 1e6)
            fl.t_launch = 0

    def poll(self) -> int:
        """Non-blocking: retire every in-flight forward whose device work
        completed — its requests report ``done`` and their continuations
        become resumable — and recycle its staging buffer.  Returns the
        number of forwards retired."""
        still: List[_InFlightChunk] = []
        retired = 0
        for fl in self._inflight:
            if fl.delay_polls > 0:
                # injected device latency: completion observed late,
                # one poll at a time (clock-free)
                fl.delay_polls -= 1
                still.append(fl)
            elif fl.ready():
                self._retire(fl)
                retired += 1
            else:
                still.append(fl)
        self._inflight = still
        return retired

    def pump(self, progressed: bool, coalesce_frames: int,
             settle: Callable[[], int]) -> None:
        """One pipelined scheduling step — THE shared driver of the
        dispatch/poll/resume protocol, so the serving runtimes
        (``MultiStreamRuntime.run``, ``MultiQueryRuntime``'s server path)
        cannot drift: dispatch once the coalescing window holds
        ``coalesce_frames`` queued frames (or nothing progressed this
        round), poll completions, ``settle()`` fulfilled continuations
        (returns how many resumed), and block for the oldest forward only
        when genuinely stalled — nothing pulled, nothing resumed.
        Polling comes first so an inflight slot freed by a completed
        forward refills in the *same* step — the device stays
        double-buffered instead of draining toward depth 1."""
        self.poll()
        if self.pending_frames() >= coalesce_frames or not progressed:
            self.dispatch()
        resumed = settle()
        if not progressed and not resumed:
            self.wait()

    def _stuck_desc(self) -> str:
        """Name the work the watchdog is stuck on — the error message a
        timed-out ``wait()``/``drain()`` raises."""
        if self._inflight:
            fl = self._inflight[0]
            total = sum(r.n for r in fl.reqs)
            feeds = sorted({r.feed for r in fl.reqs})
            return (f"in-flight chunk variant={fl.variant!r} "
                    f"bucket={_bucket_pad(total)} ({len(fl.reqs)} reqs, "
                    f"{total} frames, feeds={feeds})")
        if self._queue:
            r = self._queue[0]
            return (f"queued request feed={r.feed!r} "
                    f"variant={r.variant!r} n={r.n} "
                    f"attempts={r.attempts} "
                    f"not_before={r.not_before} (round {self._dispatch_seq})")
        return "no queued or in-flight work"

    def wait(self) -> int:
        """Block until at least one in-flight forward completes
        (dispatching queued work first when nothing is in flight); returns
        the number of forwards retired.  The runtime's stall path: called
        only when no feed can progress and nothing polled ready.

        Deadline-bounded: if ``drain_timeout_s`` passes without a single
        retirement or launch, raises ``ExtractStallError`` naming the
        stuck chunk instead of spinning forever (injected latency burns
        one poll per iteration, so it always terminates well before)."""
        if not self._inflight:
            self.dispatch()
        deadline = time.monotonic() + self.drain_timeout_s
        while self._inflight:
            self._inflight[0].block()
            retired = self.poll()
            if retired:
                return retired
            if not self.dispatch() and time.monotonic() > deadline:
                raise ExtractStallError(
                    f"wait(): no extract progress for "
                    f"{self.drain_timeout_s:g}s; stuck on "
                    f"{self._stuck_desc()}")
        return 0

    def drain(self) -> int:
        """Synchronous barrier: run every queued and in-flight request to
        completion; returns the number of forwards.  Survives as the
        end-of-run / warmup / checkpoint flush — the steady-state path is
        ``dispatch``/``poll``.

        Deadline-bounded (was an unbounded busy-wait): every round that
        launches or retires nothing eats into ``drain_timeout_s``; when
        the budget is gone an ``ExtractStallError`` names the stuck
        bucket/variant.  Rounds that *do* progress reset the deadline, so
        a long healthy drain never trips it."""
        forwards0 = self.stats["forwards"]
        deadline = time.monotonic() + self.drain_timeout_s
        while self._queue or self._inflight:
            launched = self.dispatch()
            retired = 0
            if self._inflight:
                self._inflight[0].block()
                retired = self.poll()
            if launched or retired:
                deadline = time.monotonic() + self.drain_timeout_s
            elif time.monotonic() > deadline:
                raise ExtractStallError(
                    f"drain(): no extract progress for "
                    f"{self.drain_timeout_s:g}s with "
                    f"{len(self._queue)} queued / "
                    f"{len(self._inflight)} in-flight forwards; stuck on "
                    f"{self._stuck_desc()}")
        return self.stats["forwards"] - forwards0
