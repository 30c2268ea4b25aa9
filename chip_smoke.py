#!/usr/bin/env python3
"""Chip smoke: the multi-stream serving path, end to end, on one TPU.

Drives the system the way a user does, in one process:

1. device check — a TPU or nothing (no CPU fallback);
2. stream models trained on the chip from seed 0 at their full widths
   (``train_stream_models`` with its default steps, no checkpoint read);
3. the 4-feed / 9-query workload of ``examples/multistream_serve.py``,
   planned by ``FleetOptimizer`` and served by
   ``MultiStreamRuntime.from_fleet``, once ungated and once behind a
   ``SemanticGate``;
4. every (feed, query) pair run alone through ``StreamRuntime`` and
   compared record for record with the shared run;
5. the stream kernels (``frame_diff``, ``fused_preprocess``,
   ``fused_prefix``) shown to be compiled Mosaic kernels, checked against
   their jnp references, and the fused prefix compared with its unfused
   chain.

The last line of standard output is one JSON object naming the device.
This is a smoke run, not a benchmark: its wall times include compilation
and host noise.

  python3 chip_smoke.py
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: frames per feed in each serving run, and validation frames per plan
#: in fleet optimization (the non-quick value of examples/fleet_serve.py)
FRAMES = 128
VAL_FRAMES = 128

#: kernel output against its jnp reference, per element: the tolerance
#: of the interpret-mode sweep in tests/test_kernels.py
KERNEL_ATOL = 1e-5

#: shared against independent execution, per query.  The contract proven
#: on XLA:CPU is exact equality, and exact equality is what this smoke
#: asks of the chip too: padding rows never reach a real row's result,
#: and every per-row statistic the prefix kernels compute is pooled
#: exactly.  Any differing record fails the run and is printed with the
#: stage that produced it.
MAX_DIFFERING_RECORDS = 0


#: failed checks; every phase still runs, so one run reports them all,
#: and the script exits 1 at the end if any failed
FAILED = []


def fail(msg: str) -> None:
    FAILED.append(msg)
    print(f"CHECK FAILED: {msg}", flush=True)


class CompileClock:
    """Backend compile seconds and compile count, from JAX's own
    monitoring events (``/jax/core/compile/backend_compile_duration``)."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.count = 0

        def on_duration(event: str, secs: float, **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def device_check():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(jax.devices())} "
              f"{dev.platform} device(s) ({dev.device_kind}); this smoke "
              f"runs only on a TPU", file=sys.stderr)
        sys.exit(2)
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"device: {dev.device_kind} x{len(jax.devices())}  "
          f"jax {jax.__version__}  libtpu {libtpu}")
    return dev, len(jax.devices())


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Print a phase's wall and compile seconds when it ends."""
    c0, n0 = clock.secs, clock.count
    t0 = time.perf_counter()
    yield
    print(f"[{name}] wall {time.perf_counter() - t0:.2f}s  "
          f"compile {clock.secs - c0:.2f}s in {clock.count - n0} compiles")


def train_phase():
    from repro.configs import get_config
    from repro.streaming.pretrain import train_stream_models

    for name in ("samsara-stream-mllm", "samsara-stream-mllm-small"):
        cfg = get_config(name)
        print(f"model {name}: d_model {cfg.d_model}, {cfg.n_layers} layers, "
              f"{cfg.attention.n_heads} heads / "
              f"{cfg.attention.n_kv_heads} kv heads")
    # cache_dir=None: weights come from the seed on this device, never
    # from a checkpoint a CPU run left in the git-ignored cache
    ctx = train_stream_models(cache_dir=None, verbose=False)
    return ctx


def check_logits_finite(ctx) -> None:
    """Every extract variant and the detector give finite logits on a
    normalized stream batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import TollBoothStream
    from repro.streaming.mllm import variant_models
    from repro.streaming.pretrain import CROP, preprocess_np

    frames, _ = TollBoothStream(seed=99).batch(16)
    for crop, factor in ((None, 1), (CROP, 2)):
        x = jnp.asarray(preprocess_np(frames, crop, factor))
        for variant, (model, params) in variant_models(ctx).items():
            out = jax.jit(model.forward)(params, x)
            for head, v in out.items():
                if not np.isfinite(np.asarray(v)).all():
                    fail(f"non-finite {variant} logits for head {head}")
        out = jax.jit(ctx.detector.forward)(ctx.detector_params, x)
        if not np.isfinite(np.asarray(out["present"])).all():
            fail("non-finite detector logits")
    print("logits: finite for big/small/pruned extract and TinyDet")


def check_coalesced_rows(ctx) -> None:
    """Report whether a row's logits depend on the batch it rides in: 5
    frames in a bucket of 8 against the same frames at the head of a
    bucket of 64 full of other frames.  Coalesced serving relies on this
    ("a coalesced row equals the solo row"); the shared-against-
    independent comparison is the check, this names the stage."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import TollBoothStream
    from repro.streaming.mllm import variant_models
    from repro.streaming.pretrain import CROP, preprocess_np

    frames, _ = TollBoothStream(seed=7).batch(64)
    x = preprocess_np(frames, CROP, 2)
    solo = np.concatenate([x[:5], np.zeros((3,) + x.shape[1:], x.dtype)])
    for variant, (model, params) in variant_models(ctx).items():
        fwd = jax.jit(model.forward)
        a = fwd(params, jnp.asarray(solo))
        b = fwd(params, jnp.asarray(x))
        err = max(_max_abs(np.asarray(a[h])[:5], np.asarray(b[h])[:5])
                  for h in a)
        same = all(np.array_equal(np.argmax(a[h][:5], -1),
                                  np.argmax(b[h][:5], -1)) for h in a)
        print(f"coalesced row vs solo row ({variant}): logits max |diff| "
              f"{err:.3g}, predictions {'equal' if same else 'DIFFER'}")


def fleet_phase(ctx, feeds, make_stream, val_frames: int):
    from repro.core.fleet import FleetOptimizer, FleetQuery
    from repro.queries import get_query

    workload = [FleetQuery(get_query(qid),
                           (lambda ds: lambda seed: make_stream(ds, seed))(ds),
                           feed=name)
                for name, ds, _, qids in feeds for qid in qids]
    fleet = FleetOptimizer(ctx, val_frames=val_frames).optimize(workload)
    for name, plans in fleet.plans_by_feed.items():
        for p in plans:
            print(f"plan {name}/{p.query}: "
                  + " -> ".join(op.name for op in p.ops))
    return fleet


def serve(fleet, ctx, feeds, make_stream, frames: int, gate=None):
    from repro.scheduler import MultiStreamRuntime

    streams = {name: make_stream(ds, seed) for name, ds, seed, _ in feeds}
    ms = MultiStreamRuntime.from_fleet(fleet, streams, ctx, micro_batch=16,
                                       gate=gate)
    res = ms.run(frames)
    for name, fr in res.feeds.items():
        if fr.served + fr.degraded + fr.dropped != fr.n_frames:
            fail(f"feed {name}: served {fr.served} + degraded "
                 f"{fr.degraded} + dropped {fr.dropped} != ingested "
                 f"{fr.n_frames}")
    st = res.server_stats
    print(f"  forwards {st['forwards']}  frames {st['frames']}  padding "
          f"{st['padded_frames']}/{st['frames'] + st['padded_frames']}  "
          f"coalesced batches {st['coalesced_batches']}  "
          f"query-frames/s {res.fps:.1f} (wall clock, not a benchmark)")
    return ms, res


def independent(fleet, ctx, feeds, make_stream, frames: int):
    from repro.streaming.runtime import StreamRuntime

    out = {}
    for name, ds, seed, _ in feeds:
        for p in fleet.plans_by_feed[name]:
            rt = StreamRuntime(p.clone(), ctx, micro_batch=16)
            out[(name, p.query)] = rt.run(make_stream(ds, seed), frames)
    return out


def differing(a, b) -> int:
    """Records at which two result lists differ (length mismatch counts
    every unmatched record)."""
    return sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))


def compare(shared, gated, indep) -> None:
    from repro.queries import get_query

    bad = []
    print(f"{'feed':<9} {'query':<5} {'outputs':>9} {'windows':>9} "
          f"{'diff':>5} {'acc':>7} {'acc(gated)':>10}")
    for (name, key), ir in indep.items():
        sq = shared.feeds[name].per_query[key]
        gq = gated.feeds[name].per_query[key]
        n_diff = differing(sq.outputs, ir.outputs) + \
            differing(sq.window_results, ir.window_results)
        q = get_query(key.split(":")[-1])        # fleet key: [feed:]qid
        acc, gacc = q.evaluate(sq), q.evaluate(gq)
        print(f"{name:<9} {key:<5} "
              f"{'equal' if sq.outputs == ir.outputs else 'DIFFER':>9} "
              f"{'equal' if sq.window_results == ir.window_results else 'DIFFER':>9} "
              f"{n_diff:>5} {acc:>7.3f} {gacc:>10.3f}")
        if n_diff > MAX_DIFFERING_RECORDS:
            bad.append(f"{name}/{key}: {n_diff} records")
    print(f"shared == independent: {len(indep) - len(bad)}/{len(indep)} "
          f"queries")
    if bad:
        fail("shared run differs from independent runs: " + ", ".join(bad))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _max_abs(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        raise ValueError(f"shape {a.shape} != {b.shape}")
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        fail("non-finite kernel output")
    return float(np.abs(a - b).max()) if a.size else 0.0


def _segment(plan):
    """The plan's fused prefix op, or its fusable pre-extract segment
    wrapped as a fresh one (None when it has no such segment)."""
    from repro.streaming.fused import FusedPrefixOp, fusable_start
    from repro.streaming.operators import MLLMExtractOp

    for op in plan.ops:
        if isinstance(op, FusedPrefixOp):
            return op
    mi = plan.index_of(MLLMExtractOp)
    start = fusable_start(plan.ops, mi)
    if start == mi:
        return None
    return FusedPrefixOp(stage_ops=tuple(plan.ops[start:mi]))


def kernel_phase(ms_list, fleet, ctx) -> None:
    """tpu_custom_call in the jitted ops that served the runs; each
    kernel against its jnp reference; the fused prefix against its
    unfused chain on one tollbooth segment."""
    import jax.numpy as jnp
    import numpy as np

    from repro.data import TollBoothStream
    from repro.kernels.frame_diff.ops import frame_diff
    from repro.kernels.frame_diff.ref import frame_diff_ref
    from repro.kernels.fused_prefix.ops import fused_prefix
    from repro.kernels.fused_prefix.ref import fused_prefix_ref
    from repro.kernels.fused_preprocess.ops import fused_preprocess
    from repro.kernels.fused_preprocess.ref import fused_preprocess_ref
    from repro.semantic.signature import TemporalSignature
    from repro.streaming.fused import FusedPrefixOp
    from repro.streaming.operators import FusedPreprocessOp, SkipOp

    stream = TollBoothStream(seed=2025)
    raw, _ = stream.batch(17)
    cur, prev = jnp.asarray(raw[1:]), jnp.asarray(raw[:-1])

    served = [op for ms in ms_list for fs in ms._feeds
              for g in fs.groups for op in g.all_ops()]
    skips = [o for o in served if isinstance(o, SkipOp)]
    pres = [o for o in served if isinstance(o, FusedPreprocessOp)]
    fused = [o for o in served if isinstance(o, FusedPrefixOp)]
    for o in fused:
        skips += [s for s in o.stage_ops if isinstance(s, SkipOp)]
        pres += [s for s in o.stage_ops if isinstance(s, FusedPreprocessOp)]
    print(f"served ops: {len(skips)} skip, {len(pres)} preprocess, "
          f"{len(fused)} fused prefix")

    def mosaic(name, lowered) -> None:
        ok = "tpu_custom_call" in lowered.as_text()
        print(f"kernel {name}: tpu_custom_call "
              f"{'present' if ok else 'ABSENT'}")
        if not ok:
            fail(f"{name} did not lower to a Mosaic kernel")

    regions = {s.regions for s in skips} or {(4, 8)}
    for r in sorted(regions):
        mosaic(f"frame_diff{r}", frame_diff.lower(cur, prev, regions=r))
        err = _max_abs(frame_diff(cur, prev, regions=r),
                       frame_diff_ref(cur, prev, regions=r))
        print(f"  frame_diff{r} vs ref: max |diff| {err:.3g}")
        if err > KERNEL_ATOL:
            fail(f"frame_diff{r} off its reference by {err}")
    configs = {(o.crop, o.factor, o.grey) for o in pres} or \
        {((64, 0, 64, 256), 2, False)}
    for crop, factor, grey in sorted(configs):
        kw = dict(crop=crop, factor=factor, grey=grey)
        name = f"fused_preprocess{crop}/{factor}{'grey' if grey else ''}"
        mosaic(name, fused_preprocess.lower(cur, **kw))
        err = _max_abs(fused_preprocess(cur, **kw),
                       fused_preprocess_ref(cur, **kw))
        print(f"  {name} vs ref: max |diff| {err:.3g}")
        if err > KERNEL_ATOL:
            fail(f"{name} off its reference by {err}")

    # the fused prefix: the op that served, or the first tollbooth
    # plan's pre-extract segment when the physical phase refused to fuse
    op = fused[0] if fused else next(
        s for p in fleet.plans.values() if p.ops[0].stream_name ==
        "tollbooth" for s in [_segment(p)] if s is not None)
    op = FusedPrefixOp(stage_ops=tuple(op.unfuse()))
    chain = op.unfuse()
    op.open(ctx)
    for o in chain:
        o.open(ctx)
    run = op._fn(raw.shape[1:], raw.dtype.str)
    prevs = jnp.asarray(op._skip.prev_frames(raw[1:])) \
        if op._skip is not None else None
    mosaic("fused_prefix", run.lower(cur, prevs))
    spec = op._pix_spec
    from repro.kernels.fused_prefix.kernel import out_frame_shape
    from repro.semantic.signature import signature_layout

    gy, gx, _, proj = signature_layout(out_frame_shape(spec, raw.shape[1:]))
    spec = spec + (("signature", (gy, gx)),)
    pj = jnp.asarray(proj)
    kout = fused_prefix(cur, prevs, pj, spec=spec)
    rout = fused_prefix_ref(cur, prevs, pj, spec=spec)
    for name, a, b in zip(("d", "fracs", "x", "feats", "emb"), kout, rout):
        if b is None or (name == "fracs" and not b):
            continue
        a = np.stack(a) if name == "fracs" else a
        b = np.stack(b) if name == "fracs" else b
        err = _max_abs(a, b)
        print(f"  fused_prefix {name} vs ref: max |diff| {err:.3g}")
        if err > KERNEL_ATOL:
            fail(f"fused_prefix {name} off its reference by {err}")
    print("  fused prefix segment: " + " + ".join(o.name for o in chain))

    # fused against unfused over 8 micro-batches: every stage statistic
    # on every row (so a difference names its stage), then the keep
    # decisions with Skip's state carried across batches
    sig = TemporalSignature()
    stream = TollBoothStream(seed=1234)
    keep_equal, n_kept = True, 0
    worst = {}
    prev = None
    for b in range(8):
        frames, _ = stream.batch(16)
        prevs = np.concatenate([frames[:1] if prev is None else prev,
                                frames[:-1]])
        prev = frames[-1:]
        fused_stats = run(jnp.asarray(frames), jnp.asarray(prevs))
        for name, a, u in zip(("d", "fracs", "x", "p", "feats", "emb"),
                              fused_stats,
                              _unfused_stats(chain, frames, prevs, sig)):
            if u is None or (name == "fracs" and not u):
                continue
            if name == "fracs":
                a, u = np.stack(a), np.stack(u)
            worst[name] = max(worst.get(name, 0.0), _max_abs(a, u))
        batch = {"frames": frames, "idx": np.arange(16) + 16 * b}
        fb = op.process(dict(batch))
        ub = dict(batch)
        for o in chain:
            ub = o.process(ub)
        keep_equal &= np.array_equal(fb["idx"], ub["idx"])
        n_kept += len(fb["idx"])
    print(f"fused vs unfused over 128 frames: keep-masks "
          f"{'equal' if keep_equal else 'DIFFER'} ({n_kept} kept fused); "
          "max |diff| " + "  ".join(f"{k} {v:.3g}" for k, v in worst.items()))


def _unfused_stats(chain, frames, prevs, sig):
    """What each unfused op of ``chain`` computes on every row of one
    micro-batch: (d, fracs, x, p, feats, emb) as ``FusedPrefixOp``'s
    jitted pass returns them."""
    import jax.numpy as jnp
    import numpy as np

    from repro.streaming.operators import (CheapColorFilterOp, DetectOp,
                                           SkipOp)

    d, fracs, p, x = None, [], None, frames
    for o in chain:
        if isinstance(o, SkipOp):
            d = o._diff(frames, prevs)
        elif isinstance(o, CheapColorFilterOp):
            roi = x
            if o.roi is not None:
                y0, x0, h, w = o.roi
                roi = x[:, :, y0:y0 + h, x0:x0 + w]
            fracs.append(o._frac(jnp.asarray(roi)))
        elif isinstance(o, DetectOp):
            p = o._run(jnp.asarray(x))
        else:                                   # crop / preprocess
            x = o.process({"frames": x})["frames"]
    feats, emb = sig.features(np.asarray(x))
    return d, fracs, x, p, feats, emb


# ---------------------------------------------------------------------------

def main() -> None:
    argparse.ArgumentParser(description=__doc__.splitlines()[0]).parse_args()
    dev, count = device_check()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from repro.common.utils import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    from multistream_serve import FEEDS, _make_stream

    clock = CompileClock()
    t_all = time.perf_counter()
    with phase("train (default steps, seed 0)", clock):
        ctx = train_phase()
    check_logits_finite(ctx)
    check_coalesced_rows(ctx)
    with phase(f"fleet optimize ({len(FEEDS)} feeds, "
               f"{sum(len(q) for *_, q in FEEDS)} queries)", clock):
        fleet = fleet_phase(ctx, FEEDS, _make_stream, VAL_FRAMES)

    from repro.semantic import GateConfig, SemanticGate

    with phase(f"serve shared ungated ({FRAMES} frames/feed)", clock):
        ms, shared = serve(fleet, ctx, FEEDS, _make_stream, FRAMES)
    gate = SemanticGate(GateConfig(threshold=0.06))
    with phase("serve shared gated (threshold 0.06)", clock):
        gms, gated = serve(fleet, ctx, FEEDS, _make_stream, FRAMES,
                           gate=gate)
    print(f"  gate hit rate {gate.hit_rate():.3f}  forwards "
          f"{gated.server_stats['forwards']} gated vs "
          f"{shared.server_stats['forwards']} ungated")
    with phase("independent (one StreamRuntime per query)", clock):
        indep = independent(fleet, ctx, FEEDS, _make_stream, FRAMES)
    compare(shared, gated, indep)
    with phase("kernels", clock):
        kernel_phase([ms, gms], fleet, ctx)
    print(f"total wall {time.perf_counter() - t_all:.1f}s, of it "
          f"{clock.secs:.1f}s compiling ({clock.count} compiles)")
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed:\n  "
              + "\n  ".join(FAILED), file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
