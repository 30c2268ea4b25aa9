"""FusedPrefixOp: a plan's surviving-frame prefix as one device pass.

The streaming prefix of an optimized plan — Skip's frame diff, cheap
color filters, crop/downscale/greyscale/normalize, the TinyDet cascade,
and the semantic gate's ``TemporalSignature`` — normally executes as 3–5
separate jitted calls per micro-batch, each paying dispatch overhead and
a host round trip.  ``FusedPrefixOp`` wraps that whole segment in one
descriptor whose ``process`` makes a **single** compiled call:
``kernels/fused_prefix`` (Pallas on TPU, inlined pure-jnp composite on
CPU) produces every per-row statistic plus the transformed frames and
the gate signature, and the host then replays the stage *decisions*
(mask composition and Skip's stateful loop) exactly as the unfused ops
would.

Bitwise-identity contract: filters never transform frames, so their
per-row statistics computed on the full batch equal the unfused values
computed on compacted survivor batches (the per-row determinism the
serving tier already relies on for coalesced-vs-solo equality), and
transforms are applied to all rows in chain order.  The physical phase
(``core/physical.py``) decides fused-vs-unfused per plan from
``CostCatalog`` calibration; this op never self-selects.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tollbooth import COLOR_RGB
from repro.kernels.fused_prefix.kernel import out_frame_shape
from repro.kernels.fused_prefix.ops import fused_prefix
from repro.streaming.operators import (
    Batch,
    CheapColorFilterOp,
    CropOp,
    DetectOp,
    FusedPreprocessOp,
    Op,
    OpContext,
    SkipOp,
    _mask_batch,
)

#: operator classes the fused pass can absorb.  Downscale/Greyscale are
#: deliberately absent: their host-numpy math is not guaranteed to match
#: a jnp replica bit for bit, and the logical optimizer already folds
#: them into ``FusedPreprocessOp`` (rule R3) in every optimized plan.
FUSABLE = (SkipOp, CheapColorFilterOp, CropOp, FusedPreprocessOp,
           DetectOp)


def fusable_segment(ops: List[Op]) -> bool:
    """True when ``ops`` is a chain the fused pass can execute: only
    FUSABLE classes, any Skip first (its diff reads the raw input), any
    Detect last (it scores the fully-transformed frames)."""
    if not ops or not all(isinstance(o, FUSABLE) for o in ops):
        return False
    if any(isinstance(o, SkipOp) for o in ops[1:]):
        return False
    if any(isinstance(o, DetectOp) for o in ops[:-1]):
        return False
    return sum(isinstance(o, SkipOp) for o in ops) <= 1 \
        and sum(isinstance(o, DetectOp) for o in ops) <= 1


def fusable_start(ops: List[Op], end: int) -> int:
    """Start of the longest fusable segment ``ops[start:end]`` — the
    FUSABLE run just before ``ops[end]`` (the extract), trimmed from the
    left until the ordering constraints hold; ``end`` if there is none."""
    start = end
    while start > 0 and isinstance(ops[start - 1], FUSABLE):
        start -= 1
    while start < end and not fusable_segment(ops[start:end]):
        start += 1
    return start


@dataclasses.dataclass
class FusedPrefixOp(Op):
    """One-device-pass execution of a fusable prefix segment.

    ``stage_ops`` are the original descriptors in plan order — they stay
    the single source of truth for every threshold, region, and Skip's
    runtime state (``keep_from_diff`` advances the member SkipOp
    itself, so a fused plan snapshots/restores like the unfused one).
    ``sig=True`` additionally emits the semantic-gate signature for the
    surviving rows as ``batch["_sig"]``, consumed by the extract
    immediately downstream."""

    stage_ops: Tuple[Op, ...] = ()
    sig: bool = True

    def __post_init__(self):
        assert fusable_segment(list(self.stage_ops)), \
            f"not a fusable segment: {[o.name for o in self.stage_ops]}"
        self.name = "fused_prefix[" + \
            "+".join(o.name for o in self.stage_ops) + "]"
        self._fns: Dict[Tuple, Any] = {}
        #: per-stage (name, rows_in, rows_out) of the last processed
        #: batch — the runtimes' per-stage attribution gauges
        self.last_stage_counts: List[Tuple[str, int, int]] = []

    # ------------------------------------------------------------------
    def signature(self) -> Tuple:
        # the default dataclass signature would embed unhashable Op
        # instances; flatten to nested primitive tuples so share_key
        # grouping and planner dicts keep working
        return ("FusedPrefixOp",
                tuple(o.signature() for o in self.stage_ops),
                ("sig", self.sig))

    def unfuse(self) -> List[Op]:
        """Fresh, stateless copies of the member descriptors — the
        unfused chain this op replaces (fleet canonicalization joins
        prefixes at this granularity)."""
        out = []
        for o in self.stage_ops:
            kw = {f.name: getattr(o, f.name)
                  for f in dataclasses.fields(o) if f.init}
            out.append(type(o)(**kw))
        return out

    # ------------------------------------------------------------------
    def open(self, ctx: OpContext) -> None:
        self._skip: Optional[SkipOp] = None
        self._detect: Optional[DetectOp] = None
        pix: List[Tuple] = []
        for o in self.stage_ops:
            if isinstance(o, SkipOp):
                self._skip = o
                pix.append(("diff", o.regions))
            elif isinstance(o, CheapColorFilterOp):
                pix.append(("color", tuple(COLOR_RGB[o.color]), o.roi))
            elif isinstance(o, CropOp):
                pix.append(("crop", o.region))
            elif isinstance(o, FusedPreprocessOp):
                pix.append(("preprocess", o.crop, o.factor, o.grey))
            else:
                self._detect = o
        self._pix_spec = tuple(pix)
        self._normalizes = any(isinstance(o, FusedPreprocessOp)
                               for o in self.stage_ops)
        self._det_model = ctx.detector
        self._det_params = ctx.detector_params
        self._fns = {}

    def _fn(self, shape: Tuple[int, ...], dtype_str: str):
        key = tuple(shape) + (dtype_str,)
        if key in self._fns:
            return self._fns[key]
        spec = self._pix_spec
        proj = None
        if self.sig:
            # the gate's layout for the *final* frame shape — shared
            # source of truth, so fused and unfused signatures agree
            from repro.semantic.signature import signature_layout

            out_shape = out_frame_shape(spec, tuple(shape))
            gy, gx, _, proj_np = signature_layout(out_shape)
            spec = spec + (("signature", (gy, gx)),)
            proj = jnp.asarray(proj_np)
        det, params = self._det_model, self._det_params
        run_det = self._detect is not None

        @jax.jit
        def run(frames, prevs):
            # nested jit inlines: the pixel stages, the detect forward,
            # and the signature matmul compile to ONE XLA program — one
            # dispatch per micro-batch however long the chain is
            d, fracs, x, feats, emb = fused_prefix(frames, prevs, proj,
                                                   spec=spec)
            p = None
            if run_det:
                xx = x.astype(jnp.float32)
                # DetectOp's jitted body, verbatim (per-frame raw detect)
                raw = xx.reshape(xx.shape[0], -1).max(axis=1) > 8.0
                xx = jnp.where(raw[:, None, None, None],
                               xx / 255.0 - 0.5, xx)
                out = det.forward(params, xx)
                p = jax.nn.softmax(out["present"], -1)[:, 1]
            return d, fracs, x, p, feats, emb

        self._fns[key] = run
        return run

    # ------------------------------------------------------------------
    def process(self, batch: Batch) -> Batch:
        frames = batch["frames"]
        n = frames.shape[0]
        if n == 0:
            self.link_bytes = (0, 0)
            return batch
        prevs = self._skip.prev_frames(frames) \
            if self._skip is not None else None
        run = self._fn(frames.shape[1:], frames.dtype.str)
        d, fracs, x, p, feats, emb = run(
            jnp.asarray(frames),
            jnp.asarray(prevs) if prevs is not None else None)
        # what comes back to the host below: every stage's statistic,
        # the frames, and the gate signature
        down = [x, p, *fracs, *((feats, emb) if self.sig else ()),
                d if self._skip is not None else None]
        self.link_bytes = (
            frames.nbytes + (prevs.nbytes if prevs is not None else 0),
            sum(a.nbytes for a in down if a is not None))

        # host side: replay each stage's *decision* in chain order —
        # Skip's stateful loop advances the member op itself
        keep = np.ones(n, bool)
        self.last_stage_counts = []
        ci = 0
        for o in self.stage_ops:
            rows_in = int(keep.sum())
            if isinstance(o, SkipOp):
                keep &= o.keep_from_diff(frames, np.asarray(d))
            elif isinstance(o, CheapColorFilterOp):
                keep &= np.asarray(fracs[ci]) >= o.min_frac
                ci += 1
            elif isinstance(o, DetectOp):
                keep &= np.asarray(p) >= o.threshold
            self.last_stage_counts.append(
                (o.name, rows_in, int(keep.sum())))

        batch = dict(batch)
        batch["frames"] = np.asarray(x)
        if self._normalizes:
            batch["normalized"] = True
        batch = _mask_batch(batch, keep)
        if self.sig:
            batch["_sig"] = (np.asarray(feats)[keep],
                             np.asarray(emb)[keep])
        return batch

    # ------------------------------------------------------------------
    def reset(self) -> None:
        for o in self.stage_ops:
            o.reset()
        self.last_stage_counts = []

    def snapshot(self) -> Dict[str, Any]:
        return {"stages": [o.snapshot() for o in self.stage_ops]}

    def restore(self, st: Dict[str, Any]) -> None:
        for o, s in zip(self.stage_ops, st["stages"]):
            o.restore(s)
