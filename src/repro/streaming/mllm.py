"""StreamMLLM: the multimodal LLM *operator* the streaming plans invoke.

A patch-embedding frontend + an LM backbone from the registry + per-task
readout heads.  This is the in-framework stand-in for the paper's
Qwen2.5-VL operator: `Extract(color, plate, brand, present, action)` runs
one batched forward over preprocessed frames and returns structured
attributes.  The physical optimizer swaps the backbone (big ↔ distilled
small ↔ int8-quantized ↔ pruned) behind the same interface.

Patchify: frames (B, C, h, w) -> non-overlapping p×p patches -> linear
projection to d_model; task queries are learned tokens appended after the
patches; heads read their task token's final hidden state.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ArchConfig
from repro.data.tollbooth import BRANDS, COLORS, PLATE_CHARS
from repro.data.volleyball import ACTIONS
from repro.models import LM
from repro.models.param import ParamSpec, materialize
from repro.models.layers import apply_norm
from repro.models import blocks as blk

PLATE_LEN = 6
MLLM_TASKS = {
    "present": 2,
    "color": len(COLORS),
    "brand": len(BRANDS),
    "plate": PLATE_LEN * len(PLATE_CHARS),
    "action": len(ACTIONS),
    "n_jumping": 7,           # 0..6 jumping players
    "team": 2,                # attacking team (volleyball Q11)
}


SCALAR_TASKS = ("present", "color", "brand", "action", "n_jumping", "team")


class StreamMLLM:
    """Bundles backbone cfg + patchify + heads into one extract operator.

    Readout: one learned task token per scalar task + one per plate char
    position (a 6-char plate reads from 6 dedicated tokens)."""

    def __init__(self, cfg: ArchConfig, patch: int = 8, tp: int = 1):
        assert cfg.frontend == "patch"
        self.cfg = cfg
        self.patch = patch
        self.lm = LM(cfg, tp=tp, q_block=256)
        self.n_tasks = len(SCALAR_TASKS) + PLATE_LEN

    STEM_CH = 48  # conv-stem output channels (stride 4 total)

    # ------------------------------------------------------------------
    def spec(self, in_ch: int = 3, max_patches: int = 512) -> Dict[str, Any]:
        d = self.cfg.d_model
        p = self.patch // 4  # patch size on the stride-4 conv feature map
        heads = {
            name: ParamSpec((d, MLLM_TASKS[name]), ("embed", None))
            for name in SCALAR_TASKS
        }
        heads["plate"] = ParamSpec((d, len(PLATE_CHARS)), ("embed", None))
        c = self.STEM_CH
        spec = {
            "backbone": self.lm.spec(),
            # hybrid-ViT conv stem: two stride-2 convs (translation-
            # equivariant local features => sample-efficient glyph reading)
            "conv1": ParamSpec((3, 3, in_ch, c), (None, None, None, None)),
            "conv1_b": ParamSpec((c,), (None,), "zeros"),
            "conv2": ParamSpec((3, 3, c, c), (None, None, None, None)),
            "conv2_b": ParamSpec((c,), (None,), "zeros"),
            "patch_proj": ParamSpec((c * p * p, d), ("fsdp", "embed")),
            "patch_pos_emb": ParamSpec((max_patches, d), (None, "embed"),
                                       "small"),
            "task_tokens": ParamSpec((self.n_tasks, d), (None, "embed"),
                                     "small"),
            "heads": heads,
        }
        return spec

    def init(self, key: jax.Array, dtype=jnp.float32, in_ch: int = 3
             ) -> Dict[str, Any]:
        return materialize(self.spec(in_ch=in_ch), key, dtype)

    # ------------------------------------------------------------------
    def _stem(self, params, frames: jax.Array, dtype) -> jax.Array:
        """Conv stem: (B, C, h, w) -> (B, c, h/4, w/4)."""
        x = frames.astype(dtype).transpose(0, 2, 3, 1)       # NHWC
        for wk, bk in (("conv1", "conv1_b"), ("conv2", "conv2_b")):
            x = jax.lax.conv_general_dilated(
                x, params[wk].astype(dtype), (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jax.nn.relu(x + params[bk].astype(dtype))
        return x.transpose(0, 3, 1, 2)                       # NCHW

    def _patchify(self, feats: jax.Array) -> jax.Array:
        """feature map (B, C, h, w) -> (B, P, C·p·p) with p = patch//4."""
        b, c, h, w = feats.shape
        p = self.patch // 4
        assert h % p == 0 and w % p == 0, (h, w, p)
        x = feats.reshape(b, c, h // p, p, w // p, p)
        x = x.transpose(0, 2, 4, 1, 3, 5).reshape(b, (h // p) * (w // p),
                                                  c * p * p)
        return x

    def forward(self, params: Dict[str, Any], frames: jax.Array,
                dtype=jnp.float32) -> Dict[str, jax.Array]:
        """frames (B, C, h, w) float (preprocessed) -> task logits dict."""
        cfg = self.cfg
        b = frames.shape[0]
        feats = self._stem(params, frames, dtype)
        patches = self._patchify(feats)
        n_p = patches.shape[1]
        x_p = patches @ params["patch_proj"].astype(dtype)
        x_p = x_p + params["patch_pos_emb"][:n_p].astype(dtype)[None]
        x_t = jnp.broadcast_to(params["task_tokens"].astype(dtype)[None],
                               (b, self.n_tasks, cfg.d_model))
        x = jnp.concatenate([x_p, x_t], axis=1)
        positions = jnp.arange(x.shape[1])[None, :]
        bp = params["backbone"]
        x, _, _ = blk.apply_stack(cfg, self.lm.tp, bp["stack"], x,
                                  mode="causal", positions=positions,
                                  q_block=self.lm.q_block, remat=cfg.remat)
        x = apply_norm(bp["final_norm"], x, cfg.norm)
        task_h = x[:, n_p:, :]                       # (B, n_tasks, d)
        out = {}
        for i, name in enumerate(SCALAR_TASKS):
            logits = task_h[:, i] @ params["heads"][name].astype(dtype)
            out[name] = logits.astype(jnp.float32)
        plate_h = task_h[:, len(SCALAR_TASKS):]      # (B, PLATE_LEN, d)
        out["plate"] = (plate_h @ params["heads"]["plate"].astype(dtype)
                        ).astype(jnp.float32)        # (B, PLATE_LEN, 36)
        return out

    # ------------------------------------------------------------------
    def loss(self, params: Dict[str, Any], batch: Dict[str, jax.Array],
             dtype=jnp.float32) -> jax.Array:
        """Supervised multi-task loss on labeled frames."""
        out = self.forward(params, batch["frames"], dtype)
        total = jnp.zeros((), jnp.float32)
        mask_car = batch.get("mask_car")

        def ce(logits, labels, mask=None):
            lse = jax.nn.logsumexp(logits, -1)
            ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            nll = lse - ll
            if mask is not None:
                m = mask.astype(jnp.float32)
                while m.ndim < nll.ndim:
                    m = m[..., None]
                m = jnp.broadcast_to(m, nll.shape)
                return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
            return jnp.mean(nll)

        if "present" in batch:
            total += ce(out["present"], batch["present"])
        for key in ("color", "brand"):
            if key in batch:
                total += ce(out[key], batch[key], mask_car)
        if "plate" in batch:
            total += 2.0 * ce(out["plate"], batch["plate"], mask_car)
        for key in ("action", "n_jumping", "team"):
            if key in batch:
                total += ce(out[key], batch[key])
        return total

    def predict(self, out: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Logits -> integer predictions."""
        return {
            name: jnp.argmax(out[name], -1)
            for name in out
        }


def variant_models(ctx) -> Dict[str, Tuple["StreamMLLM", Any]]:
    """Physical-variant name -> (model, params) from an OpContext — THE
    resolution table, shared by ``MLLMExtractOp.open`` and the
    ``SharedExtractServer`` so the solo path and the server can never run
    different weights for the same variant string ("adaptive" is not a
    physical variant: the op's density tracker resolves it to big/pruned
    before any forward)."""
    return {
        "big": (ctx.mllm, ctx.mllm_params),
        "small": (ctx.mllm_small, ctx.mllm_small_params),
        "pruned": (ctx.mllm, ctx.mllm_pruned_params),
    }


@functools.partial(jax.jit, static_argnums=0)
def _extract(mllm: StreamMLLM, params, frames):
    x = frames.astype(jnp.float32)
    raw = x.reshape(x.shape[0], -1).max(axis=1) > 8.0
    x = jnp.where(raw[:, None, None, None], (x / 255.0 - 0.5) / 0.25, x)
    out = mllm.forward(params, x)
    return {k: jnp.argmax(v, -1) for k, v in out.items()}


def make_extract_fn(mllm: StreamMLLM, params):
    """Jitted batched union extract: frames -> argmax prediction per task.

    One forward computes *every* head (the union of any task subset costs
    the same as a single task), so callers serving heterogeneous task sets
    simply read the attributes they asked for.  Normalization is decided
    **per frame** (raw uint8-range vs already-normalized), not from the
    batch max: the SharedExtractServer coalesces frames from several
    streams — possibly at different preprocessing stages — into one padded
    forward, and each row must come out bitwise identical to a solo run.
    Zero padding rows classify as "normalized" and are sliced off by the
    caller, so they never perturb real rows.

    The weights are an argument of one jitted function keyed by the model
    object, not constants of a fresh closure: every operator and server
    that opens the same model (and the pruned variant, which shares it)
    reuses one compiled program per frame shape.
    """
    return functools.partial(_extract, mllm, params)


def distill_loss(student: StreamMLLM, teacher_out: Dict[str, jax.Array],
                 params, frames, temperature: float = 2.0) -> jax.Array:
    """Soft-label multi-head distillation (physical optimization)."""
    s_out = student.forward(params, frames)
    t = temperature
    total = jnp.zeros((), jnp.float32)
    for name in s_out:
        p_t = jax.nn.softmax(teacher_out[name] / t, -1)
        logp_s = jax.nn.log_softmax(s_out[name] / t, -1)
        total += -jnp.mean(jnp.sum(p_t * logp_s, -1)) * t * t
    return total
