"""Train the streaming operator models on the synthetic labeled streams.

Produces (and caches) the OpContext every plan runs with:
  * big StreamMLLM  — trained supervised on mixed preprocessing configs
    (full frame / crop / crop+downscale) so it stays accurate under any plan;
  * small StreamMLLM — *distilled* from the big one on the optimized
    preprocessing (the paper's model-specialization path);
  * pruned params    — structured head/FFN pruning of the big model
    (adaptive pruning's static half; rate selection is runtime);
  * TinyDet          — the cascade detector.

This is the offline "super-optimization pays off because queries are
long-running" investment the paper argues for.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import enable_compile_cache
from repro.configs import get_config
from repro.data.tollbooth import (BRANDS, COLORS, PLATE_CHARS,
                                  TollBoothStream)
from repro.data.volleyball import ACTIONS, VolleyballStream
from repro.streaming.detector import TinyDet
from repro.streaming.mllm import MLLM_TASKS, PLATE_LEN, StreamMLLM
from repro.streaming.operators import OpContext
from repro.training.checkpoint import CheckpointManager
from repro.training.optimizer import OptimizerConfig, adamw_init, adamw_update

CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         ".cache", "stream_models")

PATCH = 16
CROP = (64, 0, 64, 256)      # road region


# ---------------------------------------------------------------------------
# label encoding
# ---------------------------------------------------------------------------

def encode_tollbooth_labels(labels) -> Dict[str, np.ndarray]:
    n = len(labels)
    out = {
        "present": np.zeros(n, np.int32),
        "color": np.zeros(n, np.int32),
        "brand": np.zeros(n, np.int32),
        "plate": np.zeros((n, PLATE_LEN), np.int32),
        "mask_car": np.zeros(n, np.float32),
    }
    for i, l in enumerate(labels):
        out["present"][i] = int(bool(l["car_present"]))
        if l.get("car_readable"):
            out["mask_car"][i] = 1.0
            out["color"][i] = COLORS.index(l["color"])
            out["brand"][i] = BRANDS.index(l["brand"])
            out["plate"][i] = [PLATE_CHARS.index(c) for c in l["plate"]]
    return out


def encode_volleyball_labels(labels) -> Dict[str, np.ndarray]:
    n = len(labels)
    return {
        "action": np.asarray([ACTIONS.index(l["action"]) for l in labels],
                             np.int32),
        "n_jumping": np.asarray([min(l["n_jumping"], 6) for l in labels],
                                np.int32),
        "team": np.asarray([l["attack_team"] for l in labels], np.int32),
    }


def preprocess_np(frames: np.ndarray, crop=None, factor: int = 1
                  ) -> np.ndarray:
    x = frames.astype(np.float32)
    if crop is not None:
        y0, x0, h, w = crop
        x = x[:, :, y0:y0 + h, x0:x0 + w]
    if factor > 1:
        b, c, h, w = x.shape
        x = x.reshape(b, c, h // factor, factor, w // factor, factor
                      ).mean(axis=(3, 5))
    return (x / 255.0 - 0.5) / 0.25


# ---------------------------------------------------------------------------
# training loops (simple, jitted per input shape)
# ---------------------------------------------------------------------------

def _train(model_loss, params, batches, steps, lr=1e-3, log_every=50,
           label=""):
    opt_cfg = OptimizerConfig(lr=lr, warmup_steps=20, total_steps=steps,
                              weight_decay=0.01)
    state = adamw_init(params, opt_cfg)

    @jax.jit
    def step_fn(params, state, batch):
        loss, grads = jax.value_and_grad(model_loss)(params, batch)
        params, state, m = adamw_update(params, grads, state, opt_cfg)
        return params, state, loss

    losses = []
    for i in range(steps):
        batch = batches(i)
        params, state, loss = step_fn(params, state, batch)
        losses.append(float(loss))
        if log_every and (i + 1) % log_every == 0:
            print(f"  [{label}] step {i+1}/{steps} "
                  f"loss={np.mean(losses[-log_every:]):.4f}")
    return params, losses


def _make_mllm_batches(seed: int, batch: int = 16):
    """Mixed tollbooth/volleyball batches under mixed preprocessing.

    Booth-shot batches (every frame readable) carry the OCR signal; natural
    batches calibrate presence/empty statistics; mixed crops/downscales keep
    the operator accurate under any plan the optimizer produces.
    """
    tb = TollBoothStream(seed=seed, car_rate=0.03)
    vb = VolleyballStream(seed=seed)

    def gen(i: int):
        mode = i % 6
        if mode in (0, 1, 3):          # booth shots (plate/color/brand)
            frames, labels = tb.booth_batch(batch)
            enc = encode_tollbooth_labels(labels)
            crop, factor = (CROP, 1) if mode != 1 else (CROP, 2)
            x = preprocess_np(frames, crop, factor)
        elif mode == 2:                # natural full frame (naive plan)
            frames, labels = tb.batch(batch)
            enc = encode_tollbooth_labels(labels)
            x = preprocess_np(frames, None, 1)
        elif mode == 4:                # natural cropped
            frames, labels = tb.batch(batch)
            enc = encode_tollbooth_labels(labels)
            x = preprocess_np(frames, CROP, 1)
        else:                          # volleyball
            frames, labels = vb.batch(batch)
            enc = encode_volleyball_labels(labels)
            x = preprocess_np(frames, None, 2)
        b = {"frames": jnp.asarray(x)}
        b.update({k: jnp.asarray(v) for k, v in enc.items()})
        return b

    return gen


def quick_stream_models(verbose: bool = False) -> OpContext:
    """Tiny, un-cached stream models for smoke runs: enough to exercise
    every code path in seconds (accuracy is the full training's job) — the
    configuration examples use under ``--quick`` and the test suite's
    session fixture uses throughout."""
    return train_stream_models(steps_mllm=40, steps_small=20, steps_det=30,
                               cache_dir=None, verbose=verbose)


def stream_models(quick: bool = False) -> OpContext:
    """The examples' single entry point: cached full-quality stream
    models, or the tiny un-cached quick set under ``--quick`` (CI smoke).
    One implementation so the quick-mode setup cannot drift between
    examples; it also turns on the persistent compilation cache."""
    enable_compile_cache()
    if quick:
        print("quick mode: training tiny stream models…")
        return quick_stream_models(verbose=False)
    print("loading/training stream operator models (cached after "
          "first run)…")
    return train_stream_models(verbose=True)


def train_stream_models(steps_mllm: int = 1600, steps_small: int = 500,
                        steps_det: int = 250, seed: int = 0,
                        cache_dir: Optional[str] = CACHE_DIR,
                        force: bool = False, verbose: bool = True
                        ) -> OpContext:
    """Train (or load cached) streaming models; returns a ready OpContext."""
    big_cfg = get_config("samsara-stream-mllm")
    small_cfg = get_config("samsara-stream-mllm-small")
    mllm = StreamMLLM(big_cfg, patch=PATCH)
    small = StreamMLLM(small_cfg, patch=PATCH)
    det = TinyDet()

    ck = CheckpointManager(cache_dir, keep=1) if cache_dir else None
    if ck is not None and not force and ck.latest_step() is not None:
        tree = ck.restore(ck.latest_step())
        if verbose:
            print("[pretrain] loaded cached stream models")
        return OpContext(
            mllm=mllm, mllm_params=tree["mllm"],
            mllm_small=small, mllm_small_params=tree["small"],
            mllm_pruned_params=tree["pruned"],
            detector=det, detector_params=tree["det"])

    log = 50 if verbose else 0
    # ---- big MLLM ----
    params = mllm.init(jax.random.PRNGKey(seed))
    gen = _make_mllm_batches(seed)
    params, _ = _train(lambda p, b: mllm.loss(p, b), params, gen,
                       steps_mllm, lr=1e-3, log_every=log, label="mllm")

    # ---- distilled small MLLM (physical optimization) ----
    sparams = small.init(jax.random.PRNGKey(seed + 1))
    tb = TollBoothStream(seed=seed + 7, car_rate=0.04)
    vb = VolleyballStream(seed=seed + 7)

    @jax.jit
    def teacher_fwd(frames):
        return mllm.forward(params, frames)

    def distill_batches(i: int):
        if i % 3 < 2:
            frames, labels = tb.booth_batch(16) if i % 3 == 0 \
                else tb.batch(16)
            x = preprocess_np(frames, CROP, 2)      # the optimized preproc
            enc = encode_tollbooth_labels(labels)
        else:
            frames, labels = vb.batch(16)
            x = preprocess_np(frames, None, 2)
            enc = encode_volleyball_labels(labels)
        xj = jnp.asarray(x)
        t_out = teacher_fwd(xj)
        b = {"frames": xj,
             "teacher": {k: jax.lax.stop_gradient(v)
                         for k, v in t_out.items()}}
        b.update({k: jnp.asarray(v) for k, v in enc.items()})
        return b

    def distill_loss(p, b):
        s_out = small.forward(p, b["frames"])
        total = jnp.zeros((), jnp.float32)
        for name in s_out:
            p_t = jax.nn.softmax(b["teacher"][name] / 2.0, -1)
            logp = jax.nn.log_softmax(s_out[name] / 2.0, -1)
            total += -jnp.mean(jnp.sum(p_t * logp, -1)) * 4.0
        return total + 0.5 * small.loss(p, {k: v for k, v in b.items()
                                            if k != "teacher"})

    sparams, _ = _train(distill_loss, sparams, distill_batches, steps_small,
                        lr=1e-3, log_every=log, label="distill")

    # ---- structured pruning of the big model (adaptive pruning, static half)
    from repro.core.physical import structured_prune

    pruned = structured_prune(mllm, params, rate=0.5)

    # ---- TinyDet ----
    dparams = det.init(jax.random.PRNGKey(seed + 2))
    tb2 = TollBoothStream(seed=seed + 13, car_rate=0.02)

    def det_batches(i: int):
        frames, labels = tb2.batch(16)
        x = preprocess_np(frames, CROP, 2)
        return {"frames": jnp.asarray(x),
                "present": jnp.asarray(
                    [int(l["car_present"]) for l in labels], jnp.int32)}

    dparams, _ = _train(lambda p, b: det.loss(p, b), dparams, det_batches,
                        steps_det, lr=2e-3, log_every=log, label="tinydet")

    if ck is not None:
        ck.save(1, {"mllm": params, "small": sparams, "pruned": pruned,
                    "det": dparams})
    return OpContext(
        mllm=mllm, mllm_params=params,
        mllm_small=small, mllm_small_params=sparams,
        mllm_pruned_params=pruned,
        detector=det, detector_params=dparams)
