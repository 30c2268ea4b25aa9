"""Plain reference of what a served frame should produce.

Independent of the program: it imports nothing from ``src/repro`` and is
written from the operator and model descriptions:

* Skip: per-region mean absolute difference of a frame against the frame
  pulled just before it, inside the region of interest; a frame whose most
  active region stays under the threshold is empty, and the ``amount``
  frames after an empty frame are dropped unseen.  The first frame of a
  stream is always kept.
* Fused preprocess: crop, scale to [0, 1], mean-pool ``factor``², then
  normalize with mean 0.5 and std 0.25 per channel.
* Extract: conv stem (two 3×3 stride-2 convs with ReLU), 2×2 patches of
  the stem's map projected to the model width, learned patch positions,
  12 learned task tokens after the patches, a causal decoder of
  pre-RMSNorm blocks (grouped-query attention with rotary positions, a
  SiLU-gated MLP), a final RMSNorm, and one linear head per task token.
* Tails: equality / prefix / and predicates on the extracted attributes,
  and tumbling windows by frame index that close when a later record
  arrives and flush a partial window at the end of the stream.

``Reference`` computes in float32 at the highest matmul precision.  It is
the dense GQA decoder's reference: a configuration's module
(``configs/<config>.py: reference``) names it, or brings its own.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

SCALAR_TASKS = ("present", "color", "brand", "action", "n_jumping", "team")
PLATE_LEN = 6
COLORS = ["red", "blue", "green", "white", "black", "yellow"]
BRANDS = ["astra", "bolt", "cresta", "dyno", "evora", "falcon"]
ACTIONS = ["idle", "pass", "set", "spike"]
PLATE_CHARS = "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
NORM_EPS = 1e-6


# ---------------------------------------------------------------- prefix
def region_activity(cur: np.ndarray, prev: np.ndarray,
                    regions: Tuple[int, int]) -> np.ndarray:
    """(n, ry, rx) mean |cur - prev| / 255 per region, from exact integer
    sums."""
    n, c, h, w = cur.shape
    ry, rx = regions
    d = np.abs(cur.astype(np.int16) - prev.astype(np.int16))
    s = d.reshape(n, c, ry, h // ry, rx, w // rx).sum(axis=(1, 3, 5),
                                                      dtype=np.int64)
    return s.astype(np.float64) / (255.0 * c * (h // ry) * (w // rx))


def skip_keep(frames: np.ndarray, amount: int, threshold: float,
              roi: Optional[Sequence[int]], regions: Tuple[int, int]
              ) -> np.ndarray:
    """Keep mask of a whole stream under Skip(amount, no_car)."""
    n, _, h, w = frames.shape
    ry, rx = regions
    rh, rw = h // ry, w // rx
    r0, r1, c0, c1 = 0, ry, 0, rx
    if roi is not None:                  # only the regions the roi touches
        y0, x0, hh, ww = roi
        r0, r1 = y0 // rh, (y0 + hh + rh - 1) // rh
        c0, c1 = x0 // rw, (x0 + ww + rw - 1) // rw
    part = frames[:, :, r0 * rh:r1 * rh, c0 * rw:c1 * rw]
    prev = np.concatenate([part[:1], part[:-1]])
    act = region_activity(part, prev, (r1 - r0, c1 - c0))
    act = act.reshape(n, -1).max(axis=1)
    keep = np.ones(n, bool)
    left = 0
    for i in range(1, n):
        if left > 0:
            left -= 1
            keep[i] = False
        elif act[i] < threshold:
            keep[i] = False
            left = amount
    return keep


def preprocess(frames: np.ndarray, crop: Sequence[int], factor: int,
               dtype=np.float32) -> np.ndarray:
    y0, x0, ch, cw = crop
    n, c = frames.shape[:2]
    x = frames[:, :, y0:y0 + ch, x0:x0 + cw].astype(np.float64) / 255.0
    x = x.reshape(n, c, ch // factor, factor, cw // factor, factor)
    x = (x.mean(axis=(3, 5)) - 0.5) / 0.25
    return x.astype(dtype)


def preprocess_lower(frames: np.ndarray, crop: Sequence[int], factor: int
                     ) -> np.ndarray:
    """The control: ``preprocess`` computed in bfloat16."""
    import jax.numpy as jnp
    y0, x0, ch, cw = crop
    n, c = frames.shape[:2]
    x = jnp.asarray(frames[:, :, y0:y0 + ch, x0:x0 + cw], jnp.bfloat16)
    x = x / jnp.bfloat16(255.0)
    x = x.reshape(n, c, ch // factor, factor, cw // factor, factor)
    x = (x.mean(axis=(3, 5)) - jnp.bfloat16(0.5)) / jnp.bfloat16(0.25)
    return np.asarray(x.astype(jnp.float32))


# ---------------------------------------------------------------- model
def _rms(x, scale, jnp):
    x32 = x.astype(jnp.float32)
    y = x32 * (1.0 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                              + NORM_EPS))
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta, jnp):
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def make_layer(arch: Dict[str, Any]):
    """One decoder block, jitted, reading layer ``i`` of the stacked
    weights."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.float32

    att = arch["attention"]
    hq, hkv, hd = att["n_heads"], att["n_kv_heads"], att["head_dim"]
    theta = att.get("rope_theta", 10000.0)

    @jax.jit
    def layer(stack, i, x):
        p = jax.tree_util.tree_map(lambda a: a[i].astype(dtype), stack)
        b, s, _ = x.shape
        h = _rms(x, p["pre_norm"]["scale"], jnp)
        mx = p["mixer"]
        q = jnp.einsum("bsd,dhk->bshk", h, mx["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, mx["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, mx["wv"])
        q, k = _rope(q, theta, jnp), _rope(k, theta, jnp)
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)           # query head j reads kv j//rep
        v = jnp.repeat(v, rep, axis=2)
        logits = jnp.einsum("bshk,bthk->bhst", q, k) / np.sqrt(hd)
        causal = np.tril(np.ones((s, s), bool))
        logits = jnp.where(causal[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        o = jnp.einsum("bhst,bthk->bshk", probs, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, mx["wo"])
        h = _rms(x, p["pre_mlp_norm"]["scale"], jnp)
        m = p["mlp"]
        u = jax.nn.silu(h @ m["w_in"]) * (h @ m["w_gate"])
        return x + u @ m["w_out"]

    return layer


def make_frontend(arch: Dict[str, Any], patch: int):
    import jax
    import jax.numpy as jnp

    dtype = jnp.float32

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w.astype(dtype), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + b.astype(dtype))

    @jax.jit
    def embed(params, frames):
        x = frames.astype(dtype).transpose(0, 2, 3, 1)
        x = conv(x, params["conv1"], params["conv1_b"])
        x = conv(x, params["conv2"], params["conv2_b"])
        b, hh, ww, c = x.shape
        p = patch // 4
        x = x.reshape(b, hh // p, p, ww // p, p, c)
        x = x.transpose(0, 1, 3, 5, 2, 4).reshape(b, -1, c * p * p)
        x = x @ params["patch_proj"].astype(dtype)
        x = x + params["patch_pos_emb"][:x.shape[1]].astype(dtype)[None]
        t = jnp.broadcast_to(params["task_tokens"].astype(dtype)[None],
                             (b,) + params["task_tokens"].shape)
        return jnp.concatenate([x, t], axis=1)

    @jax.jit
    def heads(params, x, final_scale):
        x = _rms(x, final_scale, jnp)
        n_t = len(SCALAR_TASKS) + PLATE_LEN
        th = x[:, x.shape[1] - n_t:]
        out = {name: th[:, i] @ params["heads"][name].astype(dtype)
               for i, name in enumerate(SCALAR_TASKS)}
        out["plate"] = th[:, len(SCALAR_TASKS):] @ \
            params["heads"]["plate"].astype(dtype)
        return out

    return embed, heads


class Reference:
    """The extract's logits for preprocessed frames, layer by layer."""

    def __init__(self, arch: Dict[str, Any], patch: int):
        self.arch = arch
        self.layer = make_layer(arch)
        self.embed, self.heads = make_frontend(arch, patch)

    def logits(self, params, frames: np.ndarray, block: int = 16
               ) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        outs: List[Dict[str, np.ndarray]] = []
        n = len(frames)
        with jax.default_matmul_precision("highest"):
            for a in range(0, n, block):
                chunk = frames[a:a + block]
                pad = block - len(chunk)
                if pad:
                    chunk = np.concatenate(
                        [chunk, np.zeros((pad,) + chunk.shape[1:],
                                         chunk.dtype)])
                x = self.embed(params, jnp.asarray(chunk))
                # one block kind per period: the stack's only entry "i0"
                (stack,) = params["backbone"]["stack"].values()
                for i in range(self.arch["n_layers"]):
                    x = self.layer(stack, i, x)
                o = self.heads(params, x,
                               params["backbone"]["final_norm"]["scale"])
                outs.append({k: np.asarray(v)[:block - pad]
                             for k, v in o.items()})
        return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}


def widest_gap(logits: Dict[str, np.ndarray], served: Dict[str, np.ndarray],
               tasks: Sequence[str]) -> float:
    """Largest amount by which a served answer's reference logit lies
    below the reference's best, over the rows and the given heads."""
    g = 0.0
    for t in tasks:
        lg = logits[t]
        pick = np.take_along_axis(lg, np.asarray(served[t])[..., None],
                                  -1)[..., 0]
        g = max(g, float((lg.max(-1) - pick).max(initial=0.0)))
    return g


# ---------------------------------------------------------------- tails
def predicate(pred, attrs: Dict[str, np.ndarray], n: int) -> np.ndarray:
    kind = pred[0]
    if kind in ("and", "or"):
        a, b = predicate(pred[1], attrs, n), predicate(pred[2], attrs, n)
        return a & b if kind == "and" else a | b
    _, field, val = pred
    if kind == "eq":
        vocab = {"color": COLORS, "brand": BRANDS, "action": ACTIONS}
        want = vocab[field].index(val) if isinstance(val, str) else val
        return np.asarray(attrs[field]) == want
    if kind == "prefix":
        chars = np.asarray(attrs[field])
        ok = np.ones(n, bool)
        for i, ch in enumerate(val):
            ok &= chars[:, i] == PLATE_CHARS.index(ch)
        return ok
    raise ValueError(pred)


def aggregate(kind: str, recs: List[Dict[str, Any]], w0: int, w1: int
              ) -> Dict[str, Any]:
    res: Dict[str, Any] = {"window": (w0, w1), "kind": kind, "n": len(recs)}
    if kind == "top_color":
        c = Counter(int(r["color"]) for r in recs)
        res["top_color"] = COLORS[c.most_common(1)[0][0]] if c else None
    elif kind == "top_brand":
        c = Counter(int(r["brand"]) for r in recs)
        res["top_brand"] = BRANDS[c.most_common(1)[0][0]] if c else None
    elif kind == "count_distinct_plates":
        res["distinct_plates"] = len({tuple(int(x) for x in r["plate"])
                                      for r in recs})
    elif kind == "top3_actions":
        c = Counter(int(r["action"]) for r in recs)
        res["top3"] = [ACTIONS[a] for a, _ in c.most_common(3)]
    else:
        raise ValueError(kind)
    return res


def tumbling(kind: str, size: int, recs: List[Dict[str, Any]]
             ) -> List[Dict[str, Any]]:
    """Windows by frame index over records in stream order."""
    out, buf, start = [], [], 0
    for r in recs:
        while r["idx"] >= start + size:
            out.append(aggregate(kind, [b for b in buf
                                        if b["idx"] < start + size],
                                 start, start + size))
            buf = [b for b in buf if b["idx"] >= start + size]
            start += size
        buf.append(r)
    if buf:
        res = aggregate(kind, buf, start, start + size)
        res["partial"] = True
        out.append(res)
    return out
