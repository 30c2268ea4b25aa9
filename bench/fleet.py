"""The system under test, stood up from a cell's data files.

Weights come from the seed on the device; frames come from ``frames.py``;
plans come from the configuration file.  What the benchmark adds around
the program is observation only:

* ``PacedSource`` hands a feed's micro-batch over once its last frame is
  due (open loop at camera rate, at a fixed phase) and logs each hand-over;
* ``StampSink`` is the program's sink with a clock read per call;
* ``RecordingServer`` is the program's extract server keeping a
  reference to every request submitted inside the window.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import frames as framegen
import reference

MICRO_BATCH = 16            # MultiStreamRuntime's default
FLUSH_SHAPE = (1, 1, 1)     # frame shape of the runtime's end-of-stream batches


def _tuple(x):
    return tuple(_tuple(v) for v in x) if isinstance(x, list) else x


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words (numpy's SeedSequence entropy)."""
    words = []
    seed = int(seed)
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


class Clock:
    """Shared window clock: ``t0`` is stamped by the first paced pull."""

    def __init__(self):
        self.t0: Optional[int] = None
        self.trace = False


class PacedSource:
    """A feed at camera rate.  ``batch(n)`` blocks until the last of the
    ``n`` frames is due.  Before the first ``reset()`` (the runtime's
    warm-up, part of set-up) it hands frames over at once."""

    def __init__(self, frames: np.ndarray, fps: float, phase_s: float,
                 clock: Clock):
        self.frames = frames
        self.fps = fps
        self.phase_ns = int(phase_s * 1e9)
        self.clock = clock
        self.index = 0
        self.paced = False
        self.pulls: List[Tuple[int, int, int]] = []   # (t_ns, first, n)
        self._labels = [{}] * MICRO_BATCH

    def due_ns(self, idx) -> np.ndarray:
        """Due times (ns on ``perf_counter_ns``) of frame indices."""
        return self.clock.t0 + self.phase_ns + \
            (np.asarray(idx, np.float64) * (1e9 / self.fps)).astype(np.int64)

    def batch(self, n: int):
        i = self.index
        if self.paced:
            if self.clock.t0 is None:
                self.clock.t0 = time.perf_counter_ns()
                if self.clock.trace:
                    import jax
                    with jax.profiler.TraceAnnotation("bench:window_start"):
                        pass
            wait = int(self.due_ns(i + n - 1)) - time.perf_counter_ns()
            if wait > 0:
                if self.clock.trace:
                    import jax
                    with jax.profiler.TraceAnnotation("bench:await_frames"):
                        time.sleep(wait / 1e9)
                else:
                    time.sleep(wait / 1e9)
            self.pulls.append((time.perf_counter_ns(), i, n))
        self.index = i + n
        return self.frames[i:i + n], self._labels[:n]

    def reset(self) -> None:
        self.index = 0
        self.paced = True


def make_sink_class():
    from repro.streaming.operators import SinkOp

    @dataclasses.dataclass
    class StampSink(SinkOp):
        """The program's sink; each call also logs ``(t_ns, through the
        extract?, idx, window start of the op before it)``."""

        def __post_init__(self):
            super().__post_init__()
            self.log: Optional[list] = None
            self.window_op = None
            self.clock: Optional[Clock] = None

        def process(self, batch):
            t = time.perf_counter_ns()
            out = super().process(batch)
            if self.log is not None and self.clock.t0 is not None and \
                    tuple(batch["frames"].shape[1:]) != FLUSH_SHAPE:
                ws = self.window_op._window_start \
                    if self.window_op is not None else 0
                self.log.append((t, "attrs" in batch, batch["idx"], ws))
            return out

    return StampSink


def make_server_class():
    from repro.scheduler.extract_server import SharedExtractServer

    class RecordingServer(SharedExtractServer):
        """The program's server; requests submitted once the window clock
        runs are kept for the correctness check."""

        clock: Optional[Clock] = None

        def submit(self, variant, frames, feed="", sig=None):
            req = super().submit(variant, frames, feed=feed, sig=sig)
            if self.clock is not None and self.clock.t0 is not None:
                self.requests.append((feed, req))
            return req

    return RecordingServer


# ------------------------------------------------------------------ models
def arch_config(spec: Dict[str, Any]):
    """A dense GQA ``backbone`` entry as the program takes it (exported by
    the configuration modules of the dense backbones)."""
    from repro.common.config import ArchConfig, AttentionConfig
    kw = dict(spec)
    kw["attention"] = AttentionConfig(**kw["attention"])
    kw["block_pattern"] = tuple(kw.get("block_pattern", ("attn+dense",)))
    return ArchConfig(**kw)


def fan_in(name: str, shape) -> int:
    """Inputs summed into each output of a weight (LeCun fan-in): the
    attention projections contract over the model width (``wq``/``wk``/
    ``wv``: ``(d, heads, head_dim)``) or over heads × head_dim (``wo``),
    a conv over its window × input channels, every other matrix over its
    second-to-last axis.  Stacked layers add a leading axis.  The rule of
    the dense GQA backbone, exported by its configuration modules."""
    if name in ("wq", "wk", "wv"):
        return shape[-3]
    if name == "wo":
        return shape[-3] * shape[-2]
    if name.startswith("conv") and len(shape) == 4:
        return shape[0] * shape[1] * shape[2]
    return shape[-2] if len(shape) >= 2 else shape[-1]


def _init_leaf(name: str, p, key, dtype, fan_in):
    import jax
    import jax.numpy as jnp
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "small":
        std = 0.02 * p.scale
    elif p.init == "normal":
        std = p.scale
    else:
        std = p.scale / np.sqrt(max(fan_in(name, p.shape), 1))
    return jax.random.normal(key, p.shape, dtype) * jnp.asarray(std, dtype)


def init_weights(mllm, max_patches: int, seed: int, salt: int, dtype,
                 fan_in):
    """Every weight the extract reads, on the device, in one jitted call
    from the seed (the backbone's token embedding and LM head are not
    held: the extract reads neither).  Normal with LeCun fan-in scaling
    (``fan_in(name, shape)``, the configuration's own), so activations
    and attention scores keep unit scale."""
    import jax
    from repro.models.param import ParamSpec

    spec = mllm.spec(max_patches=max_patches)
    spec["backbone"] = {k: v for k, v in spec["backbone"].items()
                        if k != "embed"}
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=lambda x: isinstance(x, ParamSpec))
    names = [str(path[-1].key) for path, _ in paths]
    leaves = [leaf for _, leaf in paths]
    k32 = int(np.random.SeedSequence(seed_words(seed) + [salt])
              .generate_state(1)[0] & 0x7FFFFFFF)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(n, p, k, dtype, fan_in)
                      for n, p, k in zip(names, leaves, keys)])

    return make(jax.random.key(k32))


def lower_precision_extract(ctx):
    """The control of ``correct``: the program's own bfloat16 path of the
    extract (``StreamMLLM.forward`` with ``dtype=bfloat16``: bfloat16
    weights and activations) in place of the served float32 one, with the
    served path's input handling and argmax.  ``picks(variant, rows)``
    gives the answers per task for the rows the served extract read."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.streaming.mllm import variant_models

    @functools.partial(jax.jit, static_argnums=0)
    def extract_bf16(mllm, params, frames):
        x = frames.astype(jnp.float32)
        raw = x.reshape(x.shape[0], -1).max(axis=1) > 8.0
        x = jnp.where(raw[:, None, None, None], (x / 255.0 - 0.5) / 0.25, x)
        out = mllm.forward(params, x, jnp.bfloat16)
        return {k: jnp.argmax(v, -1) for k, v in out.items()}

    table = variant_models(ctx)

    def picks(variant: str, rows: np.ndarray) -> Dict[str, np.ndarray]:
        mllm, params = table[variant]
        out = extract_bf16(mllm, params, jnp.asarray(rows))
        return {k: np.asarray(v) for k, v in out.items()}

    return picks


# ------------------------------------------------------------------ fleet
@dataclasses.dataclass
class FeedSpec:
    name: str
    template: str
    stream: str
    fps: float
    queries: List[str]
    frames: np.ndarray
    phase_s: float
    ref_keep: Optional[np.ndarray] = None    # reference Skip's keep mask


def make_feeds(cell: Dict[str, Any], seed: int, seconds: float
               ) -> List[FeedSpec]:
    """The cell's feeds with their frames for the whole window."""
    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    templates = {t["name"]: t for t in cfg["feed_templates"]}
    words = seed_words(seed)
    pools: Dict[str, np.ndarray] = {}
    out = []
    for k in range(int(cell["feeds"])):
        t = templates[mix["templates"][k % len(mix["templates"])]]
        stream = cfg["streams"][t["stream"]]
        fps = float(stream["fps"])
        n = int(np.ceil(seconds * fps / MICRO_BATCH)) * MICRO_BATCH
        entropy = words + [k]
        if t["stream"] not in pools:
            pools[t["stream"]] = framegen.noise_pool(
                np.random.default_rng(words + [1 << 20, len(pools)]),
                stream["noise"])
        params = dict(mix.get(t["stream"], {}))
        if t["stream"] == "tollbooth":
            fr = framegen.tollbooth(n, entropy, pools["tollbooth"],
                                    schedule=[k, int(1e6 * params[
                                        "car_rate"])], **params)
        else:
            fr = framegen.volleyball(n, entropy, pools[t["stream"]], **params)
        # arrivals are part of the mix, the same for every seed: phases
        # spread evenly over one micro-batch period
        phase = (k + 0.5) / int(cell["feeds"]) * MICRO_BATCH / fps
        out.append(FeedSpec(f"{t['name']}-{k}", t["name"], t["stream"], fps,
                            list(t["queries"]), fr, float(phase)))
    return out


def reference_keep(cfg: Dict[str, Any], feed: FeedSpec) -> np.ndarray:
    keep = np.ones(len(feed.frames), bool)
    for op in cfg["prefix"][feed.stream]:
        if op["op"] == "skip":
            keep &= reference.skip_keep(feed.frames, op["amount"],
                                        op["threshold"], op.get("roi"),
                                        tuple(op["regions"]))
    return keep


def build_plan(cfg: Dict[str, Any], stream: str, qid: str, sink_cls):
    from repro.streaming.operators import (FilterOp, FusedPreprocessOp,
                                           MLLMExtractOp, SkipOp, SourceOp,
                                           WindowAggOp)
    from repro.streaming.plan import Plan
    q = cfg["queries"][qid]
    ops = [SourceOp(stream)]
    for op in cfg["prefix"][stream]:
        if op["op"] == "skip":
            ops.append(SkipOp(amount=op["amount"], condition=op["condition"],
                              threshold=op["threshold"],
                              roi=_tuple(op.get("roi")),
                              regions=_tuple(op["regions"])))
        elif op["op"] == "fused_preprocess":
            ops.append(FusedPreprocessOp(crop=_tuple(op["crop"]),
                                         factor=op["factor"]))
        else:
            raise ValueError(op)
    ops.append(MLLMExtractOp(tasks=tuple(q["tasks"]), model=q["variant"]))
    for op in q["tail"]:
        if op["op"] == "filter":
            ops.append(FilterOp(pred=_tuple(op["pred"])))
        elif op["op"] == "window":
            ops.append(WindowAggOp(kind=op["kind"], window=op["size"]))
        else:
            raise ValueError(op)
    ops.append(sink_cls())
    return Plan(ops, query=qid)


@dataclasses.dataclass
class Fleet:
    runtime: Any
    server: Any
    sources: Dict[str, PacedSource]
    sinks: Dict[Tuple[str, str], Any]       # (feed, query) -> StampSink
    clock: Clock


def build(cell: Dict[str, Any], ctx, feeds: List[FeedSpec]) -> Fleet:
    from repro.scheduler import Feed, MultiStreamRuntime
    from repro.streaming.operators import WindowAggOp

    cfg = cell["config_spec"]
    sink_cls = make_sink_class()
    server_cls = make_server_class()
    clock = Clock()
    sources = {f.name: PacedSource(f.frames, f.fps, f.phase_s, clock)
               for f in feeds}
    rfeeds = [Feed(f.name, sources[f.name],
                   [build_plan(cfg, f.stream, q, sink_cls)
                    for q in f.queries]) for f in feeds]
    run_ctx = dataclasses.replace(ctx, micro_batch=MICRO_BATCH)
    server = server_cls(run_ctx)
    server.requests = []
    runtime = MultiStreamRuntime(rfeeds, ctx, server=server)
    sinks = {}
    for name, forest in runtime.forests.items():
        for g in forest.groups():
            for qid, tail in zip(g.execution.queries, g.execution.tails):
                sink = tail[-1]
                sink.log = []
                sink.clock = clock
                if len(tail) > 1 and isinstance(tail[-2], WindowAggOp):
                    sink.window_op = tail[-2]
                sinks[(name, qid)] = sink
    server.clock = clock
    return Fleet(runtime, server, sources, sinks, clock)
