"""One run of one cell: set-up, the measured window, metrics, the check.

``run()`` is what ``bench/run.py`` calls.  Its keyword ``require_tpu``
exists for the CPU tests, which drive a whole run on a tiny configuration
without a chip; ``hooks`` lets a test break the timed path underneath.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import check  # noqa: E402
import fleet as fleetlib  # noqa: E402
import layout  # noqa: E402
import trace_reduce  # noqa: E402
import window as windowlib  # noqa: E402

CACHE_DIR = os.path.join(layout.ROOT, ".cache", "jax_compile")
TRACE_DIR = os.path.join(layout.ROOT, ".cache", "bench_trace")
BUCKETS = (4, 8, 16, 32, 64)        # SharedExtractServer's shape buckets


class NoDevice(RuntimeError):
    pass


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _setup_jax(require_tpu: bool, chips: int):
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"needs {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {devs[0].platform} device(s)")
    return jax, devs[:chips]


class CompileLog:
    """Times of every jitted program built (traced and lowered) or loaded,
    between ``__enter__`` and ``__exit__``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __enter__(self) -> "CompileLog":
        import jax
        self.times: List[int] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, _dur: float, **_kw) -> None:
        if event == self.EVENT:
            self.times.append(time.perf_counter_ns())

    def between(self, a: int, b: int) -> int:
        return sum(1 for t in self.times if a <= t <= b)


def make_context(cfg: Dict[str, Any], seed: int, model):
    """The program's extract context and its seeded weights per variant;
    ``model`` is the configuration's module (``layout.model``), which
    gives the backbone and the fan-in of its weights."""
    import jax.numpy as jnp
    from repro.streaming.mllm import StreamMLLM
    from repro.streaming.operators import OpContext

    dtype = jnp.bfloat16 if cfg["weights_dtype"] == "bfloat16" \
        else jnp.float32
    models, params = {}, {}
    for salt, (variant, spec) in enumerate(sorted(cfg["backbone"].items())):
        m = StreamMLLM(model.arch_config(spec), patch=cfg["patch"])
        models[variant] = m
        params[variant] = fleetlib.init_weights(m, cfg["max_patches"], seed,
                                                salt, dtype, model.fan_in)
    ctx = OpContext(mllm=models.get("big"), mllm_params=params.get("big"),
                    mllm_small=models.get("small"),
                    mllm_small_params=params.get("small"),
                    frame_shape=tuple(cfg["frame"]))
    return ctx, params


def warm(fl, cfg, feeds) -> None:
    """Compile every program the window will run: each prefix op at each
    batch size its feed's frames give, and each extract program
    (variant, frame shape, bucket)."""
    from repro.streaming.operators import FusedPreprocessOp, SkipOp
    mb = fleetlib.MICRO_BATCH
    shape = tuple(cfg["frame"])
    for f in feeds:
        counts = {int(f.ref_keep[i:i + mb].sum())
                  for i in range(0, len(f.frames), mb)} - {0}
        for g in fl.runtime.forests[f.name].groups():
            for op in g.execution.prefix:
                if isinstance(op, SkipOp):
                    op.process({"frames": np.zeros((mb,) + shape, np.uint8),
                                "idx": np.arange(mb)})
                elif isinstance(op, FusedPreprocessOp):
                    for n in sorted(counts):
                        op.process({"frames": np.zeros((n,) + shape,
                                                       np.uint8),
                                    "idx": np.arange(n)})
    variants = {cfg["queries"][q]["variant"] for f in feeds
                for q in f.queries}
    shapes = set()
    for f in feeds:
        pre = [op for op in cfg["prefix"][f.stream]
               if op["op"] == "fused_preprocess"][0]
        y0, x0, h, w = pre["crop"]
        shapes.add((shape[0], h // pre["factor"], w // pre["factor"]))
    for v in sorted(variants):
        for s in sorted(shapes):
            for b in BUCKETS:
                fl.server.submit(v, np.zeros((b,) + s, np.float32))
                fl.server.drain()


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_process_ns: int, require_tpu: bool = True,
        hooks: Optional[Dict[str, Callable]] = None,
        spec: Optional[Dict[str, Any]] = None,
        control: bool = False,
        backlog: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """``cell`` as ``layout.cell`` returns it; ``spec`` is the parsed
    ``BENCHMARK.json`` (read from the checkout by default).  ``control``
    puts the lower-precision control in the program's place for the check
    (``fleet.lower_precision_extract`` for the extract, a bfloat16
    preprocess): ``compared`` and ``correct`` are then the control's,
    under the same limits, and ``program`` holds the program's own
    numbers.  ``backlog``, where given, receives the ingest-lag trend that
    ``sweep.py`` reads."""
    hooks = hooks or {}
    spec = spec or layout.benchmark()
    cfg = cell["config_spec"]
    jax, devs = _setup_jax(require_tpu, int(cell.get("chips", 1)))
    peak = layout.peaks(devs[0].device_kind) if require_tpu else \
        {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    with CompileLog() as compiles:
        return _run(cell, cfg, seed, seconds, trace, t_process_ns, jax, devs,
                    peak, compiles, hooks, spec, control, backlog)


def _run(cell, cfg, seed, seconds, trace, t_process_ns, jax, devs, peak,
         compiles, hooks, spec, control, backlog):
    cell_name = cell["name"]
    kind = devs[0].device_kind
    t = time.perf_counter()
    ctx, params = make_context(cfg, seed, cell["model"])
    jax.block_until_ready(params)
    t_w = time.perf_counter() - t
    t = time.perf_counter()
    feeds = fleetlib.make_feeds(cell, seed, seconds)
    for f in feeds:
        f.ref_keep = fleetlib.reference_keep(cfg, f)
    t_f = time.perf_counter() - t
    t = time.perf_counter()
    fl = fleetlib.build(cell, ctx, feeds)
    warm(fl, cfg, feeds)
    t_c = time.perf_counter() - t
    _log(f"set-up: weights {t_w:.3f}s  frames {t_f:.3f}s  "
         f"build+warm {t_c:.3f}s  ({len(feeds)} feeds)")
    if "after_build" in hooks:
        hooks["after_build"](fl)

    n_frames = {f.name: len(f.frames) for f in feeds}
    if trace:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        fl.clock.trace = True
        jax.profiler.start_trace(TRACE_DIR)
    res = fl.runtime.run(n_frames)
    t_end = time.perf_counter_ns()
    if trace:
        with jax.profiler.TraceAnnotation("bench:window_end"):
            pass
        jax.profiler.stop_trace()
    t0 = fl.clock.t0
    window_s = (t_end - t0) / 1e9
    mem = 0
    for d in devs:
        st = d.memory_stats() or {}
        mem = max(mem, int(st.get("peak_bytes_in_use", 0)))
    setup_s = (t0 - t_process_ns) / 1e9

    sizes = {(f.name, q): op["size"] for f in feeds for q in f.queries
             for op in cfg["queries"][q]["tail"] if op["op"] == "window"}
    win = windowlib.results(fl, sizes)
    lat_ms = win["latency_ns"] / 1e6
    total = sum(n_frames.values())
    e2e = {
        "latency_p95_ms": float(np.percentile(lat_ms, 95)),
        "frames_per_s": total / window_s,
        "setup_s": setup_s,
    }
    n_compiles = compiles.between(t0, t_end)
    lag = win["ingest_lag_ns"] / 1e6
    q = max(1, len(lag) // 4)
    slope = np.polyfit(win["ingest_t_ns"] / 1e9, lag, 1)[0] \
        if len(lag) > 2 else 0.0
    last_due = max(int(src.due_ns(len(src.frames) - 1))
                   for src in fl.sources.values())
    if backlog is not None:
        backlog.update(slope_ms_per_s=float(slope),
                       drain_s=(t_end - last_due) / 1e9)
    _log(f"backlog: ingest lag p95 first quarter "
         f"{np.percentile(lag[:q], 95):.1f} ms, last quarter "
         f"{np.percentile(lag[-q:], 95):.1f} ms, slope {slope:.1f} ms/s; "
         f"drain after last due frame "
         f"{(t_end - last_due) / 1e9:.3f} s")
    _log(f"window {window_s:.3f}s  results {len(lat_ms)}  frames {total}  "
         f"forwards {res.server_stats['forwards']}  "
         f"compiles in window {n_compiles}")

    # ---- the check, after the window, with the program's state released
    program, ctl = check.compare(
        fl, cfg, feeds, res, win["unanswered"], cell["model"], params,
        fleetlib.seed_words(seed),
        fleetlib.lower_precision_extract(ctx) if control else None)
    compared = ctl if control else program
    limits = {k: 0.0 for k in compared}
    limits.update(cfg["check"]["limits"])
    correct = all(compared[k] <= limits[k] for k in limits)
    for k in compared:
        _log(f"compared {k}: {compared[k]!r} limit {limits[k]!r}")

    out: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(total),
        "failed": int(win["unanswered"]),
    }
    want = [m for m in spec["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]
    units = {m["name"]: m["unit"] for m in want}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if not trace:
        out["metrics"] = {k: {"value": e2e[k], "unit": units[k]}
                          for k in e2e if k in units}
    else:
        xp = trace_reduce.find_xplane(TRACE_DIR)
        tr = trace_reduce.reduce(trace_reduce.events(xp)) if xp else \
            trace_reduce.reduce([])
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        data = {"cell": cell, "config": cfg, "peak": peak, "trace": tr,
                "stats": dict(res.server_stats), "window": win,
                "frames_ingested": total, "feeds": feeds,
                "requests": fl.server.requests, "window_s": window_s}
        out["metrics"] = {}
        for m in spec["per_layer"]:
            if cell_name not in m.get("workloads", [cell_name]):
                continue
            v = layout.metric_reader(m["name"]).read(data)
            if v is not None:
                out["metrics"][m["name"]] = {"value": float(v),
                                             "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["device"] = device
    if control:
        out["program"] = program
    out["compared"] = {k: {"value": compared[k], "limit": limits[k]}
                       for k in compared}
    return out
