"""A whole run on the CPU at a tiny size (no chip: the harness's look for
a TPU is skipped): a sound run is correct, the control computed in a
lower precision is not, and a run with the timed path broken underneath
is not.  The tiny configuration lives in ``tests/data``."""
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402
import layout  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
SECONDS = 1.0


@pytest.fixture(scope="module", autouse=True)
def _jax_config():
    """The harness sets JAX's persistent-cache options; give the worker
    its own back afterwards, and keep this file's compiles off disk."""
    import jax
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def _run(seed, hooks=None, control=False):
    """The tiny cell stands in for ``samsara-fleet.busy``: it reports the
    metrics that cell reports."""
    spec = layout.benchmark()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "samsara-fleet.busy" in m.get("workloads", []):
            m["workloads"].append("tiny-fleet.busy")
    cell = layout.cell("tiny-fleet.busy", root=DATA)
    return harness.run(cell, seed, SECONDS, False, time.perf_counter_ns(),
                       require_tpu=False, hooks=hooks, spec=spec,
                       control=control)


def _break_extract(transform):
    """Wrap the server's extract program: ``transform`` rewrites its
    per-task answers where they are produced."""
    def after_build(fl):
        server = fl.server
        orig = server._fn

        def fn(variant):
            inner = orig(variant)

            def broken(frames):
                return transform(inner(frames))
            return broken
        server._fn = fn
    return {"after_build": after_build}


def _shift_answers(out):
    import jax.numpy as jnp
    return {k: (v + 1) % 2 if k == "present" else jnp.where(v == 0, 1, 0)
            for k, v in out.items()}


def _half_batch(out):
    """Answers for the first half of the rows only; the rest repeat them."""
    import jax.numpy as jnp
    res = {}
    for k, v in out.items():
        n = v.shape[0]
        half = v[:max(n // 2, 1)]
        res[k] = jnp.concatenate([half] * 2 + [half[:1]] * (n % 2))[:n]
    return res


@pytest.fixture(scope="module")
def sound():
    return _run(2**31 + 77)


@pytest.fixture(scope="module")
def control():
    return _run(2**31 + 77, control=True)


def test_sound_run_is_correct_and_prints_its_numbers(sound):
    assert sound["correct"] is True
    assert list(sound)[-1] == "compared"
    c = sound["compared"]
    for k in ("keep_mismatch", "tail_mismatch", "unanswered"):
        assert c[k] == {"value": 0.0, "limit": 0.0}
    assert c["preprocess_max_abs"]["value"] <= c["preprocess_max_abs"][
        "limit"]
    m = sound["metrics"]
    assert set(m) == {"latency_p95_ms", "frames_per_s", "setup_s"}
    assert m["latency_p95_ms"]["value"] > 0
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_lower_precision_control_is_not_correct(sound, control):
    """The harness judges the control by the program's limits; the
    extract's bfloat16 path fails the logit gap by itself, and the same
    run as served reads the program's numbers."""
    assert control["correct"] is False
    gap = control["compared"]["extract_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert control["program"] == {k: v["value"]
                                  for k, v in sound["compared"].items()}


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_broken_timed_path_is_not_correct(fault):
    transform = {"answer_altered": _shift_answers,
                 "half_batch": _half_batch}[fault]
    out = _run(2**31 + 78, hooks=_break_extract(transform))
    assert out["correct"] is False
    assert out["compared"]["extract_logit_gap"]["value"] > \
        out["compared"]["extract_logit_gap"]["limit"]
    assert np.isfinite(out["compared"]["extract_logit_gap"]["value"])
