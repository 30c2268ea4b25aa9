"""A configuration brings its own backbone: ``configs/<config>.py`` exports
``arch_config``, ``fan_in``, ``reference`` and ``flops_per_frame``, and
the harness takes all four from there.  ``tiny-ownref`` (``tests/data``)
is a configuration added as new files only: its module brings its own
reference and its own fan-in rule."""
import os
import shutil
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
SEED = 2**31 + 4321
CONFIGS = [(layout.BENCH, c["name"]) for c in layout.benchmark()["configs"]] \
    + [(DATA, "tiny-fleet"), (DATA, "tiny-ownref")]

# ``tiny-fleet``'s seeded weights and its reference logits on four frames
# at SEED, each variant's float64 sum, as the commit before the
# configuration modules gave them (CPU).  The weights are exact; the
# logits' last digits follow the order in which the CPU's matmuls sum,
# which its thread count sets, so they are held to 1e-5 of the sum.
PINNED_WEIGHTS = {"big": 317.7808702767462, "small": 91.99862090371812}
PINNED_LOGITS = {"big": -20.966505467891693, "small": -107.02899512648582}


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


def _leaves(params):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(str(path[-1].key), np.asarray(leaf)) for path, leaf in flat]


@pytest.mark.parametrize("root,config", CONFIGS, ids=[c for _, c in CONFIGS])
def test_every_configuration_exports_the_four_functions(root, config):
    mod = layout.model(config, root)
    for fn in layout.MODEL_FUNCTIONS:
        assert callable(getattr(mod, fn))


@pytest.mark.parametrize("missing", layout.MODEL_FUNCTIONS)
def test_a_module_without_one_of_them_is_refused_by_name(tmp_path, missing):
    for part in ("cells/tiny-fleet.busy.json", "configs/tiny-fleet.json",
                 "traffic/busy.json"):
        os.makedirs(tmp_path / os.path.dirname(part), exist_ok=True)
        shutil.copy(os.path.join(DATA, part), tmp_path / part)
    (tmp_path / "configs" / "tiny-fleet.py").write_text("".join(
        f"def {fn}(*args):\n    pass\n\n\n"
        for fn in layout.MODEL_FUNCTIONS if fn != missing))
    with pytest.raises(AttributeError, match=rf"has no {missing}\(\)"):
        layout.cell("tiny-fleet.busy", root=str(tmp_path))


def test_tiny_fleet_weights_and_reference_logits_read_as_pinned():
    import jax
    cell = layout.cell("tiny-fleet.busy", root=DATA)
    cfg = cell["config_spec"]
    _, params = harness.make_context(cfg, SEED, cell["model"])
    frames = np.random.default_rng(SEED).standard_normal(
        (4, 3, 16, 128)).astype(np.float32)
    for v in sorted(params):
        weights = sum(float(np.asarray(leaf, np.float64).sum())
                      for leaf in jax.tree_util.tree_leaves(params[v]))
        assert weights == PINNED_WEIGHTS[v]
        lg = cell["model"].reference(cfg["backbone"][v], cfg["patch"]
                                     ).logits(params[v], frames)
        total = sum(float(np.asarray(lg[k], np.float64).sum())
                    for k in sorted(lg))
        assert total == pytest.approx(PINNED_LOGITS[v], rel=1e-5)


def test_own_fan_in_draws_its_own_weights():
    """Same seed, same shapes: ``tiny-ownref``'s weights equal
    ``tiny-fleet``'s exactly in every leaf where the two fan-in rules
    agree, and differ where they do not."""
    own = layout.cell("tiny-ownref.busy", root=DATA)
    base = layout.cell("tiny-fleet.busy", root=DATA)
    _, p_own = harness.make_context(own["config_spec"], SEED, own["model"])
    _, p_base = harness.make_context(base["config_spec"], SEED,
                                     base["model"])
    differ = set()
    for v in sorted(p_base):
        for (name, a), (_, b) in zip(_leaves(p_own[v]), _leaves(p_base[v])):
            agree = own["model"].fan_in(name, a.shape) == \
                base["model"].fan_in(name, a.shape)
            assert np.array_equal(a, b) == agree, (v, name)
            if not agree:
                differ.add(name)
    assert differ == {"w_out"}


class _Recorded:
    """The configuration's reference, with one head's logits rolled by one
    class where ``shift`` is set, keeping the weights it is given."""

    def __init__(self, reference, shift):
        self.reference, self.shift, self.params = reference, shift, {}

    def __call__(self, spec, patch):
        ref = self.reference(spec, patch)
        outer = self

        class Ref:
            def logits(self, params, frames, block=16):
                outer.params[spec["name"]] = params
                out = ref.logits(params, frames, block)
                if outer.shift:
                    out["color"] = np.roll(out["color"], 1, axis=-1)
                return out
        return Ref()


@pytest.mark.parametrize("shift", [False, True],
                         ids=["delegating", "one_head_shifted"])
def test_correct_follows_the_configurations_own_reference(shift):
    """A whole ``tiny-ownref`` run on the CPU: ``correct`` is decided by
    the module's reference, against the weights its fan-in drew."""
    name = "tiny-ownref.busy"
    spec = layout.benchmark()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "samsara-fleet.busy" in m.get("workloads", []):
            m["workloads"].append(name)
    cell = layout.cell(name, root=DATA)
    rec = _Recorded(cell["model"].reference, shift)
    cell["model"].reference = rec
    out = harness.run(cell, SEED + 1, SECONDS, False, time.perf_counter_ns(),
                      require_tpu=False, spec=spec)
    gap = out["compared"]["extract_logit_gap"]
    assert out["correct"] is (not shift)
    assert (gap["value"] > gap["limit"]) is shift
    for k in ("keep_mismatch", "tail_mismatch", "unanswered"):
        assert out["compared"][k]["value"] == 0.0
    cfg = cell["config_spec"]
    _, own = harness.make_context(cfg, SEED + 1, cell["model"])
    assert set(rec.params) == {s["name"] for s in cfg["backbone"].values()}
    for v, s in cfg["backbone"].items():
        for (_, a), (_, b) in zip(_leaves(rec.params[s["name"]]),
                                  _leaves(own[v])):
            assert np.array_equal(a, b)
