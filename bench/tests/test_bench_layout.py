"""Discovery by name, the peaks table, frames from the seed, FLOPs."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import fleet  # noqa: E402
import layout  # noqa: E402
import model_flops  # noqa: E402


def test_every_cell_config_traffic_and_metric_is_found_by_name():
    spec = layout.benchmark()
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        cell = layout.cell(w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert cell["config_spec"]["name"] == w["config"]
        assert os.path.exists(os.path.join(layout.ROOT,
                                           configs[w["config"]]["file"]))
        for fn in layout.MODEL_FUNCTIONS:
            assert callable(getattr(cell["model"], fn))
    for m in spec["per_layer"]:
        assert callable(layout.metric_reader(m["name"]).read)


def test_reduced_keys_are_stated_in_each_config_file():
    for c in layout.benchmark()["configs"]:
        with open(os.path.join(layout.ROOT, c["file"])) as f:
            body = json.load(f)
        assert set(c["reduced"]) == set(body["reduced"])
        assert body["source"] and body["assumed"]


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    assert layout.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(layout.UnknownDevice):
        layout.peaks("TPU v99")


def test_same_seed_same_frames_other_seed_other_frames():
    cell = layout.cell("samsara-fleet.busy")
    cell["feeds"] = 4
    a = fleet.make_feeds(cell, 2**31 + 12345, 1.0)
    b = fleet.make_feeds(cell, 2**31 + 12345, 1.0)
    c = fleet.make_feeds(cell, 2**31 + 12346, 1.0)
    assert [f.name for f in a] == [f.name for f in b]
    for x, y, z in zip(a, b, c):
        assert np.array_equal(x.frames, y.frames)
        assert x.phase_s == y.phase_s
        assert not np.array_equal(x.frames, z.frames)
    assert [f.stream for f in a] == ["tollbooth", "volleyball", "tollbooth",
                                     "tollbooth"]
    assert all(len(f.frames) % fleet.MICRO_BATCH == 0 for f in a)


def test_layer_flops_match_a_hand_count():
    # one Pixtral-12B decoder layer over a 44-token toll frame
    arch = layout.cell("pixtral12b-fleet.busy")["config_spec"]["backbone"][
        "big"]
    proj = 5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120      # q, k, v, o
    mlp = 3 * 5120 * 14336                                  # gated MLP
    attn = 2 * 32 * 128 * 44 * 45 // 2                      # causal QK, PV
    hand = 2 * 44 * (proj + mlp) + 2 * attn
    assert hand == 24_007_639_040
    assert model_flops.layer_flops(arch, 44) == hand
    assert model_flops.tokens(8, (3, 16, 128)) == 44
    assert model_flops.tokens(8, (3, 32, 112)) == 68


def test_run_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(layout.BENCH, "run.py"),
         "--workload", "samsara-fleet.busy", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=layout.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
