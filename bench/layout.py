"""Find a cell's parts by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under ``bench/``:

* ``cells/<cell>.json``      the configuration, the traffic mix, the feeds
* ``configs/<config>.json``  backbone, plans, feed templates, limits
* ``configs/<config>.py``    the backbone, four functions that the harness
  takes from this module alone:

  - ``arch_config(spec) -> ArchConfig``: a ``backbone`` entry of the
    config file as the program takes it;
  - ``fan_in(name, shape) -> int``: the LeCun fan-in of each weight leaf,
    by which ``fleet.init_weights`` scales the seeded weights;
  - ``reference(spec, patch)``: the plain float32 reference, an object
    whose ``logits(params, frames, block=16)`` gives each head's logits
    (``Dict[str, np.ndarray]``), at the highest matmul precision, in
    blocks of rows;
  - ``flops_per_frame(config, variant, frame_shape) -> int``: the model
    FLOPs one served frame needs.
* ``traffic/<mix>.json``     the mix's parameters
* ``metrics/<metric>.py``    ``read(run) -> float | None``
* ``peaks.json``             per ``device_kind``: peak FLOP/s and bytes/s

A later cell, mix, configuration or metric is a new file; no existing
file changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


MODEL_FUNCTIONS = ("arch_config", "fan_in", "reference", "flops_per_frame")


class UnknownDevice(KeyError):
    pass


def _json(*parts: str) -> Dict[str, Any]:
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, root: str = BENCH) -> Dict[str, Any]:
    """The cell with its configuration, mix and model module resolved
    (``root`` holds ``cells/``, ``configs/`` and ``traffic/``)."""
    c = _json(root, "cells", f"{name}.json")
    c["name"] = name
    c["config_spec"] = _json(root, "configs", f"{c['config']}.json")
    c["traffic_spec"] = _json(root, "traffic", f"{c['traffic']}.json")
    c["model"] = model(c["config"], root)
    return c


def model(config: str, root: str = BENCH) -> ModuleType:
    """``configs/<config>.py``; refused where it lacks one of
    ``MODEL_FUNCTIONS``."""
    path = os.path.join(root, "configs", f"{config}.py")
    mod = _module(path, f"bench_config_{config.replace('-', '_')}")
    for fn in MODEL_FUNCTIONS:
        if not callable(getattr(mod, fn, None)):
            raise AttributeError(
                f"{path} has no {fn}(): a configuration module exports "
                f"{', '.join(MODEL_FUNCTIONS)}")
    return mod


def metric_reader(name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "metrics", f"{name}.py"),
                   f"bench_metric_{name.replace('.', '_')}")


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _json(BENCH, "peaks.json")
    if device_kind not in table["devices"]:
        raise UnknownDevice(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json; add its peaks with their source")
    return table["devices"][device_kind]
