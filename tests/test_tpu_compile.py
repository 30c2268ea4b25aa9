"""The stream-path Pallas kernels compile for a TPU v5e.

Interpret mode (``tests/test_kernels.py``) checks what the kernels
compute; it cannot see what the TPU compiler (Mosaic) refuses — block
shapes off its (8, 128) tiling, casts it lacks, reshapes that split the
lane dimension.  These tests compile each kernel at the stream shapes
(16 uint8 frames of 3×128×256) for one chip of a described ``v5e:2x2``
topology, with no chip attached, and check that the program holds the
Mosaic kernel (``tpu_custom_call``).  The fused-prefix specs are those the
fleet plans build: Skip's (4, 8) diff grid, the plans' crops, the gate
signature.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.frame_diff.kernel import frame_diff_kernel
from repro.kernels.fused_prefix.kernel import (fused_prefix_kernel,
                                               out_frame_shape)
from repro.kernels.fused_preprocess.kernel import fused_preprocess_kernel
from repro.semantic.signature import signature_layout

FRAME = (3, 128, 256)
B = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _frames(one_chip):
    return jax.ShapeDtypeStruct((B,) + FRAME, jnp.uint8, sharding=one_chip)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("regions", [(4, 8), (4, 4)])
def test_frame_diff_compiles(one_chip, regions):
    f = _frames(one_chip)
    _assert_mosaic(functools.partial(frame_diff_kernel, regions=regions),
                   f, f)


@pytest.mark.parametrize("crop,factor,grey", [
    ((64, 0, 64, 256), 1, False),      # the tollbooth road crop
    ((64, 0, 64, 256), 2, False),
    ((96, 0, 32, 256), 2, False),      # fleet-planned tollbooth crop
    ((32, 0, 64, 224), 2, False),      # fleet-planned volleyball crop
    ((32, 128, 64, 128), 2, True),
])
def test_fused_preprocess_compiles(one_chip, crop, factor, grey):
    _assert_mosaic(functools.partial(fused_preprocess_kernel, crop=crop,
                                     factor=factor, grey=grey),
                   _frames(one_chip))


@pytest.mark.parametrize("spec", [
    # Skip + cheap color + road crop/downscale (the canonical prefix)
    (("diff", (4, 8)), ("color", (190., 40., 40.), None),
     ("preprocess", (64, 0, 64, 256), 2, False)),
    # Skip + the fleet-planned tollbooth preprocess (TinyDet runs in XLA)
    (("diff", (4, 8)), ("preprocess", (96, 0, 32, 256), 2, False)),
    # the fleet-planned volleyball preprocess alone
    (("preprocess", (32, 0, 64, 224), 2, False),),
    # ROI color filters, a crop, a grey preprocess
    (("color", (190., 40., 40.), (0, 0, 64, 128)),
     ("crop", (32, 0, 64, 256)), ("preprocess", (0, 0, 64, 256), 2, True)),
])
def test_fused_prefix_compiles(one_chip, spec):
    gy, gx, d, _ = signature_layout(out_frame_shape(spec, FRAME))
    spec = spec + (("signature", (gy, gx)),)
    f = _frames(one_chip)
    proj = jax.ShapeDtypeStruct((d, 16), jnp.float32, sharding=one_chip)
    has_diff = spec[0][0] == "diff"
    fn = functools.partial(fused_prefix_kernel, spec=spec)
    if has_diff:
        _assert_mosaic(fn, f, f, proj)
    else:
        _assert_mosaic(lambda x, p: fn(x, None, p), f, proj)
