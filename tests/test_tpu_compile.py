"""The served path's kernels compile for a TPU v5e at real widths.

Nothing runs: each case lowers and compiles for one chip of a described
``v5e:2x2`` topology, which is what catches block shapes Mosaic refuses,
primitives it cannot lower and blocks that overflow VMEM — none of which
interpret mode can see.  The topology is described inside the fixture
(never at import), so every xdist worker collects the same cases and only
the worker that runs this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import calibrate as CA
from repro.kernels import ccl as CCL
from repro.kernels import pixel_cascade as PC
from repro.kernels import similarity as SIM
from repro.kernels import triage as TR


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of this file
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B,H,W", [(12, 96, 128), (4, 540, 960)])
def test_pixel_cascade_compiles(one_chip, B, H, W):
    def fn(f0, f1, f2):
        return PC.pixel_cascade_pallas(
            *(PC.planar_frames(f) for f in (f0, f1, f2)),
            threshold=40, maxval=255, true_hw=(H, W), interpret=False)

    frame = ((B, H, W, 3), jnp.uint8)
    assert "tpu_custom_call" in _compile(fn, one_chip, frame, frame, frame)


@pytest.mark.parametrize("B,H,W", [(12, 96, 128), (24, 540, 960),
                                   (4, 1080, 1920)])
def test_ccl_fixpoint_compiles(one_chip, B, H, W):
    """The label fixpoint with one camera's planes resident in VMEM: the
    default frame, the 960x540 fleet and a 1080p frame (8.4 MB a plane)."""
    fn = functools.partial(CCL.label_components_pallas, interpret=False)
    assert "tpu_custom_call" in _compile(fn, one_chip, ((B, H, W), jnp.int32))


@pytest.mark.parametrize("E,N", [(64, 512), (8192, 512), (1 << 19, 8)])
def test_triage_fleet_compiles(one_chip, E, N):
    """city_scale's tick, a full-width superstep slab, and metropolis's
    narrow 64-tick x 8192-row slab (8 lanes pad to 128 in VMEM)."""
    fn = functools.partial(TR.triage_fleet_pallas, capacity=8,
                           interpret=False)
    hlo = _compile(fn, one_chip, ((E, N), jnp.float32), ((E, 2), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_calibrate_fleet_compiles(one_chip):
    fn = functools.partial(CA.calibrate_fleet_pallas, iters=8, min_count=8,
                           interpret=False)
    hlo = _compile(fn, one_chip, ((64, 256), jnp.float32),
                   ((64, 256), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_associate_compiles(one_chip):
    M, K, D = 256, 512, 64
    fn = functools.partial(SIM.associate_pallas, interpret=False)
    hlo = _compile(fn, one_chip, ((M, D), jnp.float32), ((K, D), jnp.float32),
                   ((M,), jnp.int32), ((K,), jnp.int32), ((M,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_score_crops_step_compiles(one_chip):
    """The pixel path's CQ classifier step (``surveiledge-cls`` edge model)
    at a 64-crop bucket of 32 px crops (16 patch tokens each)."""
    from repro.models import meta as M
    from repro.system.pixel_frontend import PixelFrontend, _conf_apply

    cfg = PixelFrontend(cache=False).cfg
    params = jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        params)
    tokens = jax.ShapeDtypeStruct((64, 16), jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(_conf_apply, cfg))
    compiled = step.lower(params, tokens).compile()
    assert compiled.memory_analysis() is not None
