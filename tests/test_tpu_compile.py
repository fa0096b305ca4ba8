"""Compile for a described TPU v5e, with no chip attached.

The TPU's compiler refuses what interpret mode lets through: block shapes
off the (8, 128) tiling, vector gathers Mosaic cannot lower, more VMEM
than a kernel may use, programs over the chip's HBM. These tests
AOT-compile each Pallas MSDA kernel at the paper's widths (800x1333
four-level pyramid, d_model 256, 8 heads, 4 points; the decoder at 300
queries x 6 layers) and the serving forward at full widths on a 256-px
bucket, and check that the kernels compiled natively
(``tpu_custom_call``) and carry their layer's scope. Nothing runs, so nothing here says a result is
right; the kernels' numbers are checked in interpret mode elsewhere.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests; the file skips where no TPU
compiler is installed.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.detr_family import CONFIGS, LEVEL_SHAPES
from repro.core import fwp as fwp_lib
from repro.obs import hlo_scopes

N_IN = sum(h * w for h, w in LEVEL_SHAPES)
B, H, DH, K = 1, 8, 32, 16
HBM_BYTES = int(15.75 * 2 ** 30)           # what one v5e lets a program use


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 - any failure
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A described-chip compile can be written to the persistent cache
    but not read back without the chip: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    exe = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text()
    return exe


def _points(s, lead):
    f32, i32 = jnp.float32, jnp.int32
    return ([s(lead + (H, K), f32)] * 2, [s(lead + (H, K), i32)] * 3,
            s(lead + (H, K), f32))


@pytest.mark.parametrize("compact", (False, True), ids=("dense", "compact"))
def test_fused_kernel_compiles_for_v5e(one_chip, compact):
    from repro.kernels.msgs_fused import msgs_fused_packed_pallas
    s = _spec(one_chip)
    caps = fwp_lib.level_capacities(LEVEL_SHAPES, 0.6)
    n_rows = sum(caps) + 1 if compact else N_IN
    (x, y), (st, wl, hl), p = _points(s, (B, N_IN))
    remap = s((B, N_IN), jnp.int32) if compact else None

    def fn(v, x, y, st, wl, hl, p, remap):
        return msgs_fused_packed_pallas(v, x, y, st, wl, hl, p, remap,
                                        head_pack=4, interpret=False)
    _compile(fn, s((B, n_rows, H, DH), jnp.bfloat16), x, y, st, wl, hl, p,
             remap)


def test_windowed_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.msgs_windowed import msgs_windowed_msp_pallas
    attn = CONFIGS["deformable-detr-defa"].encoder.attn
    s = _spec(one_chip)
    caps = fwp_lib.level_capacities(LEVEL_SHAPES, attn.fwp_capacity)
    (x, y), (lvl, _, _), p = _points(s, (B, N_IN))

    def fn(v, x, y, lvl, p, remap, keep_idx):
        return msgs_windowed_msp_pallas(
            v, x, y, lvl, p, remap, keep_idx, level_shapes=LEVEL_SHAPES,
            ranges=attn.range_narrow, tile_q=128, head_pack=4,
            caps=tuple(caps), interpret=False)
    _compile(fn, s((B, sum(caps) + 1, H, DH), jnp.bfloat16), x, y, lvl, p,
             s((B, N_IN), jnp.int32), s((B, sum(caps)), jnp.int32))


def test_decode_kernel_compiles_for_v5e(one_chip):
    """The stacked launch: one grid over 6 layers x 300 queries against
    the once-staged table."""
    from repro.kernels.msgs_decode import (DecodeStagedTable,
                                           msgs_decode_layers_pallas)
    s = _spec(one_chip)
    g = 4
    (x, y), (st, wl, hl), p = _points(s, (B, 6, 300))

    def fn(v, x, y, st, wl, hl, p):
        staged = DecodeStagedTable(v=v, remap=None, n_rows=N_IN,
                                   head_pack=g, dh=DH, table_bytes=0)
        return msgs_decode_layers_pallas(staged, x, y, st, wl, hl, p,
                                         interpret=False)
    _compile(fn, s((B, H // g, N_IN, g * DH), jnp.bfloat16), x, y, st, wl,
             hl, p)


def test_serve_forward_compiles_for_v5e(one_chip, monkeypatch):
    """The forward ``DetrServeEngine`` AOT-compiles per bucket, at the
    ``deformable-detr`` widths with a 6 x 300 decoder head, batch 1, on a
    256-px bucket. The process's default backend is the CPU, so the
    kernels are told here to compile natively."""
    from repro import msda
    from repro.core.detector import (DetectorConfig, detector_apply,
                                     init_detector)
    from repro.kernels import ops
    from repro.serve.buckets import derive_buckets
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    enc = CONFIGS["deformable-detr"].encoder
    cfg = DetectorConfig(encoder=enc, img_size=256, n_classes=91,
                         dtype=enc.dtype,
                         decoder=msda.MSDADecoderConfig(dtype=enc.dtype))
    bucket, = derive_buckets(cfg, (256,), backend="auto")
    params = jax.eval_shape(lambda k: init_detector(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    img = jax.ShapeDtypeStruct((1, 3, 256, 256), jnp.float32,
                               sharding=one_chip)
    exe = _compile(lambda p, x: detector_apply(p, bucket.cfg, x,
                                               backend="auto")[:2],
                   params, img)
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    # every MSDA kernel sits in the sampling stage of its layer's scope
    scopes = hlo_scopes(exe)
    kernels = [n for n in scopes if n.startswith("msgs_")]
    assert len(kernels) == 12
    assert all(re.match(r"(encoder/block_\d|decoder/layer_\d)/msda/sample",
                        scopes[k]) for k in kernels)
