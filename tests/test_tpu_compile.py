"""Compile rehearsal: the serving path's Pallas kernels lowered by Mosaic
for a described TPU v5e chip, with no chip attached.

Interpret mode accepts kernels the chip's compiler refuses (8-bit vector
shifts, 1-D lane relayouts), so every kernel the fused serve path
dispatches on a TPU is compiled here at real widths, with the fallback
tiles of ``kernels/ops.py``, and must come out as a Mosaic custom call.
Nothing runs: these tests prove lowering, not results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 5000            # not a tile multiple: the wrappers pad and id-mask
Q = 256             # the largest Searcher bucket: two fused query tiles


@pytest.fixture(scope="module")
def one_chip():
    # skip only where the TPU compiler is not installed; any other failure
    # to describe the chip fails the tests
    pytest.importorskip("libtpu")
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executable is written to a persistent cache but
    # can never be read back here, so keep the cache out of these compiles
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


def _assert_mosaic(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("d", [96, 256])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_fused_topk_int8_compiles(one_chip, metric, masked, d, k):
    shapes = [jax.ShapeDtypeStruct((Q, d), jnp.int8, sharding=one_chip),
              jax.ShapeDtypeStruct((N, d), jnp.int8, sharding=one_chip)]
    if masked:
        shapes.append(jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=one_chip))

    def fn(q, x, *mask):
        return ops.fused_topk(q, x, k, metric, interpret=False,
                              mask=mask[0] if mask else None)

    _assert_mosaic(fn, *shapes)


# 400: the default rerank depth (4k) under a k=100 "+r32" plan
@pytest.mark.parametrize("k", [10, 100, 400])
@pytest.mark.parametrize("d", [96, 256])
@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_fused_topk4_compiles(one_chip, metric, d, k):
    q = jax.ShapeDtypeStruct((Q, d), jnp.int8, sharding=one_chip)
    packed = jax.ShapeDtypeStruct((N, d // 2), jnp.uint8, sharding=one_chip)
    _assert_mosaic(
        lambda a, b: ops.fused_topk(a, b, k, metric, packed=True,
                                    interpret=False),
        q, packed)


@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("bits,m", [(8, 32), (4, 32)])
def test_fused_adc_compiles(one_chip, bits, m, k):
    """pq32x8 (fused_adc, 256 codewords) and pq32x4 (fused_adc4, packed
    16-codeword nibbles)."""
    n_codewords = 2 ** bits
    lut = jax.ShapeDtypeStruct((Q, m, n_codewords), jnp.int8,
                               sharding=one_chip)
    width = m if bits == 8 else m // 2
    codes = jax.ShapeDtypeStruct((N, width), jnp.uint8, sharding=one_chip)
    _assert_mosaic(
        lambda a, b: ops.fused_adc_topk(a, b, k, packed=bits == 4,
                                        interpret=False),
        lut, codes)


@pytest.mark.parametrize("d", [96, 256])
def test_quantize_compiles(one_chip, d):
    x = jax.ShapeDtypeStruct((N, d), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    _assert_mosaic(
        lambda a, lo, hi, zero: ops.quantize(a, lo, hi, zero, bits=8,
                                             interpret=False),
        x, c, c, c)
