"""Main-path kernels compiled for a described TPU v5e at real widths.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, and checks that the Pallas kernel reached the program as a
``tpu_custom_call``.  This catches what interpret mode cannot: block shapes
the Mosaic lowering refuses, VMEM overflows.  Widths are qwen3-235b-a22b's
expert FFN: 32 experts per rank plus 2 replica slots (G=34), capacity
C=256, d_model D=4096, expert d_ff F=1536; the count-aware fp kernel is
also compiled at both benchmark cells' slot shapes.

Only the worker that runs this file loads the TPU compiler, inside the
fixture; nothing here touches it at import.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.grouped_gemm import ops as gg

G, C, D, F = 34, 256, 4096, 1536


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# (slots, capacity, expert d_ff, max_rows) of the cells' programs: GLM's
# 128 mains, qwen3's 32, prefill (chunk 512) and decode (batch 4).
_FFN_SHAPES = {
    "glm.prefill": (128, 512, 1408, 4096),
    "glm.decode": (128, 8, 1408, 32),
    "qwen3.prefill": (32, 512, 1536, 4096),
    "qwen3.decode": (32, 8, 1536, 32),
}


@pytest.mark.parametrize("shape", sorted(_FFN_SHAPES))
def test_grouped_ffn_compiles_for_v5e(one_chip, shape):
    Gs, Cs, Fs, max_rows = _FFN_SHAPES[shape]
    x = _spec((Gs, Cs, D), "bfloat16", one_chip)
    n = _spec((Gs,), "int32", one_chip)
    w = _spec((Gs, D, Fs), "bfloat16", one_chip)
    w2 = _spec((Gs, Fs, D), "bfloat16", one_chip)
    text = _compiled_text(
        lambda *a: gg.grouped_ffn(*a, max_rows=max_rows), x, n, w, w, w2)
    assert "tpu_custom_call" in text


def test_grouped_ffn_compiles_for_v5e_in_float32(one_chip):
    """The widest fast-memory need: float32 blocks of qwen3's 512-wide F
    tiles, with no bound on the rows."""
    x = _spec((G, C, D), "float32", one_chip)
    n = _spec((G,), "int32", one_chip)
    w = _spec((G, D, F), "float32", one_chip)
    w2 = _spec((G, F, D), "float32", one_chip)
    assert "tpu_custom_call" in _compiled_text(gg.grouped_ffn, x, n, w, w,
                                               w2)


def test_grouped_swiglu_q8_compiles_for_v5e(one_chip):
    q = _spec((G, C, D), jnp.int8, one_chip)
    rs = _spec((G, C), jnp.float32, one_chip)
    wq = _spec((G, D, F), jnp.int8, one_chip)
    cs = _spec((G, F), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compiled_text(gg.grouped_swiglu_q8,
                                               q, rs, wq, cs, wq, cs)


def test_grouped_matmul_q8_compiles_for_v5e(one_chip):
    q = _spec((G, C, F), jnp.int8, one_chip)
    rs = _spec((G, C), jnp.float32, one_chip)
    wq = _spec((G, F, D), jnp.int8, one_chip)
    cs = _spec((G, D), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compiled_text(gg.grouped_matmul_q8,
                                               q, rs, wq, cs)
