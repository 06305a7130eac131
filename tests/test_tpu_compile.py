"""Compile every main-path QLC kernel for a described TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: Mosaic's
unsupported gathers, misaligned slices and VMEM overruns. These tests
compile each ``ops`` entry point with ``interpret=False`` for a v5e
that is described, not attached, at the real widths:

  * K=1024 over one phi3-mini-3.8b FFN weight (3072 x 8192 symbols):
    fused quantize→encode, fused decode→dequantize (+ accumulate),
    plain encode and decode;
  * K=256 for the KV-cache block decode: plain and DMA-prefetch decode.

A kernel whose tiles overrun the scoped VMEM limit is refused by that
compiler, so a compile that passes is the VMEM check; the memory
analysis then shows the program holds no HBM temporaries. Nothing
runs. The topology is described inside a module fixture, so
this file is the only one that loads the TPU compiler, and only in the
worker given it.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import TABLE1, build_tables, codec, distributions
from repro.kernels import ops

FFN_SYMBOLS = 3072 * 8192        # one phi3-mini-3.8b FFN weight
KV_CHUNKS = 4096                 # K=256 KV block chunks per dispatch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tables():
    return build_tables(distributions.ffn1_counts(1 << 14, seed=0), TABLE1)


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# name -> (K, operands, entry point called as fn(tables, K, CW, *operands))
CASES = {
    "quantize_encode": (1024, "x", lambda t, k, cw, x: ops.quantize_encode(
        x, t, cw, emit_codes=True, interpret=False)),
    "quantize_encode_bf16": (1024, "xb", lambda t, k, cw, x:
                             ops.quantize_encode(x, t, cw, interpret=False)),
    "encode": (1024, "sym", lambda t, k, cw, s: ops.encode(
        s, t, cw, interpret=False)),
    "decode": (1024, "words", lambda t, k, cw, w: ops.decode(
        w, t, k, interpret=False)),
    "decode_dequantize": (1024, "words scales", lambda t, k, cw, w, s:
                          ops.decode_dequantize(w, s, t, k,
                                                out_dtype=jnp.bfloat16,
                                                interpret=False)),
    "decode_dequantize_accumulate": (
        1024, "acc words scales", lambda t, k, cw, a, w, s:
        ops.decode_dequantize_accumulate(a, w, s, t, k, interpret=False)),
    "kv_decode": (256, "words", lambda t, k, cw, w: ops.decode(
        w, t, k, interpret=False)),
    "kv_decode_block_async": (256, "words", lambda t, k, cw, w:
                              ops.decode_block_async(w, t, k,
                                                     interpret=False)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, tables):
    k, operands, entry = CASES[name]
    n_chunks = FFN_SYMBOLS // k if k == 1024 else KV_CHUNKS
    cw = codec.worst_case_words(k, tables.max_code_length)
    shapes = {
        "x": ((n_chunks, k), jnp.float32),
        "xb": ((n_chunks, k), jnp.bfloat16),
        "sym": ((n_chunks, k), jnp.uint8),
        "words": ((n_chunks, cw), jnp.uint32),
        "scales": ((n_chunks, k // 32), jnp.float32),
        "acc": ((n_chunks, k), jnp.float32),
    }
    args = [jax.ShapeDtypeStruct(*shapes[o], sharding=one_chip)
            for o in operands.split()]
    compiled = _compile(functools.partial(entry, tables, k, cw), *args)
    mem = compiled.memory_analysis()
    # Everything the program holds lives in its operands and results:
    # the kernels keep their tiles in VMEM, not in HBM temporaries.
    assert mem.temp_size_in_bytes == 0, mem
