"""Compile-only checks for one TPU v5e chip, described rather than attached.

The TPU compiler is installed even where no chip is, and it refuses what
interpret mode and the CPU backend accept: unaligned Pallas blocks, 1-D
vector layouts, kernels over the VMEM budget, programs over the chip's HBM.
These tests compile the serving path of smollm-135m at its published widths
and both Pallas kernels at the shapes ``chip_smoke.py`` runs on a chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import mlstm_chunk
from repro.launch.specs import abstract_caches, abstract_params
from repro.models.lm import decode_step, forward

pytestmark = pytest.mark.slow

HBM_BYTES = 16 * 2**30          # one v5e chip
SMOLLM = get_config("smollm-135m")
BATCH, CACHE_LEN, PREFILL_LEN = 4, 160, 128
# the kernel shapes chip_smoke.py runs: smollm-135m attention heads
# (9 q / 3 kv, head_dim 64), and xlstm-1.3b mLSTM heads (4 x head_dim 1024,
# a 4 MiB f32 C state in VMEM)
FLASH_SHAPE = dict(B=4, H=9, KH=3, S=1024, Dh=64)
MLSTM_SHAPE = dict(B=1, H=4, S=256, Dh=1024)


@pytest.fixture(scope="module")
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_smollm_decode_step_compiles(one_chip):
    params = _on(one_chip, abstract_params(SMOLLM))
    caches = _on(one_chip, abstract_caches(SMOLLM, BATCH, CACHE_LEN))
    tok = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(decode_step, SMOLLM))
    _fits(step.lower(params, caches, tok, pos).compile())


def test_smollm_prefill_compiles(one_chip):
    params = _on(one_chip, abstract_params(SMOLLM))
    tokens = jax.ShapeDtypeStruct((BATCH, PREFILL_LEN), jnp.int32,
                                  sharding=one_chip)

    def prefill(p, toks):
        logits, _aux, caches, _ = forward(SMOLLM, p, {"tokens": toks},
                                          want_caches=True)
        return logits[:, -1:], caches

    _fits(jax.jit(prefill).lower(params, tokens).compile())


def test_flash_attention_kernel_compiles(one_chip):
    s = FLASH_SHAPE
    q = jax.ShapeDtypeStruct((s["B"], s["H"], s["S"], s["Dh"]), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((s["B"], s["KH"], s["S"], s["Dh"]),
                              jnp.bfloat16, sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv, causal=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mlstm_chunk_kernel_compiles(one_chip):
    s = MLSTM_SHAPE
    x = jax.ShapeDtypeStruct((s["B"], s["H"], s["S"], s["Dh"]), jnp.float32,
                             sharding=one_chip)
    gate = jax.ShapeDtypeStruct((s["B"], s["H"], s["S"]), jnp.float32,
                                sharding=one_chip)
    compiled = mlstm_chunk.lower(x, x, x, gate, gate).compile()
    assert "tpu_custom_call" in compiled.as_text()
