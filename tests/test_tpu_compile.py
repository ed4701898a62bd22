"""The main path's Pallas kernels compile for a TPU v5e chip, at real widths.

Each test compiles one kernel ahead of time for a *described* (not
attached) ``v5e:2x2`` topology, with the TPU compiler installed next to
jaxlib: Mosaic then refuses what the chip would refuse (tile-misaligned
blocks, too much VMEM) although nothing runs.  The topology is described in
a fixture, never at import, so that only the process that runs these tests
loads the TPU library; where it cannot be described the tests skip.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bwma_gemm import bwma_gemm
from repro.kernels.paged_attention import (
    mla_paged_attention_decode,
    paged_attention_decode,
    paged_copy,
)

SLOTS, PAGE, MAX_PAGES = 4, 128, 16  # 4 slots of 2048 tokens
NUM_PAGES = SLOTS * MAX_PAGES + 1     # plus the null page


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what it writes to a persistent
    # compilation cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes, donate=(), kernel=""):
    """Compile ``fn``; with ``kernel``, the Pallas call must keep that name
    on a ``tpu_custom_call`` op, which is how a device trace finds it."""
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if kernel:
        assert re.search(rf'%{kernel}(\.\d+)? = .*custom_call_target='
                         rf'"tpu_custom_call"', text), kernel
    return compiled


def test_paged_attention_decode_minicpm_2b(one_chip):
    """36 query and 36 KV heads of 64 dims over 128-token bf16 pages."""
    _compile(
        functools.partial(paged_attention_decode, interpret=False), one_chip,
        ((SLOTS, 1, 36, 64), jnp.bfloat16),
        ((NUM_PAGES, PAGE, 36, 64), jnp.bfloat16),
        ((NUM_PAGES, PAGE, 36, 64), jnp.bfloat16),
        ((SLOTS, MAX_PAGES), jnp.int32),
        ((SLOTS,), jnp.int32),
        kernel="paged_attention_decode",
    )


def test_mla_paged_attention_decode_deepseek_v3(one_chip):
    """128 heads against 512-dim latent and 64-dim rope pages."""
    _compile(
        functools.partial(mla_paged_attention_decode, scale=192 ** -0.5,
                          interpret=False), one_chip,
        ((SLOTS, 1, 128, 512), jnp.bfloat16),
        ((SLOTS, 1, 128, 64), jnp.bfloat16),
        ((NUM_PAGES, PAGE, 512), jnp.bfloat16),
        ((NUM_PAGES, PAGE, 64), jnp.bfloat16),
        ((SLOTS, MAX_PAGES), jnp.int32),
        ((SLOTS,), jnp.int32),
        kernel="mla_paged_attention_decode",
    )


@pytest.mark.parametrize("pool", [
    (40, NUM_PAGES, PAGE, 36, 64),  # minicpm-2b K or V pages
    (40, NUM_PAGES, PAGE, 9, 64),   # minicpm-2b per device at TP=4
    (61, NUM_PAGES, PAGE, 512),     # deepseek-v3 latent pages
    (61, NUM_PAGES, PAGE, 64),      # deepseek-v3 rope-key pages
], ids=["minicpm-2b", "minicpm-2b-tp4-shard", "deepseek-v3-latent",
        "deepseek-v3-rope"])
def test_paged_copy_stacked_pools(one_chip, pool):
    """The COW copy compiles in place: the donated pool is aliased and no
    pool-sized relayout buffer appears next to it."""
    compiled = _compile(
        functools.partial(paged_copy, interpret=False), one_chip,
        (pool, jnp.bfloat16), ((), jnp.int32), ((), jnp.int32),
        donate=(0,), kernel="paged_copy",
    )
    ma = compiled.memory_analysis()
    pool_bytes = 2 * int(jnp.prod(jnp.asarray(pool)))
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < pool_bytes // 100


def test_bwma_gemm_bert_base(one_chip):
    """512x768 @ 768x768 in 128x128 blocks (the paper's BERT-base shape)."""
    _compile(
        functools.partial(bwma_gemm, interpret=False), one_chip,
        ((4, 6, 128, 128), jnp.bfloat16),
        ((6, 6, 128, 128), jnp.bfloat16),
    )
