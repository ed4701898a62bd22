"""The main path's Pallas kernels and step programs compile for a TPU v5e
chip, at real widths.

Each test compiles one kernel, or one of the engine's step programs, ahead
of time for a *described* (not attached) ``v5e:2x2`` topology, with the TPU
compiler installed next to jaxlib: Mosaic then refuses what the chip would
refuse (tile-misaligned blocks, too much VMEM) although nothing runs, and
XLA's memory analysis shows what the program holds beside its arguments.
The topology is described in a fixture, never at import, so that only the
process that runs these tests loads the TPU library; where it cannot be
described the tests skip.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.core import backend as BE
from repro.kernels.bwma_gemm import bwma_gemm
from repro.kernels.paged_attention import (
    mla_paged_attention_decode,
    paged_attention_decode,
    paged_copy,
)
from repro.models import model as M
from repro.serve.engine import jitted_step_fns

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
    """36 query and 36 KV heads of 64 dims over 128-token bf16 pages, read
    out of the 40-layer stacked pool (pages stored (hkv, dh, page)) through
    the layer-index operand."""
    _compile(
        functools.partial(paged_attention_decode, interpret=False), one_chip,
        ((SLOTS, 1, 36, 64), jnp.bfloat16),
        ((40, NUM_PAGES, 36, 64, PAGE), jnp.bfloat16),
        ((40, NUM_PAGES, 36, 64, PAGE), jnp.bfloat16),
        ((SLOTS, MAX_PAGES), jnp.int32),
        ((SLOTS,), jnp.int32),
        ((), jnp.int32),
        kernel="paged_attention_decode",
    )


def test_mla_paged_attention_decode_deepseek_v3(one_chip):
    """128 heads against 512-dim latent and 64-dim rope pages, read out of
    the 61-layer stacked pools through the layer-index operand."""
    _compile(
        functools.partial(mla_paged_attention_decode, scale=192 ** -0.5,
                          interpret=False), one_chip,
        ((SLOTS, 1, 128, 512), jnp.bfloat16),
        ((SLOTS, 1, 128, 64), jnp.bfloat16),
        ((61, NUM_PAGES, PAGE, 512), jnp.bfloat16),
        ((61, NUM_PAGES, PAGE, 64), jnp.bfloat16),
        ((SLOTS, MAX_PAGES), jnp.int32),
        ((SLOTS,), jnp.int32),
        ((), jnp.int32),
        kernel="mla_paged_attention_decode",
    )


@pytest.mark.parametrize("pool", [
    (40, NUM_PAGES, 36, 64, PAGE),  # minicpm-2b K or V pages, as stored
    (40, NUM_PAGES, 9, 64, PAGE),   # minicpm-2b per device at TP=4
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


# the two benchmark cells' engines: (config, slots, pool pages, max_len)
CELLS = {
    "minicpm-2b": ("minicpm-2b", {}, 12, 65, 2048),
    "starcoder2-7b-pp2": ("starcoder2-7b", {"n_layers": 16}, 16, 513, 4096),
}
_POOL_OPS = ("copy", "dynamic-slice", "dynamic-update-slice")


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
def test_engine_step_keeps_pool_in_place(one_chip, monkeypatch, cell, step):
    """The decode and prefill-chunk programs at each benchmark cell's
    geometry (bf16, 128-token pages) move no pool-sized buffer: the donated
    pool is aliased, the program's temp is under a twentieth of the pool
    (the layer scan carries the stacked pool and writes tokens into it
    where it lies), and no copy, dynamic-slice or dynamic-update-slice
    yields the stacked pool or one layer of it."""
    # the engine resolves "pallas" to interpret mode on this CPU host; a
    # step compiled for the chip needs the compiled kernel
    monkeypatch.setattr(BE, "_interpret_default", lambda: False)
    arch, cut, slots, num_pages, max_len = CELLS[cell]
    cfg = C.get_config(arch, dtype=jnp.bfloat16, block=PAGE,
                       decode_backend="pallas", **cut)
    maxp = max_len // PAGE

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(lambda k: M.init_params(cfg, k),
                                   jax.random.PRNGKey(0)))
    pools = shaped(jax.eval_shape(lambda: M.init_paged_cache(
        cfg, slots, num_pages, PAGE, max_len)))
    leaf = jax.tree.leaves(pools)[0]
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pools))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = {
        "decode_step": (params, pools, i32(slots, 1), i32(slots),
                        i32(slots, maxp),
                        jax.ShapeDtypeStruct((slots,), jnp.bool_,
                                             sharding=one_chip)),
        "prefill_chunk": (params, pools, i32(1, PAGE), i32(), i32(),
                          i32(PAGE), i32(PAGE), i32(maxp), i32()),
    }[step]
    fn, donate = jitted_step_fns(cfg)[step]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < pool_bytes // 20, ma.temp_size_in_bytes
    sizes = {leaf.size, leaf.size // leaf.shape[0]}
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(2) in _POOL_OPS:
            n = 1
            for d in m.group(1).split(","):
                n *= int(d)
            if n in sizes:
                moved.append(line.strip()[:120])
    assert not moved, moved
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert kernels == (1 if step == "decode_step" else 0)
