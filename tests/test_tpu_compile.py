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
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

import repro.configs as C
from repro.distributed import axes as AX
from repro.distributed import sharding as SH
from repro.kernels.bwma_gemm import bwma_gemm
from repro.kernels.paged_attention import (
    mla_paged_attention_decode,
    paged_attention_decode,
    paged_copy,
)
from repro.models import attention as attn
from repro.models import model as M
from repro.serve.engine import jitted_step_fns

SLOTS, PAGE, MAX_PAGES = 4, 128, 16  # 4 slots of 2048 tokens
NUM_PAGES = SLOTS * MAX_PAGES + 1     # plus the null page


@pytest.fixture(scope="module")
def v5e_2x2():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


# the benchmark cells' engines: (config, cut, slots, pool pages, max_len,
# devices on the model axis of a 1xN mesh)
CELLS = {
    "minicpm-2b": ("minicpm-2b", {}, 12, 65, 2048, 1),
    "starcoder2-7b-pp2": ("starcoder2-7b", {"n_layers": 16}, 16, 513, 4096, 1),
    "starcoder2-7b-tp4": ("starcoder2-7b", {}, 16, 513, 4096, 4),
}
_POOL_OPS = ("copy", "dynamic-slice", "dynamic-update-slice")
_COLLECTIVE = re.compile(r"\s*(?:ROOT )?%\S+ = (.*?) (all-reduce|all-gather|"
                         r"reduce-scatter|all-to-all|collective-permute)"
                         r"(?:-start)?\(")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def _nbytes(dtype: str, dims: str) -> int:
    """Bytes of an HLO array type (``bf16``, ``f32``, ``s32``, ``pred``)."""
    n = max(1, int(re.sub(r"\D", "", dtype) or 8) // 8)
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
def test_engine_step_keeps_pool_in_place(v5e_2x2, monkeypatch, cell, step):
    """The decode and prefill-chunk programs at each benchmark cell's
    geometry (bf16, 128-token pages) move no pool-sized buffer: the donated
    pool is aliased, the program's temp is under a twentieth of the pool
    (the layer scan carries the stacked pool and writes tokens into it
    where it lies), and no copy, dynamic-slice or dynamic-update-slice
    yields the stacked pool or one layer of it.  On the tensor-parallel
    cell's 1x4 mesh (``SH.serve_shardings``, each device one of the 4 KV
    heads) the same holds per device, and no collective carries more than
    the step's logits or its hidden states (in float32), whichever is
    larger: no weight or pool crosses chips."""
    # the engine resolves "pallas" to interpret mode on this CPU host; a
    # step compiled for the chip needs the compiled kernel
    monkeypatch.setattr(attn, "kernels_interpreted", lambda: False)
    arch, cut, slots, num_pages, max_len, tp = CELLS[cell]
    cfg = C.get_config(arch, dtype=jnp.bfloat16, block=PAGE,
                       decode_backend="pallas", **cut)
    maxp = max_len // PAGE
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: M.init_paged_cache(
        cfg, slots, num_pages, PAGE, max_len))
    fn, donate = jitted_step_fns(cfg)[step]
    if tp == 1:
        rep = SingleDeviceSharding(v5e_2x2.devices[0])
        param_sh, pool_sh = (jax.tree.map(lambda _: rep, t)
                             for t in (params, pools))
    else:
        mesh = Mesh(np.array(v5e_2x2.devices[:tp]).reshape(1, tp),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        param_sh, pool_sh, rep = SH.serve_shardings(cfg, mesh, params, pools)
        fn = AX.traced_under(mesh, fn)

    def shaped(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    leaf = jax.tree.leaves(pools)[0]
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pools)) // tp

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    args = (shaped(params, param_sh), shaped(pools, pool_sh)) + {
        "decode_step": (i32(slots, 1), i32(slots), i32(slots, maxp),
                        jax.ShapeDtypeStruct((slots,), jnp.bool_,
                                             sharding=rep)),
        "prefill_chunk": (i32(1, PAGE), i32(), i32(), i32(PAGE), i32(PAGE),
                          i32(maxp), i32()),
    }[step]
    out = jax.eval_shape(fn, *args)
    logits = out[1] if step == "decode_step" else out[0]
    tokens = slots if step == "decode_step" else PAGE
    bound = max(logits.size * logits.dtype.itemsize, tokens * cfg.d_model * 4)
    out_sh = jax.tree.map(lambda _: rep, out[:-1]) + (pool_sh,)
    compiled = jax.jit(fn, in_shardings=jax.tree.map(
        lambda a: a.sharding, args), out_shardings=out_sh,
        donate_argnums=donate).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < pool_bytes // 20, ma.temp_size_in_bytes
    sizes = {leaf.size // tp, leaf.size // tp // leaf.shape[0]}
    moved, crossed = [], []
    text = compiled.as_text()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(2) in _POOL_OPS:
            n = 1
            for d in m.group(1).split(","):
                n *= int(d)
            if n in sizes:
                moved.append(line.strip()[:120])
        m = _COLLECTIVE.match(line)
        if m and max(_nbytes(*a) for a in _ARRAY.findall(m.group(1))) > bound:
            crossed.append(line.strip()[:120])
    assert not moved, moved
    assert not crossed, crossed
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert kernels == (1 if step == "decode_step" else 0)
