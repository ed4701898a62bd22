"""Fused paged-attention decode + paged-copy kernels (pages ARE tiles).

The engine sizes KV-cache pages to ``cfg.block`` — the accelerator kernel's
native tile — precisely so the serving hot loop can consume them *in place*.
These kernels close that loop.  The reference decode path gathers every
slot's whole logical history into a dense HBM buffer
(``k_pages[layer, page_table]`` — ``max_pages * page`` tokens per slot per
layer per step) before attending; here the grid walks each slot's
page-table row instead and the BlockSpec index map streams exactly one
physical page of one layer into VMEM per grid step:

    in_specs=[..., pl.BlockSpec((None, None) + block,
                   lambda b, j, table, seqpos, layer:
                       (layer[0], table[b, j], 0, 0, 0))]

so the gathered history never exists in HBM at all — the jaxcheck RPJ102
``max_gather_bytes`` budget on the decode step drops from the full gathered
K/V to the token-embedding lookup.  Softmax is accumulated online
(flash-style): per-slot running max / denominator / weighted-value scratch
carried across the sequential page dimension, finalized on the last page.
Keys past a slot's current position (partial-page tails, unmapped null-page
entries, stale pages of retired requests) are masked with the same
``finfo.min`` fill as the reference path, so parity holds to fused-softmax
reassociation (<= 1e-6, the PR-1 BWMA tolerance).

**The pool the kernels read.**  Pools are layer-stacked, ``(L, num_pages,
*block)``, and the engine carries them whole through its layer scan: the
decode kernels take the stacked pool plus the layer index as a
scalar-prefetch operand, so no per-layer slice of the pool is ever cut out
or written back.  A dense/GQA K/V page holds the logical axes ``(page,
n_kv_heads, d_head)`` in the order :func:`kv_page_order` picks from the
head dim: the KV-head axis leads, and the page's two minor dims are the
page axis and the head dim, which fill whole TPU tiles as stored for any
number of KV heads, so also for the one head a tensor-parallel shard may
hold.  A head dim that fills the 128 lanes stores ``(hkv, page, dh)``
(starcoder2's ``(4, 128, 128)``, ``(1, 128, 128)`` a shard at TP=4); any
other gives the lanes to the page axis, which the engine sizes to the
kernel's 128-token tile, and stores ``(hkv, dh, page)`` (minicpm-2b's
``(36, 64, 128)``).  Row-major ``(page, hkv, dh)`` would put the heads on
the sublanes, where one head leaves them empty and XLA stores the pool in
another order and relayouts it around every kernel call.  The kernel reads
either order's blocks straight into the MXU, with no in-VMEM transpose.
MLA latent pages keep ``(page, r)``: 512 and 64 lanes are already aligned
blocks.

Three kernels:

* :func:`paged_attention_decode` — dense/GQA one-token decode: per-page
  scores via grouped ``dot_general`` (query heads folded onto their KV
  head), online softmax, weighted-V accumulation.
* :func:`mla_paged_attention_decode` — MLA absorbed-matmul decode over
  streamed *latent* pages: scores ``q_lat . c_kv + q_rope . k_rope`` per
  page, accumulating the latent-space output ``o_lat``; the absorption of
  ``q_nope`` through ``W_kv_b`` and the value expansion stay outside (they
  are per-token matmuls, not paged reads).
* :func:`paged_copy` — the COW page copy: one grid step per stacked layer,
  scalar-prefetched ``src``/``dst`` page ids drive the in/out index maps,
  and ``input_output_aliases`` keeps the pool update in place (the donating
  COW jit's aliasing survives, see tests).

All three compile for the TPU and run under ``interpret=True`` on the CPU
platform (the tests exercise the identical grids/BlockSpecs).  They are
plain traceable functions — no inner ``jax.jit`` — so the engine's
already-jitted decode / COW steps inline them without nested-pjit donation
hazards.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the mask fill the reference path uses (repro.models.common.decode_attention
# / attention._mla_absorbed_attend) — also the online-softmax init value, so
# a fully-masked page contributes exp(finfo.min - m) == 0 exactly
_MASK = jnp.finfo(jnp.float32).min


# the TPU vector lane count: a stored minor dim fills tiles when it is a
# multiple of this
LANES = 128


def kv_page_order(d_head: int) -> tuple:
    """Stored order of a K/V page's logical ``(page, hkv, dh)`` axes.

    The two minor dims of a stored page should fill TPU tiles (the minor
    one a multiple of the 128 lanes, the second-minor one the sublanes),
    else XLA's TPU layout assignment stores the pool in another order and
    relayouts it around every kernel call.  The KV-head axis leads, so the
    minor pair is the page axis and the head dim whatever the number of KV
    heads a device holds (tensor parallelism shards the head axis, down to one
    head a shard): ``(hkv, page, dh)`` for a head dim that fills the
    lanes, and for any other the page axis, which the engine sizes to the
    kernel's 128-token tile, on the lanes with ``dh`` on the sublanes:
    ``(hkv, dh, page)``.  It is fixed by the head dim alone so that every
    reader (this kernel, the reference gather, the tensor-parallel specs)
    can tell a pool's order from the head dim.
    """
    return (1, 0, 2) if d_head % LANES == 0 else (1, 2, 0)


# the kv-head axis of a layer-stacked ``(L, num_pages, *block)`` K/V pool,
# the axis tensor parallelism shards: the heads lead a page in either order
KV_HEAD_AXIS = 2


# --------------------------------------------------------------------------
# Dense / GQA paged decode
# --------------------------------------------------------------------------

def _gqa_decode_kernel(table_ref, seqpos_ref, layer_ref, q_ref, k_ref, v_ref,
                       o_ref, acc_ref, m_ref, l_ref, *, page: int, maxp: int,
                       groups: int, scale: float, page_minor: bool):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)        # (H, dh)
    k = k_ref[...].astype(jnp.float32)      # one stored page: (Hkv, page, dh)
    v = v_ref[...].astype(jnp.float32)      # or (Hkv, dh, page)
    H, dh = q.shape
    hkv = H // groups
    qg = q.reshape(hkv, groups, dh)
    # per-page grouped scores: (Hkv, g, dh) x K over its dh axis
    # -> (Hkv, g, page)
    s = jax.lax.dot_general(
        qg, k, (((2,), (1 if page_minor else 2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale
    # this page covers absolute positions [j*page, (j+1)*page); mask beyond
    # the slot's current token exactly like the reference valid-set
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    s = jnp.where(kpos <= seqpos_ref[b], s, _MASK)
    s = s.reshape(H, page)

    # online (flash-style) softmax update across the page dimension
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p_att = jnp.exp(s - m_new)              # (H, page)
    l_ref[...] = l_prev * alpha + jnp.sum(p_att, axis=-1, keepdims=True)
    # (Hkv, g, page) x V over its page axis -> (Hkv, g, dh)
    pv = jax.lax.dot_general(
        p_att.reshape(hkv, groups, page), v,
        (((2,), (2 if page_minor else 1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).reshape(H, dh)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(j == maxp - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)     # unreachable guard (pos 0 is
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)  # always valid)


def _layer_operand(layer):
    return jnp.asarray(layer, jnp.int32).reshape(1)


def paged_attention_decode(q, k_pages, v_pages, page_table, seq_pos, layer,
                           *, scale: Optional[float] = None,
                           interpret: bool = False):
    """Fused one-token GQA decode over one layer of the stacked K/V pool.

    ``q``: (B, 1, H, dh); ``k_pages``/``v_pages``: (L, num_pages, *block),
    each page's block in :func:`kv_page_order` ``(dh)``; ``page_table``:
    (B, max_pages) int32; ``seq_pos``: (B,) int32; ``layer``: the scalar
    index into L, scalar-prefetched so the index map addresses the stacked
    pool directly.  Returns (B, 1, H, dh) in ``q.dtype`` — the same
    contract as the reference gather + ``decode_attention`` read (write
    happens outside).
    """
    B, S, H, dh = q.shape
    assert S == 1
    order = kv_page_order(dh)
    block = k_pages.shape[2:]
    hkv, page = block[0], block[order.index(0)]
    maxp = page_table.shape[1]
    scale = dh ** -0.5 if scale is None else scale
    # ONE physical page of one layer per grid step, straight from the table
    page_spec = pl.BlockSpec((None, None) + block,
                             lambda b, j, t, sp, ly: (ly[0], t[b, j], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_table, seq_pos, layer
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda b, j, t, sp, ly: (b, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda b, j, t, sp, ly: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, dh), jnp.float32),  # weighted-value accumulator
            pltpu.VMEM((H, 1), jnp.float32),   # running max
            pltpu.VMEM((H, 1), jnp.float32),   # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _gqa_decode_kernel, page=page, maxp=maxp, groups=H // hkv,
            scale=scale, page_minor=order[-1] == 0,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        interpret=interpret,
        name="paged_attention_decode",
    )(page_table.astype(jnp.int32), seq_pos.astype(jnp.int32),
      _layer_operand(layer), q[:, 0], k_pages, v_pages)
    return out[:, None]


# --------------------------------------------------------------------------
# MLA paged decode (absorbed-matmul over streamed latent pages)
# --------------------------------------------------------------------------

def _mla_decode_kernel(table_ref, seqpos_ref, layer_ref, ql_ref, qr_ref,
                       ckv_ref, kr_ref, o_ref, acc_ref, m_ref, l_ref, *,
                       page: int,
                       maxp: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lat = ql_ref[0].astype(jnp.float32)   # (H, r)
    q_rope = qr_ref[0].astype(jnp.float32)  # (H, dr)
    ckv = ckv_ref[...].astype(jnp.float32)  # (page, r)
    kr = kr_ref[...].astype(jnp.float32)    # (page, dr)
    # absorbed scores against this page's latents: (H, page)
    s = jax.lax.dot_general(
        q_lat, ckv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s += jax.lax.dot_general(
        q_rope, kr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s *= scale
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    s = jnp.where(kpos <= seqpos_ref[b], s, _MASK)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p_att = jnp.exp(s - m_new)              # (H, page)
    l_ref[...] = l_prev * alpha + jnp.sum(p_att, axis=-1, keepdims=True)
    # latent-space output accumulation: (H, page) x (page, r) -> (H, r)
    pv = jax.lax.dot_general(
        p_att, ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(j == maxp - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def mla_paged_attention_decode(q_lat, q_rope, ckv_pages, krope_pages,
                               page_table, seq_pos, layer, *, scale: float,
                               interpret: bool = False):
    """Fused one-token MLA decode over one layer of the stacked *latent*
    pool.

    ``q_lat``: (B, 1, H, r) — q_nope already absorbed through ``W_kv_b``;
    ``q_rope``: (B, 1, H, dr); ``ckv_pages``: (L, num_pages, page, r);
    ``krope_pages``: (L, num_pages, page, dr); ``layer``: the scalar index
    into L (scalar-prefetched, like the page table).  Returns the
    latent-space attention output ``o_lat`` (B, 1, H, r) in
    ``ckv_pages.dtype`` — the caller applies the value expansion (a
    per-token matmul, not a paged read).
    """
    B, S, H, r = q_lat.shape
    assert S == 1
    page = ckv_pages.shape[2]
    dr = q_rope.shape[-1]
    maxp = page_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_table, seq_pos, layer
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, j, t, sp, ly: (b, 0, 0)),
            pl.BlockSpec((1, H, dr), lambda b, j, t, sp, ly: (b, 0, 0)),
            pl.BlockSpec((None, None, page, r),
                         lambda b, j, t, sp, ly: (ly[0], t[b, j], 0, 0)),
            pl.BlockSpec((None, None, page, dr),
                         lambda b, j, t, sp, ly: (ly[0], t[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda b, j, t, sp, ly: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, r), jnp.float32),   # o_lat accumulator
            pltpu.VMEM((H, 1), jnp.float32),   # running max
            pltpu.VMEM((H, 1), jnp.float32),   # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, page=page, maxp=maxp,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), ckv_pages.dtype),
        interpret=interpret,
        name="mla_paged_attention_decode",
    )(page_table.astype(jnp.int32), seq_pos.astype(jnp.int32),
      _layer_operand(layer), q_lat[:, 0], q_rope[:, 0], ckv_pages,
      krope_pages)
    return out[:, None]


# --------------------------------------------------------------------------
# COW page copy
# --------------------------------------------------------------------------

def _copy_kernel(src_ref, dst_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _tiled_order(shape) -> tuple:
    """Axis order of a stacked pool whose two minor dims fill TPU tiles.

    Within one page the minor dim should be a multiple of the 128-lane
    width, and the second-minor one a multiple or a divisor of the 8-row
    sublane count.  XLA's TPU layout assignment stores a pool in such an
    order, so the transpose into it is a bitcast there, and a kernel
    reading the transposed view needs no pool-sized relayout copy.  Dense
    K/V pools are stored tiled already (:func:`kv_page_order`), so their
    view is the identity; MLA's ``(page, 64)`` rope-key pages are read
    ``(64, page)``.
    """
    axes = list(range(2, len(shape)))
    minor = next((a for a in reversed(axes) if shape[a] % LANES == 0),
                 axes[-1])
    axes.remove(minor)
    if axes:
        second = next((a for a in reversed(axes)
                       if shape[a] % 8 == 0 or 8 % shape[a] == 0), axes[-1])
        axes.remove(second)
        axes.append(second)
    return (0, 1, *axes, minor)


def paged_copy(pool, src, dst, *, interpret: bool = False):
    """Copy physical page ``src`` -> ``dst`` in one stacked page pool.

    ``pool``: (L, num_pages, *block) — any paged leaf (dense K/V or MLA
    latent; the page axis is axis 1 after layer stacking).  The kernel
    reads the pool through its tile-aligned view (:func:`_tiled_order`),
    one whole page block per grid step, one step per stacked layer;
    scalar-prefetched page ids drive the input and output index maps, and
    ``input_output_aliases`` makes every non-``dst`` page a true no-op (the
    engine's donating COW jit keeps its in-place aliasing — the pool is
    never duplicated).  Bit-exact by construction.
    """
    order = _tiled_order(pool.shape)
    view = jnp.transpose(pool, order)
    block = (None, None) + view.shape[2:]
    rest = (0,) * (view.ndim - 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # src page id, dst page id (each shape (1,))
        grid=(view.shape[0],),
        in_specs=[pl.BlockSpec(block, lambda l, s, d: (l, s[0], *rest))],
        out_specs=pl.BlockSpec(block, lambda l, s, d: (l, d[0], *rest)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        # operand indices include the scalar-prefetch args: aliased operand
        # 2 is the pool itself
        input_output_aliases={2: 0},
        interpret=interpret,
        name="paged_copy",
    )(jnp.asarray(src, jnp.int32).reshape(1),
      jnp.asarray(dst, jnp.int32).reshape(1), view)
    return jnp.transpose(out, [order.index(a) for a in range(out.ndim)])
