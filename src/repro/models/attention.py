"""Attention modules: GQA (full / sliding-window, RoPE / M-RoPE) and MLA.

Functional style: ``init`` returns a params dict; ``forward`` handles the
three execution modes:

* ``train``    — full-sequence, no cache;
* ``prefill``  — full-sequence, returns a populated cache;
* ``decode``   — one token against the cache (ring buffer for SWA).

MLA (DeepSeek-V3) caches the *latent* c_kv + the shared rotary key — the
point of MLA — and uses the absorbed-matmul formulation at decode time.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import axes as AX
from repro.distributed.axes import constrain
from repro.kernels.paged_attention import (
    KV_HEAD_AXIS,
    kv_page_order,
    mla_paged_attention_decode,
    paged_attention_decode,
    paged_copy,
)
from repro.models.common import (
    apply_mrope,
    apply_rope,
    chunked_attention,
    decode_attention,
    dense,
    dense_init,
)


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig) -> Dict:
    d, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, H * dh, cfg.dtype),
        "wk": dense_init(ks[1], d, Hkv * dh, cfg.dtype),
        "wv": dense_init(ks[2], d, Hkv * dh, cfg.dtype),
        "wo": dense_init(ks[3], H * dh, d, cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), cfg.dtype)
        p["bk"] = jnp.zeros((Hkv * dh,), cfg.dtype)
        p["bv"] = jnp.zeros((Hkv * dh,), cfg.dtype)
    return p


def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int, window_only: bool):
    """Cache for one layer.  SWA keeps only ``window`` slots (ring buffer)."""
    slots = min(cfg.window, max_len) if window_only else max_len
    dh = cfg.d_head
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, dh), cfg.dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, dh), cfg.dtype),
        "pos": jnp.full((batch, slots), -1, jnp.int32),
    }


def _project_qkv(p, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = dense(cfg, x, p["wq"])
    k = dense(cfg, x, p["wk"])
    v = dense(cfg, x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q, ("dp", None, "tp"))
    k = constrain(k, ("dp", None, "tp"))
    v = constrain(v, ("dp", None, "tp"))
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, Hkv, dh)
    v = v.reshape(B, S, Hkv, dh)
    if cfg.use_rope:
        if cfg.mrope_sections:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, S, d)
    positions,  # (B, S) or (3, B, S) for M-RoPE
    *,
    mode: str = "train",
    cache: Optional[Dict] = None,
    pos_offset=0,  # scalar: absolute position of x[:, 0] (decode/prefill)
    causal: bool = True,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, S, _ = x.shape
    window = window if window is not None else (
        cfg.window if cfg.attn_type == "swa" else None  # repro: noqa RPR004 -- default-arg resolution, not family dispatch
    )
    q, k, v = _project_qkv(p, cfg, x, positions)

    if mode == "decode":
        assert cache is not None and S == 1
        slots = cache["k"].shape[1]
        slot = jnp.mod(pos_offset, slots)
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        pos_new = jnp.full((B, 1), pos_offset, jnp.int32)
        pos_cache = jax.lax.dynamic_update_slice(cache["pos"], pos_new, (0, slot))
        out = decode_attention(
            q, k_cache, v_cache, pos_cache, pos_offset, window=window
        )
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache}
    else:
        out = chunked_attention(
            q, k, v, causal=causal, q_offset=pos_offset,
            window=window, q_chunk=cfg.q_chunk,
        )
        new_cache = None
        if mode == "prefill":
            # populate the cache (SWA: keep the trailing ``window`` tokens)
            slots = min(cfg.window, S) if window is not None else S
            ks, vs = k[:, -slots:], v[:, -slots:]
            pos = jnp.broadcast_to(
                jnp.arange(S - slots, S, dtype=jnp.int32)[None], (B, slots)
            )
            if window is not None and slots == cfg.window:
                # ring-buffer order: token at absolute position p sits in slot
                # p % window, so later decode steps index consistently.
                slot_of = jnp.mod(jnp.arange(S - slots, S), slots)
                inv = jnp.argsort(slot_of)
                ks, vs, pos = ks[:, inv], vs[:, inv], pos[:, inv]
            new_cache = {"k": ks, "v": vs, "pos": pos}
    out = out.reshape(B, S, cfg.n_heads * cfg.d_head)
    return dense(cfg, out, p["wo"]), new_cache


# --------------------------------------------------------------------------
# Paged GQA decode (block-paged KV cache, page size = accelerator block)
# --------------------------------------------------------------------------

def paged_cache_init(cfg: ModelConfig, num_pages: int, page_size: int) -> Dict:
    """One layer's share of the physical page pool.

    Pages are the accelerator-block-sized unit of cache memory (the paper's
    arrangement quantum applied to the KV cache): page ``i`` of this layer
    holds ``page_size`` contiguous token slots.  Physical page ids are shared
    across layers — a request's page table indexes every layer's pool with
    the same ids.  Page 0 is reserved as the null page (write target for
    inactive slots, gather target for unmapped table entries).

    A page's logical axes ``(page_size, n_kv_heads, d_head)`` are laid out
    in :func:`~repro.kernels.paged_attention.kv_page_order`, chosen from
    the head dim so that the page's minor dims fill TPU tiles as stored,
    for any number of KV heads a device holds: ``(hkv, page, dh)`` where
    ``d_head`` is a multiple of 128, else ``(hkv, dh, page)`` with the page
    axis on the lanes.  The array's shape is that stored order, so XLA
    keeps the pool as declared and nothing relayouts it; every reader and
    writer goes through :func:`write_page_tokens` / :func:`_kv_tokens`,
    which know the order.
    """
    logical = (page_size, cfg.n_kv_heads, cfg.d_head)
    block = tuple(logical[a] for a in kv_page_order(cfg.d_head))
    return {
        "k_pages": jnp.zeros((num_pages,) + block, cfg.dtype),
        "v_pages": jnp.zeros((num_pages,) + block, cfg.dtype),
    }


def write_page_tokens(pool, layer, phys, off, x, page_axis: int = 0):
    """Write runs of consecutive tokens into a layer-stacked page pool.

    ``pool``: (L, num_pages, *block), the token axis at ``page_axis`` of
    the block.  ``phys``/``off``: (R, C) physical page and in-page offset
    of each token; each row is a run of C consecutive positions of one
    sequence (a decode step is B runs of one token, a prefill chunk one
    run of C).  ``x``: (R, C, *rest), the block's other axes in their
    stored order; ``layer``: a scalar, or (R,) layers, one per run.  The
    donated pool is updated where it lies:

    * token axis ahead of the lane axis (``(hkv, page, dh)`` K/V pages,
      MLA latent ``(page, r)`` pages): each token is one lane row of each
      head's tile, and one XLA scatter puts the rows straight in, indexed
      by head as well, so that each update is a single row (a window that
      spans the heads would have XLA put the heads on the sublanes and
      relayout the pool around every kernel call);
    * token axis on the lanes (``(hkv, dh, page)`` K/V pages): a token is
      one lane of every tile of its page, and a scatter of such strips
      makes XLA relayout the whole pool around it.  So each page a run
      touches is sliced out, its lanes merged with the run's tokens, and
      the whole pages scattered back at once (windows of the full block).
      Tokens routed to the null page keep the real page's old content.
    """
    R, C = phys.shape
    layer = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (R,))
    x = x.astype(pool.dtype)
    if page_axis == 0:
        return pool.at[layer[:, None], phys, off].set(x)
    if page_axis < pool.ndim - 3:
        heads = jnp.arange(pool.shape[2])
        return pool.at[layer[:, None, None], phys[:, :, None], heads,
                       off[:, :, None]].set(x)
    block = pool.shape[2:]
    page = block[-1]
    n = (C + page - 2) // page + 1  # pages a run of C tokens can touch
    if C > 1:
        # pad the run so that every lane of every touched page slices a
        # token (or a pad that lands nowhere)
        pad = (page, n * page - C)
        x_pad = jnp.pad(x, ((0, 0), pad) + ((0, 0),) * (x.ndim - 2))
        phys_pad = jnp.pad(phys, ((0, 0), pad), constant_values=-1)
    lane = jnp.arange(page)
    zeros = (0,) * len(block)
    layers, targets, pages = [], [], []
    for r in range(R):
        first = off[r, 0]  # in-page offset of the run's first token
        tok_page = (first + jnp.arange(C)) // page
        for k in range(n):
            # the real page the run's tokens in its k-th page go to
            target = jnp.max(jnp.where(tok_page == k, phys[r], 0))
            old = jax.lax.dynamic_slice(
                pool, (layer[r], target) + zeros, (1, 1) + block)[0, 0]
            if C == 1:  # a lone token fills lane `first` of its page
                vals = x[r, 0][..., None]
                lands = lane == first
            else:
                t0 = k * page - first + page  # padded index of lane 0's token
                vals = jnp.moveaxis(jax.lax.dynamic_slice_in_dim(
                    x_pad[r], t0, page, axis=0), 0, -1)
                lands = (jax.lax.dynamic_slice_in_dim(phys_pad[r], t0, page)
                         == target)
            layers.append(layer[r])
            targets.append(target)
            pages.append(jnp.where(lands, vals, old))
    return pool.at[jnp.stack(layers), jnp.stack(targets)].set(
        jnp.stack(pages))


def _kv_tokens(pages, d_head: int):
    """Gathered K/V pages ``(..., *block)`` in logical ``(..., page, hkv,
    dh)`` order."""
    n = pages.ndim - 3
    order = kv_page_order(d_head)
    return jnp.transpose(pages, (*range(n), *(n + order.index(a)
                                              for a in range(3))))


def paged_gather_attend(q, k_pages, v_pages, page_table, seq_pos, layer):
    """The jnp gather->attend oracle read (the ``"reference"`` path of
    :func:`paged_attend`).

    Gathers each slot's logical pages of layer ``layer`` back into a dense
    (B, max_pages*page, Hkv, dh) HBM buffer and runs the same masked
    one-token attention as the linear cache — keys beyond ``seq_pos`` (tail
    of a partial page, unmapped null-page entries, stale pages of retired
    requests) sit at positions above it and mask exactly like empty slots.
    """
    B, dh = q.shape[0], q.shape[-1]
    maxp = page_table.shape[1]
    kg = _kv_tokens(k_pages[layer, page_table], dh)  # (B, maxp, page, ...)
    vg = _kv_tokens(v_pages[layer, page_table], dh)
    page, hkv = kg.shape[2], kg.shape[3]
    kg = kg.reshape(B, maxp * page, hkv, dh)
    vg = vg.reshape(B, maxp * page, hkv, dh)
    # gathered keys sit at their absolute positions by construction
    k_positions = jnp.broadcast_to(
        jnp.arange(maxp * page, dtype=jnp.int32)[None], (B, maxp * page)
    )
    return decode_attention(q, kg, vg, k_positions, seq_pos, window=None)


def gqa_paged_decode(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, 1, d) — one token per slot
    positions: jnp.ndarray,  # (B, 1) per-slot absolute positions (RoPE)
    cache: Dict,  # {"k_pages", "v_pages"} (L, num_pages, *block), stacked
    page_table: jnp.ndarray,  # (B, max_pages) physical page per logical page
    seq_pos: jnp.ndarray,  # (B,) absolute position of the new token
    layer,  # scalar index of this layer in the stacked pool
    active: Optional[jnp.ndarray] = None,  # (B,) slots actually decoding
) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode against layer ``layer`` of the block-paged cache.

    Write: the new K/V lands in page ``page_table[b, pos // page]`` at offset
    ``pos % page`` of the stacked pool, which the layer scan carries whole:
    no slice of the pool is cut out or written back.  Read: through
    :func:`paged_attend` — the reference
    path gathers each slot's logical pages back into order and runs the
    linear cache's masked attention (:func:`paged_gather_attend`); the
    pallas path streams the page-table row through the fused kernel
    without materializing the gathered history.

    ``active`` marks slots whose write should land: inactive slots (idle,
    or mid-way through a chunked prefill — whose page table rows are live!)
    are routed to the reserved null page so the lockstep batch step cannot
    corrupt state it does not own.
    """
    B, S, _ = x.shape
    assert S == 1
    q, k, v = _project_qkv(p, cfg, x, positions)
    page_axis = kv_page_order(cfg.d_head).index(0)
    page = cache["k_pages"].shape[2 + page_axis]
    logical = seq_pos // page  # (B,) logical page of the new token
    phys = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, 0)  # null page absorbs idle writes
    off = seq_pos % page
    # scatter the new token (inactive slots carry page_table rows of 0 and
    # seq_pos 0, so their writes land in the reserved null page)
    k_pages = write_page_tokens(cache["k_pages"], layer, phys[:, None],
                                off[:, None], k, page_axis)
    v_pages = write_page_tokens(cache["v_pages"], layer, phys[:, None],
                                off[:, None], v, page_axis)
    out = paged_attend(cfg, q, k_pages, v_pages, page_table, seq_pos, layer)
    out = out.reshape(B, 1, cfg.n_heads * cfg.d_head)
    return dense(cfg, out, p["wo"]), {"k_pages": k_pages, "v_pages": v_pages}


def paged_copy_page(cache: Dict, src, dst) -> Dict:
    """Copy one physical page (``src`` -> ``dst``) in every page pool.

    The copy-on-write step for shared-prefix serving: when a slot must write
    into a page whose refcount is > 1 (aliased by other requests or pinned
    by the prefix index), the host allocates a fresh page, this copy runs
    inside a donating jit, and the slot's page-table entry is swapped to the
    private copy.  Page ids are traced scalars, so every COW event shares
    one compiled shape.  Works on any pool whose leaves are
    ``(L, num_pages, *block)`` — dense/GQA K/V pages in either stored order
    and MLA latent pages alike (the page-id axis is axis 1 after the layer
    stack).

    This dense dynamic-slice copy is the ``"reference"`` path of
    :func:`cow_copy_page`, whose ``"pallas"`` path is the scalar-prefetched
    single-page copy kernel (:func:`repro.kernels.paged_attention.paged_copy`)
    — bit-exact either way.
    """
    out = {}
    for name, pool in cache.items():
        row = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
        out[name] = jax.lax.dynamic_update_slice_in_dim(pool, row, dst, axis=1)
    return out


# --------------------------------------------------------------------------
# Paged-decode execution path: the jnp oracles or the Pallas kernels
# --------------------------------------------------------------------------
# ``cfg.decode_backend`` picks, per op, the jnp oracle (``"reference"``) or
# the fused kernel of :mod:`repro.kernels.paged_attention` (``"pallas"``).
# The kernels are not jitted here: they trace inline in the engine's
# donating step jits (a nested jit would hazard the donation aliasing the
# in-place pool update depends on).  A pallas_call cannot be partitioned by
# GSPMD, so under an active tensor-parallel policy (the engine's
# mesh-traced steps install one) a kernel runs per shard through
# ``shard_map``, the head axis split over the model axis.  Attention is
# head-independent, so each shard's online softmax is the unsharded
# kernel's on its own heads: pages never cross devices, and no collective
# is inserted here (only the row-parallel ``wo`` all-reduces, outside).

#: the values of ``ModelConfig.decode_backend``
DECODE_BACKENDS = ("reference", "pallas")


def uses_paged_kernels(backend: str) -> bool:
    """Whether the decode path ``backend`` names runs the Pallas kernels
    (``"pallas"``) rather than the jnp oracles (``"reference"``)."""
    if backend not in DECODE_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; available: "
                         f"{sorted(DECODE_BACKENDS)}")
    return backend == "pallas"


def kernels_interpreted() -> bool:
    """The paged kernels run in the Pallas interpreter on the CPU platform
    alone: any other platform compiles them, or fails."""
    return jax.default_backend() == "cpu"


def _tp_policy(*head_counts):
    """The active TP policy when every head count divides over its model
    axis, else None."""
    pol = AX.current()
    if (pol is not None and pol.mesh is not None and pol.tp_size > 1
            and all(h % pol.tp_size == 0 for h in head_counts)):
        return pol
    return None


def _on_axis(ndim: int, axis: int, name: str) -> P:
    return P(*(name if a == axis else None for a in range(ndim)))


def paged_attend(cfg: ModelConfig, q, k_pages, v_pages, page_table, seq_pos,
                 layer):
    """Dense/GQA one-token attention over layer ``layer`` of the stacked K/V
    pools: :func:`paged_gather_attend`, or the fused kernel per KV-head
    shard."""
    if not uses_paged_kernels(cfg.decode_backend):
        return paged_gather_attend(q, k_pages, v_pages, page_table, seq_pos,
                                   layer)
    kernel = functools.partial(paged_attention_decode,
                               interpret=kernels_interpreted())
    pol = _tp_policy(q.shape[2], k_pages.shape[KV_HEAD_AXIS])
    if pol is not None:
        head = P(None, None, pol.tp_axis, None)
        pool = _on_axis(k_pages.ndim, KV_HEAD_AXIS, pol.tp_axis)
        kernel = jax.shard_map(
            kernel, mesh=pol.mesh,
            in_specs=(head, pool, pool, P(), P(), P()),
            out_specs=head, check_vma=False,
        )
    return kernel(q, k_pages, v_pages, page_table, seq_pos, layer)


def mla_paged_attend(cfg: ModelConfig, q_lat, q_rope, ckv_pages, krope_pages,
                     page_table, seq_pos, layer, *, scale):
    """MLA absorbed attention over layer ``layer`` of the stacked latent
    pools: :func:`mla_paged_gather_attend`, or the fused kernel per
    query-head shard."""
    if not uses_paged_kernels(cfg.decode_backend):
        return mla_paged_gather_attend(q_lat, q_rope, ckv_pages, krope_pages,
                                       page_table, seq_pos, layer, scale=scale)
    kernel = functools.partial(mla_paged_attention_decode, scale=scale,
                               interpret=kernels_interpreted())
    pol = _tp_policy(q_lat.shape[2])
    if pol is not None:
        # latent pages carry no head axis: they stay replicated and each
        # device attends its own query heads against the full pools
        head = P(None, None, pol.tp_axis, None)
        kernel = jax.shard_map(
            kernel, mesh=pol.mesh,
            in_specs=(head, head, P(), P(), P(), P(), P()),
            out_specs=head, check_vma=False,
        )
    return kernel(q_lat, q_rope, ckv_pages, krope_pages, page_table, seq_pos,
                  layer)


def cow_copy_page(cfg: ModelConfig, pools: Dict, src, dst) -> Dict:
    """Copy page ``src`` -> ``dst`` in every stacked pool of ``pools``:
    :func:`paged_copy_page` (jnp ops, which GSPMD partitions), or the copy
    kernel — per KV-head shard for a K/V pool, replicated for MLA's latent
    pools, which carry no head axis."""
    if not uses_paged_kernels(cfg.decode_backend):
        return paged_copy_page(pools, src, dst)
    copy = functools.partial(paged_copy, interpret=kernels_interpreted())
    out = {}
    for name, pool in pools.items():
        # a K/V pool is (L, num_pages, *3-d block), a latent one
        # (L, num_pages, page, r)
        pol = _tp_policy(pool.shape[KV_HEAD_AXIS]) if pool.ndim == 5 else None
        if pol is not None:
            spec = _on_axis(pool.ndim, KV_HEAD_AXIS, pol.tp_axis)
            out[name] = jax.shard_map(
                copy, mesh=pol.mesh, in_specs=(spec, P(), P()),
                out_specs=spec, check_vma=False,
            )(pool, src, dst)
        else:
            out[name] = copy(pool, src, dst)
    return out


def gqa_paged_prefill_chunk(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (1, C, d) — one prompt chunk for one slot
    positions: jnp.ndarray,  # (1, C) absolute positions q_off + [0, C)
    cache: Dict,  # {"k_pages", "v_pages"} (L, num_pages, *block), stacked
    table_row: jnp.ndarray,  # (max_pages,) this slot's page table row
    phys_tok: jnp.ndarray,  # (C,) physical page per chunk token
    off_tok: jnp.ndarray,  # (C,) in-page offset per chunk token
    q_off,  # scalar absolute position of x[:, 0]
    layer,  # scalar index of this layer in the stacked pool
) -> Tuple[jnp.ndarray, Dict]:
    """One prompt chunk against the block-paged cache (prefix-conditioned).

    Write first: the chunk's K/V scatters straight into its physical pages
    of layer ``layer`` (per-token ``(phys, off)`` targets; tokens past the
    slot's allocation are routed to the null page by the host).  Then
    gather the slot's whole page table back into logical order — the
    prefix written by earlier
    chunks AND this chunk's own keys — and run the same causal masked
    attention as full prefill.  Gathered keys sit at their absolute
    positions, so every unmasked key matches the one-shot prefill's key
    sequence in ascending-position order (bit-exactness) and pages beyond
    the current position mask out exactly like empty cache slots.
    """
    B, C, _ = x.shape
    assert B == 1
    q, k, v = _project_qkv(p, cfg, x, positions)
    page_axis = kv_page_order(cfg.d_head).index(0)
    k_pages = write_page_tokens(cache["k_pages"], layer, phys_tok[None],
                                off_tok[None], k, page_axis)
    v_pages = write_page_tokens(cache["v_pages"], layer, phys_tok[None],
                                off_tok[None], v, page_axis)
    page = k_pages.shape[2 + page_axis]
    maxp = table_row.shape[0]
    shape = (1, maxp * page, cfg.n_kv_heads, cfg.d_head)
    kg = _kv_tokens(k_pages[layer, table_row], cfg.d_head).reshape(shape)
    vg = _kv_tokens(v_pages[layer, table_row], cfg.d_head).reshape(shape)
    kpos = jnp.arange(maxp * page, dtype=jnp.int32)[None]
    out = chunked_attention(
        q, kg, vg, causal=True, q_offset=q_off, k_positions=kpos,
        q_chunk=cfg.q_chunk,
    )
    out = out.reshape(B, C, cfg.n_heads * cfg.d_head)
    return dense(cfg, out, p["wo"]), {"k_pages": k_pages, "v_pages": v_pages}


def gqa_ring_prefill_chunk(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (1, C, d)
    positions: jnp.ndarray,  # (1, C)
    cache_row: Dict,  # {"k", "v", "pos"} — (1, slots, ...) this slot's ring
    q_off,  # scalar absolute position of x[:, 0]
    *,
    window: int,
) -> Tuple[jnp.ndarray, Dict]:
    """One prompt chunk against the O(window) ring buffer (SWA).

    The prefix is gathered from the ring in **ascending position order**
    (ring slot of position p is p % slots, so the gather is a rotation);
    empty or reset entries carry position label -1 and mask out.  Attention
    then runs over [prefix ; chunk] with the same causal + window masking
    as full prefill — ascending-position key order keeps the surviving
    softmax terms in the one-shot prefill's summation order (bit-exactness).
    The chunk's trailing min(C, slots) tokens are then written into the ring
    at their p % slots homes, the layout every later chunk and decode step
    expects.
    """
    B, C, _ = x.shape
    assert B == 1
    q, k, v = _project_qkv(p, cfg, x, positions)
    slots = cache_row["k"].shape[1]
    # prefix positions q_off-slots .. q_off-1 in ascending order
    pref_pos = q_off - slots + jnp.arange(slots, dtype=jnp.int32)
    idx = jnp.mod(pref_pos, slots)
    keys = jnp.concatenate([cache_row["k"][:, idx], k], axis=1)
    vals = jnp.concatenate([cache_row["v"][:, idx], v], axis=1)
    kpos = jnp.concatenate([cache_row["pos"][:, idx], positions], axis=1)
    out = chunked_attention(
        q, keys, vals, causal=True, q_offset=q_off, k_positions=kpos,
        window=window, q_chunk=cfg.q_chunk,
    )
    # persist the chunk's trailing tokens (older ones fall off the ring)
    w = min(C, slots)
    wpos = positions[0, C - w:]  # (w,)
    widx = jnp.mod(wpos, slots)
    new_row = {
        "k": cache_row["k"].at[:, widx].set(k[:, C - w:]),
        "v": cache_row["v"].at[:, widx].set(v[:, C - w:]),
        "pos": cache_row["pos"].at[:, widx].set(wpos[None]),
    }
    out = out.reshape(B, C, cfg.n_heads * cfg.d_head)
    return dense(cfg, out, p["wo"]), new_row


def cross_attention(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                    k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Non-causal attention over a fixed encoder-side K/V (enc-dec cross).

    The ONE implementation both the static decoder layer and the engine's
    cross adapter call — q/softmax/output math cannot drift between them
    (the bit-exactness guarantee leans on this).
    """
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.d_head)
    out = chunked_attention(q, k, v, causal=False, q_chunk=cfg.q_chunk)
    return out.reshape(B, S, -1) @ p["wo"]


def gqa_ring_decode(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, 1, d)
    positions: jnp.ndarray,  # (B, 1)
    cache: Dict,  # {"k", "v", "pos"} — (B, slots, ...) ring buffer
    seq_pos: jnp.ndarray,  # (B,) absolute position of the new token
    *,
    window: Optional[int] = None,
    active: Optional[jnp.ndarray] = None,  # (B,) slots actually decoding
) -> Tuple[jnp.ndarray, Dict]:
    """Per-slot-position decode against the O(window) ring buffer (SWA).

    Same layout as the static-wave ring (token at absolute position p sits in
    slot p % slots) but each batch slot advances independently, which is what
    continuous batching needs.  Inactive slots (idle, or mid-way through a
    chunked prefill whose ring rows are being built incrementally) write to
    ring index ``slots`` — out of bounds, so the scatter drops it.
    """
    B, S, _ = x.shape
    assert S == 1
    q, k, v = _project_qkv(p, cfg, x, positions)
    slots = cache["k"].shape[1]
    slot = seq_pos % slots  # (B,)
    if active is not None:
        slot = jnp.where(active, slot, slots)  # OOB scatter index: dropped
    rows = jnp.arange(B)
    k_cache = cache["k"].at[rows, slot].set(k[:, 0])
    v_cache = cache["v"].at[rows, slot].set(v[:, 0])
    pos_cache = cache["pos"].at[rows, slot].set(seq_pos)
    out = decode_attention(q, k_cache, v_cache, pos_cache, seq_pos, window=window)
    out = out.reshape(B, 1, cfg.n_heads * cfg.d_head)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache}
    return dense(cfg, out, p["wo"]), new_cache


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# --------------------------------------------------------------------------
# Paged variants live below mla_forward: the engine pages the *latent*
# c_kv + shared rotary key (kv_lora_rank + qk_rope_dim floats per token
# instead of 2 * n_kv_heads * d_head) and decodes with the absorbed-matmul
# formulation straight over the gathered latent pages.

def mla_init(key, cfg: ModelConfig) -> Dict:
    d, H = cfg.d_model, cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], d, r_q, cfg.dtype),
        "q_norm": jnp.ones((r_q,), cfg.dtype),
        "wq_b": dense_init(ks[1], r_q, H * (dn + dr), cfg.dtype),
        "wkv_a": dense_init(ks[2], d, r_kv + dr, cfg.dtype),
        "kv_norm": jnp.ones((r_kv,), cfg.dtype),
        "wkv_b": dense_init(ks[3], r_kv, H * (dn + dv), cfg.dtype),
        "wo": dense_init(ks[4], H * dv, d, cfg.dtype),
    }


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int):
    """MLA caches the latent (r_kv) + shared rotary key (dr) — tiny vs GQA."""
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.dtype),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), cfg.dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def _rms(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _mla_qkv_latent(p, cfg: ModelConfig, x, positions):
    """Common projections: per-head q (nope+rope), latent ckv, shared k_rope."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = _rms(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv = x @ p["wkv_a"]  # (B, S, r_kv + dr)
    ckv = _rms(kv[..., : cfg.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(
        kv[..., cfg.kv_lora_rank:][:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0]  # (B, S, dr) — shared across heads
    return q_nope, q_rope, ckv, k_rope


# The two MLA attention formulations, each implemented ONCE: the linear-
# cache decode and the paged decode both call _mla_absorbed_attend, the
# one-shot prefill and the paged prefill chunk both call
# _mla_expanded_attend — the engine's bit-exactness guarantee against the
# static Server leans on the math being impossible to drift apart.

def mla_latent_attend(q_lat, q_rope, ckv_c, kr_c, valid, *, scale):
    """Latent-space MLA attention (the absorbed formulation's core).

    ``q_lat``: (B, S, H, r) — q_nope already absorbed through ``W_kv_b``;
    ``valid``: (B, K) key mask.  Returns the latent-space output ``o_lat``
    (B, S, H, r) — the caller applies the value expansion.  This is the
    ``"reference"`` MLA decode read (over gathered latents); the
    pallas kernel reproduces exactly this math page-by-page.
    """
    s = jnp.einsum("bshr,bkr->bhsk", q_lat, ckv_c,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bshd,bkd->bhsk", q_rope, kr_c,
                    preferred_element_type=jnp.float32)
    s *= scale
    s = jnp.where(valid[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    att = jax.nn.softmax(s, -1).astype(ckv_c.dtype)  # (B, H, S, K)
    return jnp.einsum("bhsk,bkr->bshr", att, ckv_c)


def mla_paged_gather_attend(q_lat, q_rope, ckv_pages, krope_pages,
                            page_table, seq_pos, layer, *, scale):
    """The jnp gather->attend oracle over latent pages (reference op).

    Gathers each slot's latent pages of layer ``layer`` into logical order
    and scores with :func:`mla_latent_attend`; gathered entries sit at
    their absolute positions, so masking by ``k_pos <= seq_pos`` reproduces
    the linear cache's valid set exactly.
    """
    B = q_lat.shape[0]
    page, r_kv = ckv_pages.shape[2], ckv_pages.shape[3]
    maxp = page_table.shape[1]
    ckv_g = ckv_pages[layer, page_table].reshape(B, maxp * page, r_kv)
    kr_g = krope_pages[layer, page_table].reshape(B, maxp * page, -1)
    k_positions = jnp.arange(maxp * page, dtype=jnp.int32)
    valid = k_positions[None] <= seq_pos[:, None]  # (B, K)
    return mla_latent_attend(q_lat, q_rope, ckv_g, kr_g, valid, scale=scale)


def _mla_absorbed_attend(cfg: ModelConfig, wkv_b, q_nope, q_rope,
                         ckv_c, kr_c, valid):
    """Absorbed-matmul MLA attention over a latent cache.

    score = q_nope . (W_kv_b,k^T c) + q_rope . k_rope
          = (q_nope W_k^T) . c + q_rope . k_rope
    ``valid``: (B, K) key mask.  Returns (B, S, H, v_head_dim).
    """
    dn = cfg.qk_nope_dim
    scale = (dn + cfg.qk_rope_dim) ** -0.5
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wkv_b[..., :dn])
    o_lat = mla_latent_attend(q_lat, q_rope, ckv_c, kr_c, valid, scale=scale)
    return jnp.einsum("bshr,rhd->bshd", o_lat, wkv_b[..., dn:])  # value expand


def _mla_expanded_attend(cfg: ModelConfig, wkv_b, q_nope, q_rope,
                         ckv, k_rope, *, pos_offset, k_positions=None):
    """Expanded-formulation MLA attention (train / prefill / prefill chunk).

    Each key position's kv expansion depends only on its own latent, so the
    same call serves contiguous latents and page-gathered ones (with
    ``k_positions`` labelling the gathered order).
    """
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    scale = (dn + dr) ** -0.5
    B, K = ckv.shape[:2]
    kv = jnp.einsum("bsr,rhd->bshd", ckv, wkv_b)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, K, H, dr))], -1
    )
    q = jnp.concatenate([q_nope, q_rope], -1)
    return chunked_attention(
        q, k, v, causal=True, q_offset=pos_offset, k_positions=k_positions,
        q_chunk=cfg.q_chunk, scale=scale,
    )


def mla_forward(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions,
    *,
    mode: str = "train",
    cache: Optional[Dict] = None,
    pos_offset=0,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dv = cfg.qk_nope_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank
    q_nope, q_rope, ckv, k_rope = _mla_qkv_latent(p, cfg, x, positions)
    wkv_b = p["wkv_b"].reshape(r_kv, H, dn + dv)

    if mode == "decode":
        assert cache is not None and S == 1
        ckv_c = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, pos_offset, 0))
        kr_c = jax.lax.dynamic_update_slice(cache["krope"], k_rope, (0, pos_offset, 0))
        pos_new = jnp.full((B, 1), pos_offset, jnp.int32)
        pos_c = jax.lax.dynamic_update_slice(cache["pos"], pos_new, (0, pos_offset))
        valid = (pos_c >= 0) & (pos_c <= pos_offset)
        out = _mla_absorbed_attend(cfg, wkv_b, q_nope, q_rope, ckv_c, kr_c,
                                   valid)
        new_cache = {"ckv": ckv_c, "krope": kr_c, "pos": pos_c}
    else:
        out = _mla_expanded_attend(cfg, wkv_b, q_nope, q_rope, ckv, k_rope,
                                   pos_offset=pos_offset)
        new_cache = None
        if mode == "prefill":
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
            new_cache = {"ckv": ckv, "krope": k_rope, "pos": pos}
    out = out.reshape(B, S, H * dv)
    return out @ p["wo"], new_cache


# --------------------------------------------------------------------------
# Paged MLA (latent pages — the continuous-batching engine's MLA cache)
# --------------------------------------------------------------------------

def mla_paged_cache_init(cfg: ModelConfig, num_pages: int, page_size: int) -> Dict:
    """One layer's share of the latent page pool.

    A page holds ``page_size`` token slots of the MLA *latent* cache — the
    rank-``kv_lora_rank`` c_kv plus the shared ``qk_rope_dim`` rotary key —
    which is all the absorbed-matmul decode ever reads.  Same page-id space
    and null-page discipline as the dense K/V pool.  Pages keep the
    row-major ``(page, r)`` order: the 512-wide latent fills the lanes as
    it is.
    """
    return {
        "ckv_pages": jnp.zeros(
            (num_pages, page_size, cfg.kv_lora_rank), cfg.dtype
        ),
        "krope_pages": jnp.zeros(
            (num_pages, page_size, cfg.qk_rope_dim), cfg.dtype
        ),
    }


def mla_paged_decode(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, 1, d) — one token per slot
    positions: jnp.ndarray,  # (B, 1) per-slot absolute positions (RoPE)
    cache: Dict,  # {"ckv_pages", "krope_pages"} (L, num_pages, page, .)
    page_table: jnp.ndarray,  # (B, max_pages) physical page per logical page
    seq_pos: jnp.ndarray,  # (B,) absolute position of the new token
    layer,  # scalar index of this layer in the stacked pool
    active: Optional[jnp.ndarray] = None,  # (B,) slots actually decoding
) -> Tuple[jnp.ndarray, Dict]:
    """Absorbed-matmul decode against layer ``layer`` of the latent pool.

    Write: the new token's (c_kv, k_rope) lands in its slot's page of the
    stacked pool the layer scan carries.  Read:
    through :func:`mla_paged_attend`, always in the absorbed formulation —
    q_nope is folded into the latent space through ``W_kv_b`` so attention
    runs over rank-r latents, never materializing per-head K/V.  The
    reference path gathers the latent pages into logical order
    (:func:`mla_paged_gather_attend`); the pallas path streams them
    page-by-page through the fused kernel.  Either way entries sit at
    their absolute positions, so masking by ``k_pos <= seq_pos``
    reproduces the linear cache's valid set exactly (stale pages /
    partial-page tails mask out like empty slots).
    """
    B, S, _ = x.shape
    assert S == 1
    H = cfg.n_heads
    dn, dv, r_kv = cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_rope, ckv, k_rope = _mla_qkv_latent(p, cfg, x, positions)
    wkv_b = p["wkv_b"].reshape(r_kv, H, dn + dv)

    page = cache["ckv_pages"].shape[2]
    logical = seq_pos // page
    phys = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, 0)  # null page absorbs idle writes
    off = seq_pos % page
    ckv_pages = write_page_tokens(cache["ckv_pages"], layer, phys[:, None],
                                  off[:, None], ckv)
    krope_pages = write_page_tokens(cache["krope_pages"], layer,
                                    phys[:, None], off[:, None], k_rope)

    scale = (dn + cfg.qk_rope_dim) ** -0.5
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wkv_b[..., :dn])
    o_lat = mla_paged_attend(cfg, q_lat, q_rope, ckv_pages, krope_pages,
                             page_table, seq_pos, layer, scale=scale)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, wkv_b[..., dn:])  # value expand
    out = out.reshape(B, 1, H * dv)
    return out @ p["wo"], {"ckv_pages": ckv_pages, "krope_pages": krope_pages}


def mla_paged_prefill_chunk(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (1, C, d) — one prompt chunk for one slot
    positions: jnp.ndarray,  # (1, C) absolute positions q_off + [0, C)
    cache: Dict,  # {"ckv_pages", "krope_pages"} (L, num_pages, page, .)
    table_row: jnp.ndarray,  # (max_pages,) this slot's page table row
    phys_tok: jnp.ndarray,  # (C,) physical page per chunk token
    off_tok: jnp.ndarray,  # (C,) in-page offset per chunk token
    q_off,  # scalar absolute position of x[:, 0]
    layer,  # scalar index of this layer in the stacked pool
) -> Tuple[jnp.ndarray, Dict]:
    """One prompt chunk against the latent page pool (prefix-conditioned).

    Write first (per-token latent scatter), then gather the slot's whole
    table row and run the *expanded* formulation over the gathered latent —
    the same per-position kv expansion and causal masked attention the
    one-shot prefill uses, so every unmasked key matches the one-shot key
    sequence in ascending-position order (bit-exactness).  The absorbed
    formulation is reserved for decode, where it is the win.
    """
    B, C, _ = x.shape
    assert B == 1
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank
    q_nope, q_rope, ckv, k_rope = _mla_qkv_latent(p, cfg, x, positions)
    wkv_b = p["wkv_b"].reshape(r_kv, H, dn + dv)

    ckv_pages = write_page_tokens(cache["ckv_pages"], layer, phys_tok[None],
                                  off_tok[None], ckv)
    krope_pages = write_page_tokens(cache["krope_pages"], layer,
                                    phys_tok[None], off_tok[None], k_rope)
    page = ckv_pages.shape[2]
    maxp = table_row.shape[0]
    K = maxp * page
    ckv_g = ckv_pages[layer, table_row].reshape(1, K, r_kv)
    kr_g = krope_pages[layer, table_row].reshape(1, K, dr)
    kpos = jnp.arange(K, dtype=jnp.int32)[None]
    out = _mla_expanded_attend(cfg, wkv_b, q_nope, q_rope, ckv_g, kr_g,
                               pos_offset=q_off, k_positions=kpos)
    out = out.reshape(B, C, H * dv)
    return out @ p["wo"], {"ckv_pages": ckv_pages, "krope_pages": krope_pages}
