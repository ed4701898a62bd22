"""CacheAdapter: one paged-cache protocol implementation per layer family.

The continuous-batching engine (:mod:`repro.serve`) stores decode context in
the units the accelerator kernel consumes — pages of ``cfg.block`` token
slots.  What a *page of context* means differs per layer family:

* full-attention dense/GQA layers page the K/V tensors themselves,
* MLA layers page the tiny latent ``c_kv`` + shared rotary key (the point
  of MLA: the latent is what the absorbed-matmul decode consumes),
* SWA layers keep an O(window) ring row per batch slot,
* SSM layers keep an O(1) state row per batch slot,
* encoder-decoder cross-attention keeps an immutable encoder-side K/V row
  per slot, installed once at admission.

Each family implements :class:`CacheAdapter`: pool shapes, the donated
prefill install, the chunked-prefill step, the per-slot decode step, and
the active-mask semantics that keep a lockstep batch step from corrupting
slots it does not own.  The engine, scheduler and model layers drive
adapters generically through :func:`adapters_for` — this module is the ONLY
place that knows which family uses which cache layout.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import KV_HEAD_AXIS, kv_page_order
from repro.models import attention as attn
from repro.models import ssm as ssmm


# --------------------------------------------------------------------------
# Segment structure (which layer kinds a config stacks, and how many)
# --------------------------------------------------------------------------

def layer_segments(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """Homogeneous layer groups, each scanned with stacked params."""
    if cfg.family == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        return [("hybrid", cfg.n_layers)]
    if cfg.family == "moe":
        segs = []
        if cfg.first_k_dense:
            segs.append(("dense", cfg.first_k_dense))
        segs.append(("moe", cfg.n_layers - cfg.first_k_dense))
        return segs
    return [("dense", cfg.n_layers)]  # dense / vlm / encdec decoder


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """Sizing of the engine's cache pools (tokens are page-granular)."""

    max_seqs: int
    num_pages: int
    page_size: int
    max_len: int


# --------------------------------------------------------------------------
# Shared slot-row helpers (per-slot, non-paged layouts)
# --------------------------------------------------------------------------

def read_slot_rows(seg_cache: Dict, slot) -> Dict:
    """Extract one batch slot's rows as a (1, ...) pytree (traced slot id)."""
    return {
        k: jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=0)
        for k, v in seg_cache.items()
    }


def write_slot_rows(seg_cache: Dict, rows: Dict, slot, *, axis: int = 0) -> Dict:
    """Scatter one slot's rows back into the per-slot cache arrays.

    ``axis`` is the slot axis: 0 inside a layer step (the leading L axis is
    scanned away), 1 for install into the full (L, max_seqs, ...) pools.
    """
    return {
        k: jax.lax.dynamic_update_slice_in_dim(
            seg_cache[k], rows[k].astype(seg_cache[k].dtype), slot, axis
        )
        for k in seg_cache
    }


def _install_paged(dst: Dict, src: Dict, phys_tok, off_tok,
                   names: Dict[str, str], page_axis: int = 0) -> Dict:
    """Scatter (L, S)-shaped prefill tensors per token into physical pages.

    ``names`` maps prefill-cache keys to pool keys (e.g. ``k -> k_pages``);
    ``page_axis`` is the token axis within a stored page.  Tokens past the
    slot's allocation arrive mapped to the null page (the bucketed-prefill
    pad tail), whose content is garbage by design.
    """
    out = dict(dst)
    for s_name, p_name in names.items():
        x = src[s_name][:, 0]  # (L, S, ...): one run of S tokens per layer
        L = x.shape[0]
        out[p_name] = attn.write_page_tokens(
            dst[p_name], jnp.arange(L),
            jnp.broadcast_to(phys_tok, (L,) + phys_tok.shape),
            jnp.broadcast_to(off_tok, (L,) + off_tok.shape), x, page_axis,
        )
    return out


# --------------------------------------------------------------------------
# The protocol
# --------------------------------------------------------------------------

class CacheAdapter:
    """One layer family's share of the engine cache.

    ``key`` is the segment-cache entry the adapter owns; ``param_key`` the
    layer-parameter subtree that drives it.  ``paged`` adapters draw on the
    shared physical page pool (page accounting in the allocator covers
    them); non-paged adapters own ``max_seqs`` per-slot rows.

    The engine's layer scan treats the two apart: a paged adapter's pools
    ride the scan's carry whole, so its ``chunk`` / ``decode`` receive the
    layer-stacked ``(L, num_pages, ...)`` pool plus the scalar ``layer``
    index and write only the tokens they add; a non-paged adapter receives
    this layer's rows, sliced by the scan, and ignores ``layer``.
    """

    key: str = ""
    param_key: str = ""
    family: str = ""  # human name the registry reports
    paged: bool = False
    # prefix sharing capability: a shareable adapter's cache entries are
    # position-indexed pages whose content is a pure function of the token
    # prefix, so physical pages may be aliased across requests (PagedAttn,
    # LatentMLA).  Slot-local rows (SWA rings, SSM states) and per-request
    # side-input caches (enc-dec cross rows) declare False.
    shareable: bool = False
    # True when the family's cache content depends on per-request inputs
    # beyond the token ids (enc-dec audio): the whole stack's hidden states
    # are then request-specific and token-keyed page aliasing is unsound
    # for EVERY co-resident adapter, not just this one.
    side_inputs: bool = False

    def copy_page(self, cfg: ModelConfig, seg_cache: Dict, src, dst) -> Dict:
        """Copy physical page ``src`` -> ``dst`` in this adapter's pools
        (the COW step; traced inside the engine's donating copy jit).
        Only meaningful for paged adapters."""
        raise NotImplementedError

    def pool_pspecs(self, cfg: ModelConfig, *, tp_axis: str = "model",
                    tp_size: int = 1) -> Dict:
        """PartitionSpec per **L-stacked** pool leaf for tensor-parallel
        serving (``{pool_name: PartitionSpec}``; missing names replicate).

        Specs describe the engine pools AFTER layer stacking (leading L
        axis, see :func:`repro.models.model.init_paged_cache`).  Page ids,
        page tables and free lists are host/replicated state and never
        appear here.  The base adapter replicates everything — families
        whose pools carry a kv-head axis override to shard it over the
        model axis when it divides, which is the mesh-parallel half of the
        paper's arrangement claim: each core streams only its own heads'
        pages.
        """
        return {}

    def chunk_multiple(self, cfg: ModelConfig) -> int:
        """Prefill chunk boundaries must sit on multiples of this."""
        return 1

    def init_pool(self, cfg: ModelConfig, geom: CacheGeometry) -> Dict:
        """One layer's share of the engine cache (pre L-stacking)."""
        raise NotImplementedError

    def install(self, cfg: ModelConfig, dst: Dict, src: Dict, slot,
                phys_tok, off_tok) -> Dict:
        """Write one request's one-shot prefill cache into its slot
        (traced inside the engine's donating install jit)."""
        raise NotImplementedError

    def src_tokens(self, src: Dict) -> Optional[int]:
        """Token count of a (possibly padded) paged prefill source — the
        host needs it to build per-token page targets.  None: not paged."""
        return None

    def chunk(self, p: Dict, cfg: ModelConfig, h, positions, cache: Dict,
              ctx: Dict, pos_offset):
        """One prompt chunk of one slot.  ``ctx`` carries {slot, first,
        table_row, phys_tok, off_tok, layer}.  Returns (mixer_out,
        new_cache)."""
        raise NotImplementedError

    def decode(self, p: Dict, cfg: ModelConfig, h, positions, cache: Dict,
               *, seq_pos, page_table, active, layer):
        """One lockstep decode step, every slot at its own position.
        Inactive slots' cache writes must be dropped (null page / OOB
        index / where-mask).  Returns (mixer_out, new_cache)."""
        raise NotImplementedError


class PagedAttnAdapter(CacheAdapter):
    """Full-attention dense/GQA: K/V paged in kernel-block-sized pages."""

    key = "attn"
    param_key = "attn"
    family = "dense/GQA (paged K/V)"
    paged = True
    shareable = True

    def init_pool(self, cfg, geom):
        """One layer's K/V pages, each page's ``(page, n_kv_heads,
        d_head)`` axes in the stored order the head dim picks
        (:func:`repro.kernels.paged_attention.kv_page_order`): ``(hkv,
        dh, page)`` for a head dim short of the 128 lanes (minicpm-2b's
        64), so the 128-token page axis fills them; ``(hkv, page, dh)``
        for a head dim that fills them itself (starcoder2's 128).  The
        heads lead, so a tensor-parallel shard of any number of them, one
        included, holds whole tiles.  The shape is the stored order, so
        the pool needs no relayout; the kernel, the reference gather, the
        scatters, the install and the COW copy all read and write it in
        that order."""
        return attn.paged_cache_init(cfg, geom.num_pages, geom.page_size)

    def copy_page(self, cfg, seg_cache, src, dst):
        return attn.cow_copy_page(cfg, seg_cache, src, dst)

    def pool_pspecs(self, cfg, *, tp_axis="model", tp_size=1):
        # stacked pools are (L, num_pages, *block): shard the kv-head axis
        # (which leads each page) so each device holds (and streams) 1/tp
        # of every page; pages themselves never cross devices.  Query
        # heads arrive pre-partitioned by the column-parallel wq/wk/wv, so
        # only the post-attention row-parallel wo all-reduces.
        if tp_size > 1 and cfg.n_kv_heads % tp_size == 0:
            head = P(*(tp_axis if a == KV_HEAD_AXIS else None
                       for a in range(5)))
            return {"k_pages": head, "v_pages": head}
        return {}

    def install(self, cfg, dst, src, slot, phys_tok, off_tok):
        return _install_paged(dst, src, phys_tok, off_tok,
                              {"k": "k_pages", "v": "v_pages"},
                              kv_page_order(cfg.d_head).index(0))

    def src_tokens(self, src):
        return int(src["k"].shape[2])

    def chunk(self, p, cfg, h, positions, cache, ctx, pos_offset):
        return attn.gqa_paged_prefill_chunk(
            p, cfg, h, positions, cache, ctx["table_row"],
            ctx["phys_tok"], ctx["off_tok"], pos_offset, ctx["layer"],
        )

    def decode(self, p, cfg, h, positions, cache, *, seq_pos, page_table,
               active, layer):
        return attn.gqa_paged_decode(
            p, cfg, h, positions, cache, page_table, seq_pos, layer,
            active=active,
        )


class RingAttnAdapter(CacheAdapter):
    """Sliding-window attention: O(window) ring row per batch slot."""

    key = "attn"
    param_key = "attn"
    family = "SWA (ring)"

    def init_pool(self, cfg, geom):
        return attn.gqa_cache_init(cfg, geom.max_seqs, geom.max_len,
                                   window_only=True)

    def pool_pspecs(self, cfg, *, tp_axis="model", tp_size=1):
        # stacked rings are (L, max_seqs, slots, n_kv_heads, d_head): the
        # head axis shards like the paged pools (ring attention is
        # head-independent); the position labels replicate.
        if tp_size > 1 and cfg.n_kv_heads % tp_size == 0:
            head = P(None, None, None, tp_axis, None)
            return {"k": head, "v": head}
        return {}

    def install(self, cfg, dst, src, slot, phys_tok, off_tok):
        slots_e = dst["k"].shape[2]  # engine ring length: min(window, max_len)
        got = src["k"].shape[2]  # prefill ring length: min(window, S)
        assert got <= slots_e, (got, slots_e)
        # token at absolute position p lives in ring slot p % slots_e; the
        # prefill packing already satisfies this for got == window
        # (== slots_e) and trivially for S < window (identity placement)
        out = {}
        for name, empty in (("k", 0.0), ("v", 0.0), ("pos", -1)):
            L = dst[name].shape[0]
            row_shape = (L, 1) + dst[name].shape[2:]
            row = jnp.full(row_shape, empty, dst[name].dtype)
            row = row.at[:, :, :got].set(src[name].astype(dst[name].dtype))
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                dst[name], row, slot, 1
            )
        return out

    def chunk(self, p, cfg, h, positions, cache, ctx, pos_offset):
        # the first chunk resets the row's position labels to -1 (masked-
        # empty) so a re-used slot cannot leak a previous occupant's window
        row = read_slot_rows(cache, ctx["slot"])
        row["pos"] = jnp.where(ctx["first"], -1, row["pos"])
        out, new_row = attn.gqa_ring_prefill_chunk(
            p, cfg, h, positions, row, pos_offset, window=cfg.window
        )
        return out, write_slot_rows(cache, new_row, ctx["slot"])

    def decode(self, p, cfg, h, positions, cache, *, seq_pos, page_table,
               active, layer):
        return attn.gqa_ring_decode(
            p, cfg, h, positions, cache, seq_pos, window=cfg.window,
            active=active,
        )


class LatentMLAAdapter(CacheAdapter):
    """MLA (DeepSeek-V3): latent ``c_kv`` + shared rotary key paged.

    Pages hold ``kv_lora_rank + qk_rope_dim`` floats per token instead of
    ``2 * n_kv_heads * d_head`` — the families with the most bandwidth to
    save from the paper's block-sized arrangement.  Decode runs the
    absorbed-matmul formulation straight over the gathered latent pages.
    """

    key = "attn"
    param_key = "attn"
    family = "MLA (latent pages)"
    paged = True
    shareable = True

    def init_pool(self, cfg, geom):
        return attn.mla_paged_cache_init(cfg, geom.num_pages, geom.page_size)

    def pool_pspecs(self, cfg, *, tp_axis="model", tp_size=1):
        # MLA latent pools carry NO head axis — the rank-r c_kv and the
        # shared rotary key are consumed by every query head, so the pages
        # replicate (they are tiny: r + dr floats per token vs
        # 2*Hkv*dh).  Head parallelism lives on the activation side: the
        # absorbed q_lat / q_rope are head-sharded by the column-parallel
        # wq projections and each device attends its own heads against the
        # replicated latent pages.
        return {"ckv_pages": P(), "krope_pages": P()}

    def copy_page(self, cfg, seg_cache, src, dst):
        return attn.cow_copy_page(cfg, seg_cache, src, dst)

    def install(self, cfg, dst, src, slot, phys_tok, off_tok):
        return _install_paged(dst, src, phys_tok, off_tok,
                              {"ckv": "ckv_pages", "krope": "krope_pages"})

    def src_tokens(self, src):
        return int(src["ckv"].shape[2])

    def chunk(self, p, cfg, h, positions, cache, ctx, pos_offset):
        return attn.mla_paged_prefill_chunk(
            p, cfg, h, positions, cache, ctx["table_row"],
            ctx["phys_tok"], ctx["off_tok"], pos_offset, ctx["layer"],
        )

    def decode(self, p, cfg, h, positions, cache, *, seq_pos, page_table,
               active, layer):
        return attn.mla_paged_decode(
            p, cfg, h, positions, cache, page_table, seq_pos, layer,
            active=active,
        )


class SSMStateAdapter(CacheAdapter):
    """SSM (mamba2 / hymba branch): O(1) state + conv rows per slot."""

    key = "ssm"
    param_key = "ssm"
    family = "SSM (state rows)"

    def chunk_multiple(self, cfg):
        # chunk boundaries must sit on the SSD chunk grid — the grid the
        # one-shot prefill uses — so every chunk reproduces the exact
        # per-chunk ops of the one-shot path (bit-exactness)
        return cfg.ssm_chunk

    def init_pool(self, cfg, geom):
        return ssmm.ssm_state_init(cfg, geom.max_seqs)

    def install(self, cfg, dst, src, slot, phys_tok, off_tok):
        return write_slot_rows(dst, src, slot, axis=1)

    def chunk(self, p, cfg, h, positions, cache, ctx, pos_offset):
        # on the first chunk the row is zeroed (a fresh request's state; the
        # row may hold garbage from a previous occupant) — zero state /
        # history is bit-identical to prefilling with no carried state
        row = read_slot_rows(cache, ctx["slot"])
        state_in = {
            "state": jnp.where(ctx["first"], 0.0, row["state"]),
            "conv": jnp.where(ctx["first"], 0.0, row["conv"]),
        }
        out, st = ssmm.ssm_forward(p, cfg, h, mode="prefill", state=state_in)
        return out, write_slot_rows(cache, st, ctx["slot"])

    def decode(self, p, cfg, h, positions, cache, *, seq_pos, page_table,
               active, layer):
        out, st = ssmm.ssm_forward(p, cfg, h, mode="decode", state=cache)
        if active is not None:
            st = jax.tree.map(
                lambda new, old: jnp.where(
                    active.reshape((-1,) + (1,) * (new.ndim - 1)),
                    new.astype(old.dtype), old,
                ), st, cache,
            )
        return out, st


class CrossAttnAdapter(CacheAdapter):
    """Encoder-decoder cross-attention: immutable encoder-side K/V rows.

    The encoder runs ONCE per request at admission; its projected K/V are
    installed into the slot's rows and never written again — chunked
    decoder prefill and decode both read the same rows, so preemption-with-
    recompute only re-runs the encoder, never corrupts it mid-stream.
    """

    key = "cross"
    param_key = "cross"
    family = "enc-dec (cross rows + paged self-attn)"
    installs_at_admission = True
    side_inputs = True  # cache content depends on the request's audio

    def init_pool(self, cfg, geom):
        dh = cfg.d_head
        return {
            "k": jnp.zeros(
                (geom.max_seqs, cfg.encoder_seq, cfg.n_kv_heads, dh), cfg.dtype
            ),
            "v": jnp.zeros(
                (geom.max_seqs, cfg.encoder_seq, cfg.n_kv_heads, dh), cfg.dtype
            ),
        }

    def pool_pspecs(self, cfg, *, tp_axis="model", tp_size=1):
        # stacked cross rows are (L, max_seqs, encoder_seq, n_kv_heads,
        # d_head): immutable per request, head-sharded like the paged pools
        # so cross-attention reads stay local to each device's heads.
        if tp_size > 1 and cfg.n_kv_heads % tp_size == 0:
            head = P(None, None, None, tp_axis, None)
            return {"k": head, "v": head}
        return {}

    def install(self, cfg, dst, src, slot, phys_tok, off_tok):
        return write_slot_rows(dst, src, slot, axis=1)

    def admission_src(self, cfg, params, batch: Dict) -> Dict:
        """Encoder-side K/V for one request, as a partial install source
        (the jitted call is memoized per config).  The stacked per-layer
        rows are split along the segment boundaries, so a multi-segment
        decoder gets every segment's share — no seg0 special case."""
        kv = _cross_src_fn(cfg)(params, batch["audio_embeds"])
        src, off = {}, 0
        for si, (kind, n) in enumerate(layer_segments(cfg)):
            if self in adapters_for(cfg, kind):
                src[f"seg{si}"] = {"cross": jax.tree.map(
                    lambda a: a[off:off + n], kv
                )}
            off += n
        return src

    def chunk(self, p, cfg, h, positions, cache, ctx, pos_offset):
        rows = read_slot_rows(cache, ctx["slot"])
        return attn.cross_attention(p, cfg, h, rows["k"], rows["v"]), cache

    def decode(self, p, cfg, h, positions, cache, *, seq_pos, page_table,
               active, layer):
        # read-only: inactive slots produce garbage that is discarded, and
        # there is no write to mask
        return attn.cross_attention(p, cfg, h, cache["k"], cache["v"]), cache


@functools.lru_cache(maxsize=None)
def _cross_src_fn(cfg: ModelConfig):
    from repro.models import model as M

    return jax.jit(functools.partial(M.encdec_cross_kv, cfg))


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

PAGED_GQA = PagedAttnAdapter()
RING_SWA = RingAttnAdapter()
MLA_LATENT = LatentMLAAdapter()
SSM_STATE = SSMStateAdapter()
CROSS_ENC = CrossAttnAdapter()

_ATTN_ADAPTERS = {"full": PAGED_GQA, "swa": RING_SWA, "mla": MLA_LATENT}


def adapters_for(cfg: ModelConfig, kind: str) -> List[CacheAdapter]:
    """Adapters serving one segment kind, in mixer order (attention first —
    the hybrid fusion averages outputs in this order)."""
    ads: List[CacheAdapter] = []
    if kind in ("dense", "moe", "hybrid"):
        ads.append(_ATTN_ADAPTERS[cfg.attn_type])
        if cfg.n_encoder_layers:
            ads.append(CROSS_ENC)
    if kind in ("ssm", "hybrid"):
        ads.append(SSM_STATE)
    return ads


def all_adapters(cfg: ModelConfig) -> List[CacheAdapter]:
    """Every adapter the config's segments use (deduplicated, in order)."""
    seen: List[CacheAdapter] = []
    for kind, _n in layer_segments(cfg):
        for ad in adapters_for(cfg, kind):
            if ad not in seen:
                seen.append(ad)
    return seen


def admission_adapters(cfg: ModelConfig) -> List[CacheAdapter]:
    """Adapters that install request-level context once at admission,
    outside the token-chunk loop (e.g. enc-dec encoder K/V)."""
    return [
        ad for ad in all_adapters(cfg)
        if getattr(ad, "installs_at_admission", False)
    ]


def prefix_shareable(cfg: ModelConfig) -> bool:
    """Whether this config's physical pages may be ALIASED across requests
    with a matching token prefix (memory dedup + COW on divergence).

    Requires at least one shareable paged adapter (something to alias) and
    no side-input family in the stack: enc-dec hidden states depend on the
    request's audio, so token-keyed aliasing is unsound for every layer of
    that stack.  Non-shareable slot-local adapters (rings, SSM states) do
    NOT block aliasing of their paged co-residents — they only block
    compute skipping (see :func:`prefix_compute_skippable`).
    """
    ads = all_adapters(cfg)
    return (any(ad.shareable for ad in ads)
            and not any(ad.side_inputs for ad in ads))


def prefix_compute_skippable(cfg: ModelConfig) -> bool:
    """Whether a cached prefix lets admission SKIP the prefix's prefill
    chunks entirely (start chunking at the first uncached page boundary).

    Stricter than :func:`prefix_shareable`: every adapter must be
    shareable (a ring/SSM row is a slot-local summary of the whole
    sequence, so those families must still run every prompt token even
    when the attention pages are aliased), and MoE segments must be absent
    (capacity dispatch groups tokens per forward call, so a suffix-only
    chunk would regroup the dispatch — the documented multi-chunk MoE
    caveat; MoE stacks alias pages for the memory win and recompute).
    """
    if not prefix_shareable(cfg):
        return False
    if any(kind == "moe" for kind, _n in layer_segments(cfg)):
        return False
    return all(ad.shareable for ad in all_adapters(cfg))


def prefill_chunk_multiple(cfg: ModelConfig) -> int:
    """Grid every prefill chunk boundary must sit on (lcm over adapters)."""
    m = 1
    for ad in all_adapters(cfg):
        m = math.lcm(m, ad.chunk_multiple(cfg))
    return m


def supported_families() -> Tuple[str, ...]:
    """Family names the adapter registry serves (the engine error text and
    the launch driver report exactly this list)."""
    return (
        PAGED_GQA.family,
        RING_SWA.family,
        MLA_LATENT.family,
        SSM_STATE.family,
        CROSS_ENC.family,
    )


def unsupported_reason(cfg: ModelConfig) -> Optional[str]:
    """Why the continuous-batching engine cannot serve this config (None =
    it can).  The only hole left: the vision frontend's M-RoPE prefix."""
    if cfg.frontend == "vision" or cfg.mrope_sections:
        return (
            "the vision frontend (M-RoPE position streams + image prefix) "
            "has no cache adapter yet"
        )
    return None


def unsupported_message(cfg: ModelConfig, hint: str = "") -> Optional[str]:
    """The ONE unsupported-family error text (None = config is served):
    the reason plus exactly the families the registry reports.  Every
    refusing layer (kvcache, launch driver) formats through here so the
    copies cannot drift."""
    reason = unsupported_reason(cfg)
    if reason is None:
        return None
    msg = (f"{cfg.name}: {reason}; the paged engine serves: "
           + ", ".join(supported_families()))
    return msg + (f" — {hint}" if hint else "")
