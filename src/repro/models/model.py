# repro: noqa-file RPR004 -- the model math itself dispatches per family;
# the registry rule protects the serving stack, not the layer definitions
"""Model assembly: one functional LM supporting every assigned family.

Layers are grouped into homogeneous *segments* (e.g. DeepSeek-V3 = 3 dense
layers + 58 MoE layers); each segment's parameters are stacked along a
leading L axis and executed with ``jax.lax.scan`` (+ remat in training) so
the HLO stays compact enough to compile 512-device dry-runs on CPU.

Execution modes: ``train`` (loss), ``prefill`` (populate caches),
``decode`` (one token against caches).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.axes import constrain
from repro.models import adapters as A
from repro.models import attention as attn
from repro.models import ffn as ffnm
from repro.models import ssm as ssmm
from repro.models.common import apply_norm, default_positions, dense_init, norm_init

# Segment structure lives with the cache-adapter registry (the one place
# that knows which layer family uses which layout); re-exported here because
# the whole system addresses it as M.layer_segments.
layer_segments = A.layer_segments


def _attn_init(key, cfg: ModelConfig):
    return attn.mla_init(key, cfg) if cfg.attn_type == "mla" else attn.gqa_init(key, cfg)


def init_layer(key, cfg: ModelConfig, kind: str) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": norm_init(cfg, cfg.d_model)}
    if kind == "ssm":
        p["ssm"] = ssmm.ssm_init(ks[0], cfg)
        return p
    p["attn"] = _attn_init(ks[0], cfg)
    if kind == "hybrid":
        p["ssm"] = ssmm.ssm_init(ks[1], cfg)
    p["ln2"] = norm_init(cfg, cfg.d_model)
    if kind == "moe":
        p["moe"] = ffnm.moe_init(ks[2], cfg)
    else:
        p["ffn"] = ffnm.ffn_init(ks[2], cfg)
    return p


def layer_forward(
    cfg: ModelConfig,
    kind: str,
    p: Dict,
    x: jnp.ndarray,
    positions,
    *,
    mode: str,
    cache: Optional[Dict],
    pos_offset,
    seq_pos=None,  # (B,) per-slot absolute positions (continuous batching)
    page_table=None,  # (B, max_pages) physical page ids (paged KV cache)
    active=None,  # (B,) bool: slots whose decode writes may land
    chunk: Optional[Dict] = None,  # chunked-prefill context (mode "chunk")
    layer=None,  # index into the segment's stacked paged pools (engine)
) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    if chunk is not None or (mode == "decode" and seq_pos is not None):
        return _layer_forward_engine(
            cfg, kind, p, x, positions, mode=mode, cache=cache,
            pos_offset=pos_offset, seq_pos=seq_pos, page_table=page_table,
            active=active, chunk=chunk, layer=layer,
        )
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    h = apply_norm(cfg, p["ln1"], x)

    if kind == "ssm":
        out, st = ssmm.ssm_forward(
            p["ssm"], cfg, h, mode=mode,
            state=cache.get("ssm") if cache else None,
        )
        if st is not None:
            new_cache["ssm"] = st
        return x + out, (new_cache or None), aux

    if cfg.attn_type == "mla":
        a_out, a_cache = attn.mla_forward(
            p["attn"], cfg, h, positions, mode=mode,
            cache=cache.get("attn") if cache else None, pos_offset=pos_offset,
        )
    else:
        a_out, a_cache = attn.gqa_forward(
            p["attn"], cfg, h, positions, mode=mode,
            cache=cache.get("attn") if cache else None, pos_offset=pos_offset,
        )
    if a_cache is not None:
        new_cache["attn"] = a_cache
    if kind == "hybrid":
        s_out, st = ssmm.ssm_forward(
            p["ssm"], cfg, h, mode=mode,
            state=cache.get("ssm") if cache else None,
        )
        if st is not None:
            new_cache["ssm"] = st
        mixer_out = 0.5 * (a_out + s_out)  # Hymba: fused parallel heads
    else:
        mixer_out = a_out
    x = x + mixer_out
    h2 = apply_norm(cfg, p["ln2"], x)
    if kind == "moe":
        m_out, m_aux = ffnm.moe_forward(p["moe"], cfg, h2)
        x = x + m_out
        aux = aux + m_aux
    else:
        x = x + ffnm.ffn_forward(p["ffn"], cfg, h2)
    x = constrain(x, ("dp", None, None))
    return x, (new_cache or None), aux


def _layer_forward_engine(
    cfg: ModelConfig, kind: str, p: Dict, x, positions, *, mode, cache,
    pos_offset, seq_pos, page_table, active, chunk, layer,
):
    """Engine-mode layer step (chunked prefill / per-slot paged decode).

    The cache semantics — pool layout, slot addressing, chunk scatter,
    decode gather, active masking — live entirely in the family's
    :class:`~repro.models.adapters.CacheAdapter`; this function only wires
    adapter outputs into the residual stream (attention first, hybrid
    fusion, cross-attention after the self mixer, then FFN/MoE).  Paged
    adapters get their stacked pools and ``layer``; the rest get this
    layer's rows.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    h = apply_norm(cfg, p["ln1"], x)

    def run(ad, sub_p, hh):
        if mode == "chunk":
            return ad.chunk(sub_p, cfg, hh, positions, cache[ad.key],
                            dict(chunk, layer=layer), pos_offset)
        return ad.decode(sub_p, cfg, hh, positions, cache[ad.key],
                         seq_pos=seq_pos, page_table=page_table, active=active,
                         layer=layer)

    cross = None
    outs = []
    for ad in A.adapters_for(cfg, kind):
        if ad.key == "cross":
            cross = ad  # applies after the self mixer's residual add
            continue
        out, c_new = run(ad, p[ad.param_key], h)
        new_cache[ad.key] = c_new
        outs.append(out)
    if kind == "ssm":
        return x + outs[0], new_cache, aux
    # hybrid (Hymba) fuses parallel attention + SSM heads by mean
    x = x + (outs[0] if len(outs) == 1 else 0.5 * (outs[0] + outs[1]))
    if cross is not None:
        hc = apply_norm(cfg, p["cross"]["ln"], x)
        out_c, c_cross = run(cross, p["cross"]["attn"], hc)
        new_cache["cross"] = c_cross
        x = x + out_c
    h2 = apply_norm(cfg, p["ln2"], x)
    if kind == "moe":
        m_out, m_aux = ffnm.moe_forward(p["moe"], cfg, h2)
        x = x + m_out
        aux = aux + m_aux
    else:
        x = x + ffnm.ffn_forward(p["ffn"], cfg, h2)
    x = constrain(x, ("dp", None, None))
    return x, new_cache, aux


# --------------------------------------------------------------------------
# Cache init
# --------------------------------------------------------------------------

def _layer_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    c: Dict[str, Any] = {}
    if kind in ("dense", "moe", "hybrid"):
        if cfg.attn_type == "mla":
            c["attn"] = attn.mla_cache_init(cfg, batch, max_len)
        else:
            c["attn"] = attn.gqa_cache_init(
                cfg, batch, max_len, window_only=(cfg.attn_type == "swa")
            )
    if kind in ("ssm", "hybrid"):
        c["ssm"] = ssmm.ssm_state_init(cfg, batch)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked-per-segment cache pytree for decode."""
    segs = {}
    for si, (kind, n) in enumerate(layer_segments(cfg)):
        one = _layer_cache_init(cfg, kind, batch, max_len)
        segs[f"seg{si}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), one
        )
    if cfg.n_encoder_layers:  # whisper: cross-attention K/V filled at prefill
        shape = (cfg.n_layers, batch, cfg.encoder_seq, cfg.n_kv_heads,
                 cfg.d_head)
        segs["seg0"]["cross"] = {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
        }
    return segs


def supports_padded_prefill(cfg: ModelConfig) -> bool:
    """Families whose prefill may be right-padded to a bucketed length.

    Full-attention dense/GQA caches index token slots by absolute position
    and mask by position label, so pad keys never survive attention (they
    are causally masked during prefill and overwritten by decode before
    their label becomes reachable) — padding is bit-exact and lets prompt
    lengths share a handful of power-of-two-page jit buckets.  SWA ring
    packing and SSM final states are position-*dependent* summaries of the
    sequence end, and MoE capacity dispatch lets pad tokens steal expert
    slots from real ones, so those families keep exact prefill shapes.
    """
    return (
        cfg.attn_type == "full"
        and cfg.family == "dense"
        and cfg.n_encoder_layers == 0
        and cfg.frontend == "none"
        and not cfg.mrope_sections
    )


def init_paged_cache(
    cfg: ModelConfig, max_seqs: int, num_pages: int, page_size: int, max_len: int
):
    """Stacked-per-segment decode cache for the continuous-batching engine.

    Each segment's cache is whatever its family's adapters declare: paged
    pools share physical page ids across layers (page ids are pool-wide);
    non-paged adapters own ``max_seqs`` per-slot rows.
    """
    msg = A.unsupported_message(cfg)
    if msg is not None:
        raise NotImplementedError(msg)
    geom = A.CacheGeometry(max_seqs, num_pages, page_size, max_len)
    segs = {}
    for si, (kind, n) in enumerate(layer_segments(cfg)):
        c = {ad.key: ad.init_pool(cfg, geom) for ad in A.adapters_for(cfg, kind)}
        segs[f"seg{si}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), c
        )
    return segs


def decode_step_paged(cfg: ModelConfig, params, caches, tokens, seq_pos,
                      page_table, active=None):
    """One continuous-batching decode step (all slots advance together).

    tokens: (B, 1) int32 — last sampled token per slot (0 for idle slots);
    seq_pos: (B,) int32 — absolute position the new token occupies (0 idle);
    page_table: (B, max_pages) int32 — physical page per logical page (idle
    and unmapped entries point at the reserved null page 0);
    active: (B,) bool — slots actually decoding.  Inactive slots (idle, or
    mid-way through a chunked prefill) run the math but their cache writes
    are dropped, so the lockstep step cannot corrupt a half-prefilled slot.
    Returns (logits (B, 1, V), new caches).
    """
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.n_encoder_layers:
        # learned decoder positions, gathered per slot (enc-dec decode)
        h = h + jnp.take(params["dec_pos"], seq_pos, axis=0)[:, None]
    positions = seq_pos[:, None]  # (B, 1) per-slot RoPE positions
    h, new_caches, _ = _run_segments(
        cfg, params, h, positions, mode="decode", caches=caches,
        pos_offset=0, remat=False, seq_pos=seq_pos, page_table=page_table,
        active=active,
    )
    h = apply_norm(cfg, params["final_norm"], h)
    return _lm_logits(cfg, params, h), new_caches


def prefill_chunk(
    cfg: ModelConfig, params, caches, tokens, slot, q_off,
    phys_tok, off_tok, table_row, last_idx,
):
    """One prompt chunk of one request against the engine's paged caches.

    The workhorse of chunked admission: ``tokens`` (1, C) are positions
    ``q_off .. q_off + C`` of one request's prompt.  Paged segments scatter
    the chunk's K/V straight into its physical pages (``phys_tok``/
    ``off_tok``, null-page-routed when past the slot's allocation) and
    attend over the slot's ``table_row`` gather; SWA rings and SSM states
    carry slot rows across chunks.  ``caches`` is the engine's full cache
    pytree and is donated by the caller's jit, so no admission ever copies
    the pool.

    Returns (logits (1, 1, V) at in-chunk index ``last_idx`` — the next-
    token distribution after the chunk's last real token, only meaningful
    on the final chunk — and the updated caches).
    """
    B, C = tokens.shape
    assert B == 1
    h = jnp.take(params["embed"], tokens, axis=0)
    positions = (q_off + jnp.arange(C, dtype=jnp.int32))[None]  # (1, C)
    if cfg.n_encoder_layers:
        # learned decoder positions for this chunk's absolute range
        h = h + jnp.take(params["dec_pos"], positions[0], axis=0)[None]
    chunk = {
        "slot": slot, "first": q_off == 0, "table_row": table_row,
        "phys_tok": phys_tok, "off_tok": off_tok,
    }
    h, new_caches, _ = _run_segments(
        cfg, params, h, positions, mode="chunk", caches=caches,
        pos_offset=q_off, remat=False, chunk=chunk,
    )
    h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)
    h_last = apply_norm(cfg, params["final_norm"], h_last)
    return _lm_logits(cfg, params, h_last), new_caches


# --------------------------------------------------------------------------
# Parameter init
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Dict:
    keys = jax.random.split(key, 8)
    d, V = cfg.d_model, cfg.padded_vocab  # padded for even TP shards
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (V, d), jnp.float32) * 0.02
                  ).astype(cfg.dtype),
        "final_norm": norm_init(cfg, d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], d, V, cfg.dtype, scale=0.02)
    ki = 2
    for si, (kind, n) in enumerate(layer_segments(cfg)):
        seg_keys = jax.random.split(keys[ki], n)
        ki += 1
        params[f"seg{si}"] = jax.vmap(
            lambda k: init_layer(k, cfg, kind)
        )(seg_keys)
    if cfg.mtp_depth:
        mk = jax.random.split(keys[5], 3)
        params["mtp"] = {
            "proj": dense_init(mk[0], 2 * d, d, cfg.dtype),
            "norm_h": norm_init(cfg, d),
            "norm_e": norm_init(cfg, d),
            "layer": init_layer(mk[1], cfg, "dense"),
            "final_norm": norm_init(cfg, d),
        }
    if cfg.n_encoder_layers:
        ek = jax.random.split(keys[6], cfg.n_encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: _enc_layer_init(k, cfg)
        )(ek)
        params["enc_final_norm"] = norm_init(cfg, d)
        params["enc_pos"] = (jax.random.normal(keys[7], (cfg.encoder_seq, d),
                                               jnp.float32) * 0.02).astype(cfg.dtype)
        # decoder cross-attention weights (per decoder layer, stacked)
        ck = jax.random.split(jax.random.fold_in(key, 99), cfg.n_layers)
        params["cross"] = jax.vmap(lambda k: _cross_init(k, cfg))(ck)
        params["dec_pos"] = (jax.random.normal(jax.random.fold_in(key, 98),
                                               (cfg.max_decoder_positions, d),
                                               jnp.float32) * 0.02
                             ).astype(cfg.dtype)
    return params


def _enc_layer_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "ln1": norm_init(cfg, cfg.d_model),
        "attn": attn.gqa_init(ks[0], cfg),
        "ln2": norm_init(cfg, cfg.d_model),
        "ffn": ffnm.ffn_init(ks[1], cfg),
    }


def _cross_init(key, cfg: ModelConfig):
    return {"ln": norm_init(cfg, cfg.d_model), "attn": attn.gqa_init(key, cfg)}


# --------------------------------------------------------------------------
# Forward passes
# --------------------------------------------------------------------------

def frontend_extras(cfg: ModelConfig, batch: Dict, B: int, S: int) -> Dict:
    """Fill *missing* modality inputs with stub zero embeddings (vision /
    audio frontends).  Inputs already present (e.g. a request's real
    ``audio_embeds``) are left untouched."""
    if cfg.frontend == "vision":
        batch.setdefault("vis_embeds", jnp.zeros(
            (B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype
        ))
        batch.setdefault("positions3", jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S)
        ))
    if cfg.frontend == "audio":
        batch.setdefault("audio_embeds", jnp.zeros(
            (B, cfg.encoder_seq, cfg.d_model), cfg.dtype
        ))
    return batch


def _embed_inputs(cfg: ModelConfig, params, batch: Dict) -> Tuple[jnp.ndarray, Any]:
    tokens = batch["tokens"]
    h = jnp.take(params["embed"], tokens, axis=0)
    h = constrain(h, ("dp", None, None))
    if cfg.frontend == "vision" and "vis_embeds" in batch:
        # stub frontend: precomputed patch embeddings occupy the prefix
        v = batch["vis_embeds"].astype(h.dtype)
        h = jax.lax.dynamic_update_slice(h, v, (0, 0, 0))
    if cfg.mrope_sections and "positions3" in batch:
        positions = batch["positions3"]
    else:
        positions = default_positions(tokens.shape[0], tokens.shape[1])
    return h, positions


def _run_segments(
    cfg: ModelConfig, params, h, positions, *, mode: str, caches=None,
    pos_offset=0, remat: bool = False, seq_pos=None, page_table=None,
    active=None, chunk=None,
):
    """Scan each stacked segment; returns (h, new_caches, aux_sum).

    In the engine modes a paged adapter's stacked pools ride the scan's
    carry, with the layer index beside them, instead of its ``xs``/``ys``:
    each layer scatters its new tokens into the carried pool at ``[layer,
    phys, off]`` and the decode kernel streams pages of that layer out of
    it, so no layer's pool is sliced out or written back and the donated
    pool is updated where it lies.  Non-paged rows (SWA rings, SSM states,
    cross rows: small per-slot rows) are scanned as ``xs``/``ys``.
    """
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {}
    engine = chunk is not None or (mode == "decode" and seq_pos is not None)
    seg_off = 0
    for si, (kind, n) in enumerate(layer_segments(cfg)):
        stacked = params[f"seg{si}"]
        if engine and cfg.n_encoder_layers and "cross" in params:
            # enc-dec engine path: the per-layer cross-attention params ride
            # the same scan as the decoder layers they belong to (sliced to
            # this segment's share of the layer stack, matching how the
            # cross adapter splits its admission install)
            stacked = dict(stacked)
            stacked["cross"] = jax.tree.map(
                lambda a: a[seg_off:seg_off + n], params["cross"]
            )
        seg_off += n
        cache_seg = caches.get(f"seg{si}") if caches else None
        pools = {}
        if engine and cache_seg is not None:
            paged = {ad.key for ad in A.adapters_for(cfg, kind) if ad.paged}
            pools = {k: v for k, v in cache_seg.items() if k in paged}
            cache_seg = {k: v for k, v in cache_seg.items() if k not in paged}

        def body(carry, inp, _kind=kind):
            x, pools_l, layer = carry
            p_layer = inp[0]
            c_layer = inp[1] if cache_seg is not None else None
            if pools_l:
                c_layer = {**c_layer, **pools_l}
            x, c_new, aux = layer_forward(
                cfg, _kind, p_layer, x, positions,
                mode=mode, cache=c_layer, pos_offset=pos_offset,
                seq_pos=seq_pos, page_table=page_table,
                active=active, chunk=chunk, layer=layer,
            )
            if c_new is None:
                c_new = 0  # scan needs a consistent pytree; 0 = no cache
            elif pools_l:
                pools_l = {k: c_new[k] for k in pools_l}
                c_new = {k: v for k, v in c_new.items() if k not in pools_l}
            return (x, pools_l, layer + 1), (c_new, aux)

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable
            )
        xs = (stacked, cache_seg) if cache_seg is not None else (stacked,)
        (h, pools, _), (cache_out, auxs) = jax.lax.scan(
            body, (h, pools, jnp.int32(0)), xs
        )
        aux_total = aux_total + jnp.sum(auxs)
        if mode in ("prefill", "decode", "chunk"):
            new_caches[f"seg{si}"] = (
                {**cache_out, **pools} if pools else cache_out
            )
    return h, new_caches, aux_total


def forward_train(cfg: ModelConfig, params, batch: Dict, *, remat: bool = True):
    """Returns (per-token logits, aux losses, final hidden)."""
    if cfg.n_encoder_layers:
        return _forward_encdec_train(cfg, params, batch, remat=remat)
    h, positions = _embed_inputs(cfg, params, batch)
    h, _, aux = _run_segments(cfg, params, h, positions, mode="train", remat=remat)
    h = apply_norm(cfg, params["final_norm"], h)
    logits = _lm_logits(cfg, params, h)
    return logits, aux, h


def _lm_logits(cfg: ModelConfig, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = constrain(h @ w, ("dp", None, "tp"))
    if cfg.padded_vocab != cfg.vocab_size:
        # mask pad columns so logsumexp / sampling never see them
        pad_mask = (
            jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
            < cfg.vocab_size
        )
        logits = jnp.where(pad_mask, logits, jnp.finfo(logits.dtype).min)
    return logits


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE over positions with label >= 0 (fp32 logsumexp).

    The label log-prob is extracted with an iota-compare masked sum instead of
    ``take_along_axis``: a vocab-dim gather forces GSPMD to all-gather the
    (B, S, V) logits when the vocab is TP-sharded, whereas the masked sum
    stays local per shard and reduces with one psum.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
        == labels[..., None]
    )
    ll = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)


def loss_fn(cfg: ModelConfig, params, batch: Dict, *, remat: bool = True):
    """Next-token LM loss (+ MoE aux, + MTP head for DeepSeek-V3)."""
    if cfg.n_encoder_layers:
        logits, aux, _ = _forward_encdec_train(cfg, params, batch, remat=remat)
        loss = cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
        return loss + aux, {"ce": loss, "aux": aux}
    logits, aux, h = forward_train(cfg, params, batch, remat=remat)
    labels = batch["labels"]
    loss = cross_entropy(logits[:, :-1], labels[:, :-1])
    metrics = {"ce": loss, "aux": aux}
    if cfg.mtp_depth:
        mtp_loss = _mtp_loss(cfg, params, h, batch)
        metrics["mtp"] = mtp_loss
        loss = loss + 0.1 * mtp_loss
    return loss + aux, metrics


def _mtp_loss(cfg: ModelConfig, params, h, batch):
    """DeepSeek-V3 multi-token prediction: one extra transformer block
    predicting token t+2 from [h_t ; emb(token_{t+1})], sharing the head."""
    p = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    e_next = jnp.take(params["embed"], tokens[:, 1:], axis=0)
    h_cur = h[:, :-1]
    comb = jnp.concatenate(
        [apply_norm(cfg, p["norm_h"], h_cur), apply_norm(cfg, p["norm_e"], e_next)],
        axis=-1,
    ) @ p["proj"]
    positions = default_positions(comb.shape[0], comb.shape[1])
    out, _, _ = layer_forward(
        cfg, "dense", p["layer"], comb, positions,
        mode="train", cache=None, pos_offset=0,
    )
    out = apply_norm(cfg, p["final_norm"], out)
    logits = _lm_logits(cfg, params, out)  # predicts labels shifted by +1
    return cross_entropy(logits[:, :-1], labels[:, 1:-1])


# --------------------------------------------------------------------------
# Encoder-decoder (whisper)
# --------------------------------------------------------------------------

def _encoder_forward(cfg: ModelConfig, params, audio_embeds, *, remat=False):
    h = audio_embeds.astype(cfg.dtype) + params["enc_pos"][None]
    positions = default_positions(h.shape[0], h.shape[1])

    def body(carry, p_layer):
        x = carry
        hh = apply_norm(cfg, p_layer["ln1"], x)
        a, _ = attn.gqa_forward(p_layer["attn"], cfg, hh, positions,
                                mode="train", causal=False)
        x = x + a
        x = x + ffnm.ffn_forward(
            p_layer["ffn"], cfg, apply_norm(cfg, p_layer["ln2"], x)
        )
        return x, 0

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["encoder"])
    return apply_norm(cfg, params["enc_final_norm"], h)


def _dec_layer(cfg, p_layer, p_cross, x, positions, enc_out, *, mode,
               cache, pos_offset):
    new_cache = {}
    h = apply_norm(cfg, p_layer["ln1"], x)
    a, c = attn.gqa_forward(
        p_layer["attn"], cfg, h, positions, mode=mode,
        cache=cache.get("attn") if cache else None, pos_offset=pos_offset,
    )
    if c is not None:
        new_cache["attn"] = c
    x = x + a
    # cross attention (non-causal over encoder output)
    hc = apply_norm(cfg, p_cross["ln"], x)
    pc = p_cross["attn"]
    B = hc.shape[0]
    if mode == "decode" and cache is not None and "cross" in cache:
        ck, cv = cache["cross"]["k"], cache["cross"]["v"]
    else:
        ck = (enc_out @ pc["wk"]).reshape(
            B, -1, cfg.n_kv_heads, cfg.d_head)
        cv = (enc_out @ pc["wv"]).reshape(
            B, -1, cfg.n_kv_heads, cfg.d_head)
    x = x + attn.cross_attention(pc, cfg, hc, ck, cv)
    x = x + ffnm.ffn_forward(
        p_layer["ffn"], cfg, apply_norm(cfg, p_layer["ln2"], x)
    )
    return x, new_cache, (ck, cv)


def _forward_encdec_train(cfg: ModelConfig, params, batch, *, remat=True):
    enc_out = _encoder_forward(cfg, params, batch["audio_embeds"], remat=remat)
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0) + params["dec_pos"][None, :S]
    positions = default_positions(B, S)

    def body(carry, inp):
        x = carry
        p_layer, p_cross = inp
        x, _, _ = _dec_layer(cfg, p_layer, p_cross, x, positions, enc_out,
                             mode="train", cache=None, pos_offset=0)
        return x, 0

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    h, _ = jax.lax.scan(body, h, (params["seg0"], params["cross"]))
    h = apply_norm(cfg, params["final_norm"], h)
    return _lm_logits(cfg, params, h), jnp.zeros((), jnp.float32), h


# --------------------------------------------------------------------------
# Prefill / decode
# --------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, batch: Dict, last_idx=None):
    """Full-sequence forward that returns (last-position logits, caches).

    ``last_idx`` (optional traced scalar) selects which position's logits
    to return — the bucketed-prefill path right-pads the prompt to a shared
    jit shape and reads the logits at the last *real* token instead of the
    last padded one (:func:`supports_padded_prefill`).
    """
    if cfg.n_encoder_layers:
        return _prefill_encdec(cfg, params, batch)
    h, positions = _embed_inputs(cfg, params, batch)
    h, caches, _ = _run_segments(
        cfg, params, h, positions, mode="prefill", remat=False
    )
    if last_idx is None:
        h = h[:, -1:]
    else:
        h = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)
    h = apply_norm(cfg, params["final_norm"], h)
    return _lm_logits(cfg, params, h), caches


def _prefill_encdec(cfg: ModelConfig, params, batch):
    enc_out = _encoder_forward(cfg, params, batch["audio_embeds"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0) + params["dec_pos"][None, :S]
    positions = default_positions(B, S)

    def body(carry, inp):
        x = carry
        p_layer, p_cross = inp
        x, c_new, (ck, cv) = _dec_layer(
            cfg, p_layer, p_cross, x, positions, enc_out,
            mode="prefill", cache=None, pos_offset=0,
        )
        c_new["cross"] = {"k": ck, "v": cv}
        return x, c_new

    h, caches_seg = jax.lax.scan(body, h, (params["seg0"], params["cross"]))
    h = apply_norm(cfg, params["final_norm"], h[:, -1:])
    return _lm_logits(cfg, params, h), {"seg0": caches_seg}


def decode_step(cfg: ModelConfig, params, caches, tokens, pos):
    """One decode step.  tokens: (B, 1) int32; pos: scalar int32 absolute
    position.  Returns (logits (B, 1, V), new caches)."""
    B = tokens.shape[0]
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32), (3, B, 1)
        )
    else:
        positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B, 1))
    if cfg.n_encoder_layers:
        return _decode_encdec(cfg, params, caches, h, positions, pos)
    h, new_caches, _ = _run_segments(
        cfg, params, h, positions, mode="decode", caches=caches, pos_offset=pos,
        remat=False,
    )
    h = apply_norm(cfg, params["final_norm"], h)
    return _lm_logits(cfg, params, h), new_caches


def _decode_encdec(cfg: ModelConfig, params, caches, h, positions, pos):
    h = h + jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1)[None]

    def body(carry, inp):
        x = carry
        p_layer, p_cross, c_layer = inp
        x, c_new, _ = _dec_layer(
            cfg, p_layer, p_cross, x, positions, None,
            mode="decode", cache=c_layer, pos_offset=pos,
        )
        c_new["cross"] = c_layer["cross"]  # immutable encoder-side K/V
        return x, c_new

    h, new_seg = jax.lax.scan(
        body, h, (params["seg0"], params["cross"], caches["seg0"])
    )
    h = apply_norm(cfg, params["final_norm"], h)
    return _lm_logits(cfg, params, h), {"seg0": new_seg}


def encdec_cross_kv(cfg: ModelConfig, params, audio_embeds):
    """Encoder forward + per-decoder-layer cross K/V projections.

    The continuous-batching engine runs this ONCE per admission and installs
    the result into the slot's immutable cross rows — chunked decoder
    prefill and decode never touch the encoder again.  Returns stacked
    {"k", "v"} of shape (n_layers, B, encoder_seq, n_kv_heads, d_head).
    """
    enc_out = _encoder_forward(cfg, params, audio_embeds)
    B = enc_out.shape[0]

    def body(carry, p_cross):
        pc = p_cross["attn"]
        ck = (enc_out @ pc["wk"]).reshape(B, -1, cfg.n_kv_heads, cfg.d_head)
        cv = (enc_out @ pc["wv"]).reshape(B, -1, cfg.n_kv_heads, cfg.d_head)
        return carry, {"k": ck, "v": cv}

    _, kv = jax.lax.scan(body, 0, params["cross"])
    return kv
