"""Shared building blocks: norms, RoPE / M-RoPE, chunked attention math."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def norm_apply(cfg: ModelConfig, w, x, b=None, eps: float = 1e-5):
    """RMSNorm or LayerNorm, computed in fp32 (standard practice)."""
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        y = y * w.astype(jnp.float32)
    else:
        mean = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mean) ** 2, -1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
        if b is not None:
            y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def norm_init(cfg: ModelConfig, d: int):
    if cfg.norm == "rmsnorm":
        return {"w": jnp.ones((d,), cfg.dtype)}
    return {"w": jnp.ones((d,), cfg.dtype), "b": jnp.zeros((d,), cfg.dtype)}


def apply_norm(cfg: ModelConfig, p, x):
    return norm_apply(cfg, p["w"], x, p.get("b"))


def activation(cfg: ModelConfig, x):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    s = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def dense(cfg: ModelConfig, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Linear layer with a selectable memory arrangement (the paper's
    technique as a first-class switch):

    * ``xla``  — plain jnp matmul; XLA picks layouts (production dry-run path);
    * ``bwma`` — route through the Pallas blocked-GEMM kernel: weights and
      activations move HBM->VMEM as contiguous accelerator-sized blocks
      (paper Fig. 4d).  On CPU this runs in interpret mode (small scale);
    * ``rwma`` — the row-major tiled Pallas kernel (the paper's baseline);
      shapes the block does not divide are refused, not run as ``x @ w``.
    """
    if cfg.gemm_backend == "xla" or w.ndim != 2:
        return x @ w
    from repro.core import blockwise as bw
    from repro.core.backend import resolve_backend
    from repro.core.layout import BlockLayout
    from repro.kernels import ops as kops

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    blk = min(cfg.block, *x2.shape, *w.shape)
    blk = max(8, blk)
    if cfg.gemm_backend == "bwma":
        # the shared, memoized Pallas backend: its per-operator jit caches
        # persist across layers/steps, so the whole model zoo reuses one
        # compiled kernel per shape instead of re-tracing each call
        layout = BlockLayout(blk, blk)
        out = resolve_backend("pallas").matmul(
            bw.block(x2, layout), bw.block(w, layout)
        ).unblock()
    else:  # rwma
        m, k = x2.shape
        n = w.shape[1]
        if m % blk or k % blk or n % blk:
            raise ValueError(
                f"gemm_backend='rwma' needs every matmul dim divisible by "
                f"the block {blk}, got ({m}, {k}) @ ({k}, {n})"
            )
        # memoized jit wrapper sharing the backend's dispatch policy
        out = kops.matmul_rwma(x2, w, bm=blk, bk=blk, bn=blk)
    return out.astype(x.dtype).reshape(*lead, w.shape[1])


# --------------------------------------------------------------------------
# RoPE / M-RoPE
# --------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    """(dim//2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Standard RoPE.  x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)  # (d/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray, positions3: jnp.ndarray, theta: float, sections: Tuple[int, ...]
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.  positions3: (3, B, S) — temporal/height/width
    position streams; ``sections`` splits the head dim's frequency pairs among
    the three streams (sum(sections) == D//2)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)  # (d/2,)
    # pick which positional stream drives each frequency index
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=d // 2
    )  # (d/2,) in {0,1,2}
    pos = positions3.astype(jnp.float32)  # (3, B, S)
    ang_all = pos[..., None] * inv  # (3, B, S, d/2)
    # select per-frequency stream: ang[b, s, i] = ang_all[sec_id[i], b, s, i]
    sel = jax.nn.one_hot(sec_id, len(sections), dtype=jnp.float32)  # (d/2, 3)
    ang = jnp.einsum("tbsf,ft->bsf", ang_all, sel)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def default_positions(batch: int, seq: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))


# --------------------------------------------------------------------------
# Chunked (flash-style) attention, pure XLA
# --------------------------------------------------------------------------

def chunked_attention(
    q: jnp.ndarray,  # (B, Sq, H, Dq)
    k: jnp.ndarray,  # (B, Sk, Hkv, Dq)
    v: jnp.ndarray,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    q_offset=0,  # absolute position of q[0] (int or traced scalar)
    k_positions: Optional[jnp.ndarray] = None,  # (B, Sk) absolute key positions
    window: Optional[int] = None,  # SWA: keys with q_pos - k_pos >= window masked
    q_chunk: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Memory-bounded attention: scan over query chunks, full K/V per chunk.

    Avoids materializing the (B, H, Sq, Sk) score tensor — with the layer scan
    + remat this is what keeps 32k prefill inside HBM.  GQA is handled by
    reshaping heads into (Hkv, group) so no K/V repetition is materialized.
    """
    B, Sq, H, Dq = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    g = H // Hkv
    scale = scale if scale is not None else Dq ** -0.5
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32)[None], (B, Sk))

    qc = min(q_chunk, Sq)
    if Sq % qc:
        qc = Sq  # fall back to single chunk for awkward sizes
    nc = Sq // qc
    qr = q.reshape(B, nc, qc, Hkv, g, Dq)

    def one_chunk(c):
        qi = qr[:, c]  # (B, qc, Hkv, g, Dq)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qi, k,
            preferred_element_type=jnp.float32,  # f32 accum, NO operand
            # converts (convert(dot(bf16)) makes XLA materialize f32 copies
            # of the whole K/V cache, hoisted out of the layer scan)
        ) * scale
        q_pos = q_offset + c * qc + jnp.arange(qc, dtype=jnp.int32)  # (qc,)
        kp = k_positions[:, None, None, None, :]  # (B,1,1,1,Sk)
        qp = q_pos[None, None, None, :, None]
        # kp >= 0 masks empty / reset cache entries (labelled -1) when an
        # explicit k_positions is passed; the default arange labels are
        # always >= 0 so the non-cached paths are unaffected
        mask = jnp.broadcast_to(kp >= 0, (B, 1, 1, qc, Sk))
        if causal:
            mask = jnp.logical_and(mask, kp <= qp)
        if window is not None:
            mask = jnp.logical_and(mask, qp - kp < window)
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)  # (B, qc, Hkv, g, Dv)

    if nc == 1:
        out = one_chunk(0)
        return out.reshape(B, Sq, H, Dv)
    # remat each chunk: without this, AD saves every chunk's (B,H,qc,Sk)
    # softmax for the backward pass — O(S^2) memory, defeating the chunking.
    one_chunk = jax.checkpoint(one_chunk)
    outs = jax.lax.map(one_chunk, jnp.arange(nc))  # (nc, B, qc, Hkv, g, Dv)
    out = jnp.moveaxis(outs, 0, 1)  # (B, nc, qc, ...)
    return out.reshape(B, Sq, H, Dv)


def decode_attention(
    q: jnp.ndarray,  # (B, 1, H, Dq)
    k_cache: jnp.ndarray,  # (B, Sc, Hkv, Dq)
    v_cache: jnp.ndarray,  # (B, Sc, Hkv, Dv)
    k_positions: jnp.ndarray,  # (B, Sc) absolute positions; -1 = empty slot
    q_pos,  # absolute position of the new token: scalar or (B,) per slot
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """One-token attention over a (possibly ring-buffer) cache."""
    B, Sc, Hkv, Dq = k_cache.shape
    H = q.shape[2]
    g = H // Hkv
    Dv = v_cache.shape[-1]
    scale = scale if scale is not None else Dq ** -0.5
    qi = q.reshape(B, Hkv, g, Dq)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qi, k_cache,
        preferred_element_type=jnp.float32,  # see chunked_attention note
    ) * scale
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if q_pos.ndim == 0:  # one shared position (static-wave decode)
        q_pos = jnp.broadcast_to(q_pos, (B,))
    qp = q_pos[:, None]  # (B, 1) — per-slot positions (continuous batching)
    valid = (k_positions >= 0) & (k_positions <= qp)
    if window is not None:
        valid = valid & (qp - k_positions < window)
    s = jnp.where(valid[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache)
    return out.reshape(B, 1, H, Dv)
