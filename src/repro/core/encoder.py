"""End-to-end blocked transformer encoder — the paper's case study (BERT-base).

Demonstrates the paper's §3.2 claim: with BWMA, the *entire* encoder stack runs
on block-wise data; RWMA↔BWMA conversion happens once at the input and once at
the output.  Every intermediate (Q/K/V, attention scores, head outputs, FFN
activations) stays blocked.

Two functionally-identical paths are provided:

* ``encoder_rwma`` — conventional row-major jnp (the paper's baseline),
* ``encoder_bwma`` — everything blocked, dispatched through a selectable
  execution :class:`~repro.core.backend.Backend`:

  - ``backend="reference"`` — the pure-jnp blockwise operators,
  - ``backend="pallas"`` — the Pallas BWMA kernels (``interpret=True``
    on the CPU platform, compiled elsewhere), including the fused
    attention and the fused GEMM+bias+GELU feed-forward.

All paths must agree to float tolerance (tested); the *performance*
difference is what ``repro.core.memmodel`` and the Pallas kernels quantify.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import blockwise as bw
from repro.core.backend import Backend, resolve_backend
from repro.core.layout import BlockLayout, to_blockwise


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """BERT-style encoder. Paper defaults: BERT-base, seq 512."""

    seq_len: int = 512
    d_model: int = 768
    n_heads: int = 12
    d_head: int = 64
    d_ff: int = 3072
    n_layers: int = 12
    block: int = 16  # accelerator kernel size (paper: 8/16; TPU: 128)
    dtype: jnp.dtype = jnp.float32

    @property
    def layout(self) -> BlockLayout:
        return BlockLayout(self.block, self.block)


def init_layer_params(key, cfg: EncoderConfig) -> Dict[str, jnp.ndarray]:
    """One encoder layer's parameters, row-major (canonical storage)."""
    d, h, dh, f = cfg.d_model, cfg.n_heads, cfg.d_head, cfg.d_ff
    ks = jax.random.split(key, 8)
    s = 0.02
    return {
        "wq": jax.random.normal(ks[0], (h, d, dh), cfg.dtype) * s,
        "wk": jax.random.normal(ks[1], (h, d, dh), cfg.dtype) * s,
        "wv": jax.random.normal(ks[2], (h, d, dh), cfg.dtype) * s,
        "wo": jax.random.normal(ks[3], (h * dh, d), cfg.dtype) * s,
        "w1": jax.random.normal(ks[4], (d, f), cfg.dtype) * s,
        "b1": jnp.zeros((f,), cfg.dtype),
        "w2": jax.random.normal(ks[5], (f, d), cfg.dtype) * s,
        "b2": jnp.zeros((d,), cfg.dtype),
        "ln1_g": jnp.ones((d,), cfg.dtype),
        "ln1_b": jnp.zeros((d,), cfg.dtype),
        "ln2_g": jnp.ones((d,), cfg.dtype),
        "ln2_b": jnp.zeros((d,), cfg.dtype),
    }


def init_params(key, cfg: EncoderConfig) -> List[Dict[str, jnp.ndarray]]:
    return [init_layer_params(k, cfg) for k in jax.random.split(key, cfg.n_layers)]


# --------------------------------------------------------------------------
# RWMA baseline (row-major, conventional)
# --------------------------------------------------------------------------

def _layernorm(x, g, b, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def encoder_layer_rwma(p, x, cfg: EncoderConfig):
    h = []
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.d_head, x.dtype))
    for i in range(cfg.n_heads):
        q = x @ p["wq"][i]
        k = x @ p["wk"][i]
        v = x @ p["wv"][i]
        a = jax.nn.softmax((q @ k.T) * scale, axis=-1)
        h.append(a @ v)
    att = jnp.concatenate(h, axis=-1) @ p["wo"]
    x = _layernorm(x + att, p["ln1_g"], p["ln1_b"])
    ff = jax.nn.gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
    return _layernorm(x + ff, p["ln2_g"], p["ln2_b"])


def encoder_rwma(params, x, cfg: EncoderConfig):
    for p in params:
        x = encoder_layer_rwma(p, x, cfg)
    return x


# --------------------------------------------------------------------------
# BWMA path — everything blocked end-to-end
# --------------------------------------------------------------------------

def block_layer_params(p, cfg: EncoderConfig):
    """Pre-arrange one layer's weights block-wise (done once, offline).

    This is the paper's 'governed by the accelerator kernel size' step: the
    stored layout of every weight matrix is the accelerator block sequence.
    """
    lo = cfg.layout
    h, dh, d = cfg.n_heads, cfg.d_head, cfg.d_model
    out = {}
    for name in ("wq", "wk", "wv"):
        out[name] = to_blockwise(p[name], lo)  # (h, gm, gn, bm, bn)
    # wo is blocked PER HEAD along its row (h*dh) axis: each head's dh rows
    # are padded to a block multiple independently, so they line up with the
    # per-head padded columns that merge_heads stacks (interior zeros cancel
    # in the GEMM).  For dh % block == 0 this is bit-identical to blocking
    # the (h*dh, d) matrix directly.
    wo = to_blockwise(p["wo"].reshape(h, dh, d), lo)  # (h, gdh, gd, b, b)
    out["wo"] = wo.reshape(h * wo.shape[1], *wo.shape[2:])
    for name in ("w1", "w2"):
        out[name] = to_blockwise(p[name], lo)
    for name in ("b1", "b2", "ln1_g", "ln1_b", "ln2_g", "ln2_b"):
        out[name] = bw.block_vector(p[name], lo)
    return out


def block_params(params, cfg: EncoderConfig):
    return [block_layer_params(p, cfg) for p in params]


def encoder_layer_bwma(
    pb,
    xb: bw.Blocked,
    cfg: EncoderConfig,
    backend: Union[str, Backend, None] = None,
) -> bw.Blocked:
    lo = cfg.layout
    d, dh, f = cfg.d_model, cfg.d_head, cfg.d_ff
    be = resolve_backend(backend)
    scale = 1.0 / float(dh) ** 0.5  # static: kernels close over it
    # All heads at once: weights keep their (h, ...) leading dim, the input
    # gains a broadcasting head axis, and every op below runs as ONE batched
    # kernel call (vmap collapses the former per-head python loop).
    xh = bw.add_head_axis(xb)
    q = be.matmul(xh, bw.Blocked(pb["wq"], (d, dh), lo))  # (..., h, gs, gd, b, b)
    k = be.matmul(xh, bw.Blocked(pb["wk"], (d, dh), lo))
    v = be.matmul(xh, bw.Blocked(pb["wv"], (d, dh), lo))
    # Fused scores -> softmax -> @V: intermediates never leave BWMA order.
    ctx = be.attention(q, k, v, scale=scale)
    att_all = bw.merge_heads(ctx)  # (..., gs, h*gd, b, b)
    proj = be.matmul(att_all, bw.Blocked(pb["wo"], (att_all.shape[1], d), lo))
    x1 = be.layernorm(be.add(xb, proj), pb["ln1_g"], pb["ln1_b"])
    # Feed-forward up-projection: GEMM + bias + GELU fused at write-back.
    act = be.ffn(x1, bw.Blocked(pb["w1"], (d, f), lo), pb["b1"])
    down = be.bias(be.matmul(act, bw.Blocked(pb["w2"], (f, d), lo)), pb["b2"])
    return be.layernorm(be.add(x1, down), pb["ln2_g"], pb["ln2_b"])


def encoder_bwma(
    blocked_params,
    x,
    cfg: EncoderConfig,
    backend: Union[str, Backend, None] = None,
    *,
    interpret: Optional[bool] = None,
):
    """Full encoder: RWMA->BWMA once, N blocked layers, BWMA->RWMA once.

    ``backend`` selects the execution path ("reference" | "pallas" | a
    :class:`Backend` instance); ``interpret`` forces/disables Pallas
    interpreter mode (default: interpret everywhere but TPU).  ``x`` may
    carry leading batch dims: ``(..., seq_len, d_model)``.
    """
    be = resolve_backend(backend, interpret=interpret)
    xb = bw.block(x, cfg.layout)  # the only input-side conversion
    for pb in blocked_params:
        xb = encoder_layer_bwma(pb, xb, cfg, be)
    return xb.unblock()  # the only output-side conversion


def bert_base_config(block: int = 16, n_layers: int = 12) -> EncoderConfig:
    """The paper's evaluation model (§4.1): BERT-base, 512x768 input."""
    return EncoderConfig(
        seq_len=512, d_model=768, n_heads=12, d_head=64, d_ff=3072,
        n_layers=n_layers, block=block,
    )
