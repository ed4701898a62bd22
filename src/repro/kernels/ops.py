"""Public convenience wrappers around the Pallas kernels.

Dispatch policy lives in :class:`repro.core.backend.PallasBackend`
(interpret mode on the CPU platform, compiled everywhere else); these
wrappers delegate to the shared, memoized instance from
:func:`resolve_backend` so every caller hits the same per-shape jit cache.
They also adapt between the logical (2-D) world and the blocked (BWMA)
world using :mod:`repro.core.layout`, carrying the accelerator block size
as the layout quantum (the paper's 'governed by the kernel size').

Dtype contract: the element-wise-shaped ops (softmax/layernorm/attention)
preserve the input dtype, matching the backend convention.  The GEMM-shaped
ops (``blocked_matmul``, ``blocked_ffn``) return the **f32 accumulator**
unless ``out_dtype`` says otherwise — mixed-precision callers depend on
that, so they bypass the backend's input-dtype cast and call the kernels
directly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.backend import resolve_backend
from repro.core.blockwise import Blocked
from repro.core.layout import BlockLayout, from_blockwise, to_blockwise
from repro.kernels.bwma_fused_ffn import bwma_fused_ffn
from repro.kernels.bwma_gemm import bwma_gemm
from repro.kernels.rwma_gemm import rwma_gemm


def _pallas():
    return resolve_backend("pallas")


def _interpret() -> bool:
    # one source of truth for the dispatch policy: the shared backend
    return _pallas().interpret


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def blocked_matmul(a: Blocked, b: Blocked, out_dtype=None) -> Blocked:
    """BWMA GEMM; returns the f32 accumulator unless ``out_dtype`` is given."""
    out = bwma_gemm(a.data, b.data, interpret=_interpret())
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return Blocked(out, (a.shape[0], b.shape[1]), a.layout)


def blocked_softmax(a: Blocked) -> Blocked:
    return _pallas().softmax(a)


def blocked_layernorm(a: Blocked, gamma_blocked, beta_blocked) -> Blocked:
    return _pallas().layernorm(a, gamma_blocked, beta_blocked)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def blocked_ffn(a: Blocked, w: Blocked, bias_blocked, out_dtype=None) -> Blocked:
    """Fused GEMM+bias+GELU; f32 accumulator unless ``out_dtype`` is given."""
    out = bwma_fused_ffn(a.data, w.data, bias_blocked, interpret=_interpret())
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return Blocked(out, (a.shape[0], w.shape[1]), a.layout)


def blocked_attention(q: Blocked, k: Blocked, v: Blocked, *, scale: float) -> Blocked:
    """Fused softmax(q @ k^T * scale) @ v without leaving BWMA order."""
    return _pallas().attention(q, k, v, scale=scale)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn"))
def matmul_rwma(a: jnp.ndarray, b: jnp.ndarray, bm=128, bk=128, bn=128):
    """Row-major tiled GEMM — the RWMA baseline kernel."""
    return rwma_gemm(a, b, bm=bm, bk=bk, bn=bn, interpret=_interpret())


def matmul_bwma_2d(
    a: jnp.ndarray, b: jnp.ndarray, layout: Optional[BlockLayout] = None
) -> jnp.ndarray:
    """Convenience: 2-D in, 2-D out, blocked internally (conversion at edges
    only — mirrors the paper's whole-model I/O conversion)."""
    layout = layout or BlockLayout(128, 128)
    ab = to_blockwise(a, BlockLayout(layout.bm, layout.bn))
    bb = to_blockwise(b, BlockLayout(layout.bn, layout.bn))
    out = bwma_gemm(ab, bb, interpret=_interpret())
    return from_blockwise(out, layout, (a.shape[0], b.shape[1]))
