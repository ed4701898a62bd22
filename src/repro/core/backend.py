"""Selectable execution backends for the blocked (BWMA) encoder.

The paper separates *arrangement* (how matrices are laid out in memory) from
*execution* (the kernels that consume them).  This module does the same for
the repo: :class:`Backend` is the set of compute operators the encoder needs,
all closed over :class:`~repro.core.blockwise.Blocked` values, with two
implementations:

* ``"reference"`` — the pure-jnp blockwise operators from
  :mod:`repro.core.blockwise`.  Bit-for-bit the semantics the tests treat as
  the oracle; XLA fuses it however it likes.
* ``"pallas"`` — the Pallas kernels from :mod:`repro.kernels`: blocked GEMM,
  blocked softmax/layernorm, the fused GEMM+bias+GELU feed-forward, and the
  fused attention (scores -> softmax -> @V without materializing scores in
  HBM).  They compile on every platform but the CPU, where they run with
  ``interpret=True`` so the tests exercise the identical BlockSpecs/grids.

Layout-neutral element-wise ops (add, bias, scale, map) are shared: they are
the paper's "Activation" category — no data movement depends on arrangement,
so there is nothing for a kernel backend to change (the FFN fusion handles
the one case where fusing them into a GEMM epilogue matters).

The protocol also carries the serving engine's paged-decode operators
(``paged_attention_decode``, ``mla_paged_attention_decode``,
``paged_copy_page``): the engine's KV pages are sized to ``cfg.block``, so
they are already kernel tiles — the reference backend reads them through
the jnp gather->attend oracle, the pallas backend streams them page-by-page
through the fused kernels in :mod:`repro.kernels.paged_attention`.  The
engine selects per :attr:`ModelConfig.decode_backend` via
:func:`resolve_backend`.

Select a backend by name or instance::

    from repro.core import backend as B
    be = B.resolve_backend("pallas")           # interpret on CPU only
    be = B.resolve_backend("pallas", interpret=True)
    be = B.resolve_backend(MyCustomBackend())
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Protocol, Union, runtime_checkable

import jax

from jax.sharding import PartitionSpec as _P

from repro.core import blockwise as bw
from repro.core.blockwise import Blocked
from repro.kernels.bwma_attention import bwma_attention
from repro.kernels.bwma_fused_ffn import bwma_fused_ffn
from repro.kernels.bwma_gemm import bwma_gemm
from repro.kernels.bwma_layernorm import bwma_layernorm
from repro.kernels.bwma_softmax import bwma_softmax
from repro.kernels.bwma_transpose import bwma_transpose
from repro.kernels.paged_attention import (
    KV_HEAD_AXIS,
    mla_paged_attention_decode,
    paged_attention_decode,
    paged_copy,
)


@runtime_checkable
class Backend(Protocol):
    """The operator set the blocked encoder dispatches through.

    All matrix arguments/results are :class:`Blocked`; blocked vectors
    (bias, gamma, beta) are raw ``(gn, bn)`` arrays as produced by
    :func:`repro.core.blockwise.block_vector`.  Implementations must accept
    leading batch/head dims on the data operands.
    """

    name: str

    def matmul(self, a: Blocked, b: Blocked) -> Blocked: ...

    def softmax(self, a: Blocked) -> Blocked: ...

    def layernorm(self, a: Blocked, gamma_b, beta_b) -> Blocked: ...

    def ffn(self, a: Blocked, w: Blocked, bias_b) -> Blocked: ...

    def attention(self, q: Blocked, k: Blocked, v: Blocked, *, scale) -> Blocked: ...

    def transpose(self, a: Blocked) -> Blocked: ...

    # -- serving-engine paged-decode operators (raw arrays, not Blocked:
    # -- the engine's pages already ARE kernel tiles — page size is
    # -- cfg.block — so there is no separate blocked arrangement step).
    # -- Pools are layer-stacked (L, num_pages, *block); the decode reads
    # -- take the layer index, the COW copy moves a page in every layer,
    # -- and ``head_axis`` names the stacked pools' kv-head axis (None:
    # -- the pools have none) --

    def paged_attention_decode(self, q, k_pages, v_pages, page_table,
                               seq_pos, layer): ...

    def mla_paged_attention_decode(self, q_lat, q_rope, ckv_pages,
                                   krope_pages, page_table, seq_pos, layer,
                                   *, scale): ...

    def paged_copy_page(self, pools: Dict, src, dst,
                        head_axis: Optional[int] = None) -> Dict: ...

    # -- layout-neutral element-wise ops (shared implementations) --

    def add(self, a: Blocked, b: Blocked) -> Blocked: ...

    def bias(self, a: Blocked, bias_b) -> Blocked: ...

    def scale(self, a: Blocked, s) -> Blocked: ...

    def map(self, a: Blocked, fn: Callable) -> Blocked: ...


class _ElementwiseMixin:
    """The arrangement-independent ops, shared by every backend."""

    def add(self, a: Blocked, b: Blocked) -> Blocked:
        return bw.bw_add(a, b)

    def bias(self, a: Blocked, bias_b) -> Blocked:
        return bw.bw_bias(a, bias_b)

    def scale(self, a: Blocked, s) -> Blocked:
        return bw.bw_scale(a, s)

    def map(self, a: Blocked, fn: Callable) -> Blocked:
        return bw.bw_map(a, fn)


class ReferenceBackend(_ElementwiseMixin):
    """Pure-jnp blockwise semantics (the oracle path)."""

    name = "reference"

    def matmul(self, a: Blocked, b: Blocked) -> Blocked:
        return bw.bw_matmul(a, b)

    def softmax(self, a: Blocked) -> Blocked:
        return bw.bw_softmax(a)

    def layernorm(self, a: Blocked, gamma_b, beta_b) -> Blocked:
        return bw.bw_layernorm(a, gamma_b, beta_b)

    def ffn(self, a: Blocked, w: Blocked, bias_b) -> Blocked:
        return bw.bw_map(bw.bw_bias(bw.bw_matmul(a, w), bias_b), jax.nn.gelu)

    def attention(self, q: Blocked, k: Blocked, v: Blocked, *, scale) -> Blocked:
        return bw.bw_attention(q, k, v, scale=scale)

    def transpose(self, a: Blocked) -> Blocked:
        return bw.bw_transpose(a)

    # -- paged-decode operators: the jnp gather->attend oracle paths.
    # Lazy imports (models sits above core in the layering; the reference
    # math lives next to the cache layouts it reads, mirroring how
    # models.common.dense lazily resolves this module in the other
    # direction).

    def paged_attention_decode(self, q, k_pages, v_pages, page_table,
                               seq_pos, layer):
        from repro.models import attention as attn

        return attn.paged_gather_attend(
            q, k_pages, v_pages, page_table, seq_pos, layer
        )

    def mla_paged_attention_decode(self, q_lat, q_rope, ckv_pages,
                                   krope_pages, page_table, seq_pos, layer,
                                   *, scale):
        from repro.models import attention as attn

        return attn.mla_paged_gather_attend(
            q_lat, q_rope, ckv_pages, krope_pages, page_table, seq_pos,
            layer, scale=scale,
        )

    def paged_copy_page(self, pools: Dict, src, dst,
                        head_axis: Optional[int] = None) -> Dict:
        # jnp ops: GSPMD partitions them whatever the pools' sharding
        from repro.models import attention as attn

        return attn.paged_copy_page(pools, src, dst)


def _interpret_default() -> bool:
    """Interpret Pallas kernels on the CPU platform only."""
    return jax.default_backend() == "cpu"


class PallasBackend(_ElementwiseMixin):
    """The Pallas BWMA kernels — the execution path the paper describes.

    ``interpret=None`` auto-selects: the interpreter on the CPU platform
    (bit-accurate, the test path), compiled kernels everywhere else — a
    platform that cannot compile them fails instead of silently running
    interpreted XLA in place of the kernel.
    """

    name = "pallas"

    def __init__(self, *, interpret: Optional[bool] = None):
        self._interpret = interpret
        ip = self.interpret
        # jit each operator once per backend instance: repeated shapes
        # (every layer of an encoder, every step of a sweep) reuse the
        # compiled/interpreted trace instead of re-tracing the pallas_call.
        self._matmul = jax.jit(functools.partial(bwma_gemm, interpret=ip))
        self._softmax = jax.jit(functools.partial(bwma_softmax, interpret=ip))
        self._layernorm = jax.jit(functools.partial(bwma_layernorm, interpret=ip))
        self._ffn = jax.jit(functools.partial(bwma_fused_ffn, interpret=ip))
        self._attention = jax.jit(
            functools.partial(bwma_attention, interpret=ip),
            static_argnames=("scale",),
        )
        self._transpose = jax.jit(functools.partial(bwma_transpose, interpret=ip))
        # the paged-decode kernels are deliberately NOT jitted here: they
        # trace inline inside the engine's already-jitted decode / COW
        # steps (a nested pjit would hazard the donation aliasing the
        # engine's in-place pool update depends on); standalone callers
        # (benchmarks, tests) jit them as needed
        self._paged_attention_decode = functools.partial(
            paged_attention_decode, interpret=ip
        )
        self._mla_paged_attention_decode = functools.partial(
            mla_paged_attention_decode, interpret=ip
        )
        self._paged_copy = functools.partial(paged_copy, interpret=ip)

    @property
    def interpret(self) -> bool:
        if self._interpret is None:
            return _interpret_default()
        return self._interpret

    def matmul(self, a: Blocked, b: Blocked) -> Blocked:
        return self._matmul(a, b)

    def softmax(self, a: Blocked) -> Blocked:
        return self._softmax(a)

    def layernorm(self, a: Blocked, gamma_b, beta_b) -> Blocked:
        return self._layernorm(a, gamma_b, beta_b)

    def ffn(self, a: Blocked, w: Blocked, bias_b) -> Blocked:
        return self._ffn(a, w, bias_b)

    def attention(self, q: Blocked, k: Blocked, v: Blocked, *, scale) -> Blocked:
        return self._attention(q, k, v, scale=scale)

    def transpose(self, a: Blocked) -> Blocked:
        return self._transpose(a)

    # -- tensor-parallel dispatch: a pallas_call cannot be auto-partitioned
    # -- by GSPMD, so under an active TP shard policy (the engine's
    # -- mesh-traced steps install one) the paged kernels run PER SHARD via
    # -- shard_map with the head axis pre-partitioned.  Attention is
    # -- head-independent, so the per-shard online softmax is bit-identical
    # -- to the unsharded kernel on each head — the streamed pages never
    # -- cross devices and no collective is inserted here (only the
    # -- post-attention row-parallel projection all-reduces, outside).

    @staticmethod
    def _tp_policy(*head_counts):
        """The active TP policy when the head axes can shard, else None."""
        from repro.distributed import axes as AX

        pol = AX.current()
        if (pol is not None and pol.mesh is not None and pol.tp_size > 1
                and all(h % pol.tp_size == 0 for h in head_counts)):
            return pol
        return None

    @staticmethod
    def _axis_spec(ndim: int, axis: int, name: str):
        return _P(*(name if a == axis else None for a in range(ndim)))

    def paged_attention_decode(self, q, k_pages, v_pages, page_table,
                               seq_pos, layer):
        pol = self._tp_policy(q.shape[2], k_pages.shape[KV_HEAD_AXIS])
        if pol is not None:
            tp = pol.tp_axis
            head = _P(None, None, tp, None)
            pool = self._axis_spec(k_pages.ndim, KV_HEAD_AXIS, tp)
            return jax.shard_map(
                self._paged_attention_decode, mesh=pol.mesh,
                in_specs=(head, pool, pool, _P(), _P(), _P()),
                out_specs=head, check_vma=False,
            )(q, k_pages, v_pages, page_table, seq_pos, layer)
        return self._paged_attention_decode(
            q, k_pages, v_pages, page_table, seq_pos, layer
        )

    def mla_paged_attention_decode(self, q_lat, q_rope, ckv_pages,
                                   krope_pages, page_table, seq_pos, layer,
                                   *, scale):
        pol = self._tp_policy(q_lat.shape[2])
        if pol is not None:
            # latent pages carry no head axis: they stay replicated and each
            # device attends its own query heads against the full pools
            head = _P(None, None, pol.tp_axis, None)
            return jax.shard_map(
                functools.partial(
                    self._mla_paged_attention_decode, scale=scale
                ),
                mesh=pol.mesh,
                in_specs=(head, head, _P(), _P(), _P(), _P(), _P()),
                out_specs=head, check_vma=False,
            )(q_lat, q_rope, ckv_pages, krope_pages, page_table, seq_pos,
              layer)
        return self._mla_paged_attention_decode(
            q_lat, q_rope, ckv_pages, krope_pages, page_table, seq_pos,
            layer, scale=scale,
        )

    def paged_copy_page(self, pools: Dict, src, dst,
                        head_axis: Optional[int] = None) -> Dict:
        out = {}
        for name, pool in pools.items():
            # pools with a kv-head axis (dense/GQA K/V) COW-copy per head
            # shard; headless pools (MLA latent) copy replicated
            pol = (self._tp_policy(pool.shape[head_axis])
                   if head_axis is not None else None)
            if pol is not None:
                spec = self._axis_spec(pool.ndim, head_axis, pol.tp_axis)
                out[name] = jax.shard_map(
                    self._paged_copy, mesh=pol.mesh,
                    in_specs=(spec, _P(), _P()),
                    out_specs=spec, check_vma=False,
                )(pool, src, dst)
            else:
                out[name] = self._paged_copy(pool, src, dst)
        return out


BACKENDS: Dict[str, Callable[..., Backend]] = {
    "reference": lambda **kw: ReferenceBackend(),
    "pallas": PallasBackend,
}

# Named backends are memoized: a PallasBackend's jit caches live on the
# instance, so handing out a fresh instance per resolve would retrace every
# kernel on every encoder/benchmark call.
_INSTANCES: Dict[tuple, Backend] = {}


def resolve_backend(
    spec: Union[str, Backend, None], *, interpret: Optional[bool] = None
) -> Backend:
    """Turn a backend name / instance / None into a Backend.

    ``None`` means ``"reference"``.  ``interpret`` only applies to backends
    that take it (the Pallas one) — passing it with any other backend is an
    error rather than a silent no-op.  Instances for a given resolved
    ``(name, interpret)`` are shared so their compilation caches persist.
    """
    if spec is None:
        spec = "reference"
    if isinstance(spec, str):
        try:
            factory = BACKENDS[spec]
        except KeyError:
            raise ValueError(
                f"unknown backend {spec!r}; available: {sorted(BACKENDS)}"
            ) from None
        takes_interpret = factory is PallasBackend
        if interpret is not None and not takes_interpret:
            raise ValueError(
                f"interpret={interpret!r} only applies to the 'pallas' "
                f"backend, not {spec!r}"
            )
        if takes_interpret:
            # normalize auto (None) to its resolved value so the auto and
            # explicit spellings share one instance (and one jit cache)
            resolved = (interpret if interpret is not None
                        else _interpret_default())
            key = (spec, resolved)
            kw = {"interpret": resolved}
        else:
            key, kw = (spec, None), {}
        if key not in _INSTANCES:
            _INSTANCES[key] = factory(**kw)
        return _INSTANCES[key]
    if isinstance(spec, Backend):
        if interpret is not None:
            raise ValueError(
                "interpret= cannot override an already-constructed Backend"
            )
        return spec
    raise TypeError(f"backend must be a name or Backend, got {type(spec)}")
