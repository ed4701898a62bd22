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

from repro.core import blockwise as bw
from repro.core.blockwise import Blocked
from repro.kernels.bwma_attention import bwma_attention
from repro.kernels.bwma_fused_ffn import bwma_fused_ffn
from repro.kernels.bwma_gemm import bwma_gemm
from repro.kernels.bwma_layernorm import bwma_layernorm
from repro.kernels.bwma_softmax import bwma_softmax
from repro.kernels.bwma_transpose import bwma_transpose


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
