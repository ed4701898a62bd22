"""Serving engines: static-wave batching and continuous batching.

Two engines share the model's prefill/decode functions:

* :class:`Server` — the original **static-wave** engine: one batch of
  requests prefills together, decodes in lockstep, and the wave drains
  before the next starts.  Finished slots keep stepping on padding.  Kept as
  the baseline the continuous engine is benchmarked against.
* :class:`Engine` — **continuous batching** over the block-paged KV cache
  (:mod:`repro.serve.kvcache`): a scheduler admits requests from a queue
  into batch slots as pages free up, each slot advances at its own position,
  and a finished slot is re-filled the same step.  The decode step is one
  jitted function of static shape ``(max_seqs, 1)``.

**Chunked, donating prefill**: admission feeds a prompt through the model
in page-sized chunks (:func:`repro.models.model.prefill_chunk`), each
chunk's K/V scattered straight into its physical pages by a jitted step
that *donates* the cache pytree — no admission copies the pool, and a long
prompt interleaves with the running batch's decode steps instead of
stalling it.  Chunking also bounds jit-cache growth: every prompt length
reuses one full-chunk shape plus a small set of final-chunk shapes
(power-of-two buckets for dense/GQA; exact lengths — capped by the chunk
size — where semantics require it: SWA ring packing, SSM final states).

**Shared-prefix paged KV**: physical pages are reference-counted, and a
radix prefix index over page-aligned token prefixes lets an admission
*alias* the pages of a prompt's longest cached prefix — chunked prefill
then starts mid-prompt at the first uncached page boundary, and a decode
write into a still-shared page copies-on-write (:mod:`repro.serve.kvcache`).
Shared system prompts are the common case in production traffic: the
redundant prefill they used to cost is exactly the avoidable off-chip
traffic the paper's arrangement thesis targets.

Cache families are the registry's business (:mod:`repro.models.adapters`):
one :class:`~repro.models.adapters.CacheAdapter` per layer family owns its
pool shapes, chunk scatter, decode gather, active-mask semantics and
prefix-shareability — dense/GQA K/V pages, MLA latent pages, SWA rings,
SSM state rows, enc-dec cross rows (installed once at admission).  The
engine drives adapters generically; only the vision frontend still
requires :class:`Server`.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed import axes as AX
from repro.distributed import sharding as SH
from repro.models import adapters as A
from repro.models import attention as attn
from repro.models import model as M
from repro.models.model import frontend_extras  # re-exported for callers
from repro.serve.kvcache import PagedCacheConfig, PagedKVCache
from repro.serve.obs import Observability
from repro.serve.scheduler import Request, Scheduler


@dataclasses.dataclass
class ServeConfig:
    """Static-wave server knobs.

    ``prefill_bucket``: quantum (tokens) for power-of-two prompt-length
    bucketing of dense/GQA prefill — 0 derives it from ``cfg.block``, -1
    disables bucketing (one jit entry per distinct prompt length, the
    unbounded-compile-cache failure mode).  Families whose prefill
    semantics depend on exact length (SWA ring packing, SSM states, MoE
    capacity) always use exact shapes regardless.
    """

    max_len: int = 512
    temperature: float = 0.0  # 0 = greedy
    eos_id: Optional[int] = None
    seed: int = 0
    prefill_bucket: int = 0


def bucket_tokens(n: int, block: int) -> int:
    """Round a token count up to a power-of-two number of ``block``-sized
    pages — the shared jit shapes for bucketed (dense/GQA) prefill."""
    pages = max(1, math.ceil(n / block))
    return (1 << (pages - 1).bit_length()) * block


# --------------------------------------------------------------------------
# Chunk-shape closure: the jit signatures chunked admission may compile.
#
# These are module-level (not Engine methods) so the compiled-artifact
# linter (repro.analysis.jaxcheck, rule RPJ104) can statically enumerate the
# engine's expected jit-cache key set without constructing an engine — and
# fail when a code change lets a prompt length escape the closure.
# --------------------------------------------------------------------------


def resolve_chunk_size(cfg: ModelConfig, page_size: int, requested: int = 0) -> int:
    """Prefill chunk size: page-sized by default, adapter-grid-aligned
    (see :meth:`Engine._resolve_chunk`, which delegates here)."""
    grid = A.prefill_chunk_multiple(cfg)
    if requested:
        if requested < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {requested}")
        if requested % grid:
            raise ValueError(
                f"prefill_chunk {requested} must be a multiple of "
                f"the cache adapters' chunk grid {grid}"
            )
        return requested
    return math.lcm(page_size, grid)


def final_chunk_len(cfg: ModelConfig, chunk_size: int, n: int) -> int:
    """Jit shape for a final (ragged) chunk of ``n`` real tokens: bucketed
    to the next power of two for dense/GQA, exact (capped by the chunk
    size) where semantics require it (SWA rings, SSM states, MoE)."""
    if not M.supports_padded_prefill(cfg):
        return n
    return min(bucket_tokens(n, 1), chunk_size)


def chunk_plan(cfg: ModelConfig, chunk_size: int, prompt_len: int,
               cached: int = 0) -> List[int]:
    """The chunk jit shapes (token lengths) admission runs for a prompt of
    ``prompt_len`` tokens, ``cached`` of them served from the prefix cache
    (chunking resumes at the first uncached token)."""
    plan: List[int] = []
    off = cached
    while off < prompt_len:
        n = min(chunk_size, prompt_len - off)
        last = off + n >= prompt_len
        plan.append(final_chunk_len(cfg, chunk_size, n) if last else chunk_size)
        off += n
    return plan


def chunk_shape_set(cfg: ModelConfig, chunk_size: int) -> tuple:
    """Every chunk length :func:`chunk_plan` can ever emit — the closed set
    of ``prefill_chunk`` jit signatures for this (config, chunk size).
    Bucketing families: the full chunk plus each power of two below it;
    exact-shape families: every length up to the chunk size."""
    if M.supports_padded_prefill(cfg):
        shapes = {chunk_size}
        p = 1
        while p <= chunk_size:
            shapes.add(p)
            p *= 2
        return tuple(sorted(shapes))
    return tuple(range(1, chunk_size + 1))


def _bound(fn, cfg: ModelConfig):
    """``functools.partial(fn, cfg)`` named after ``fn``: jit names its
    program, and the profiler its runs, ``jit_<name>`` (a bare partial
    lowers as ``jit__unknown``), so a trace tells the steps apart."""
    step = functools.partial(fn, cfg)
    step.__name__ = step.__qualname__ = fn.__name__
    return step


# jitted step functions are memoized per (hashable, frozen) ModelConfig so
# every engine instance — and repeated benchmark constructions — share one
# compile cache; the mesh path builds its own closures under the mesh context
@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg: ModelConfig):
    return jax.jit(_bound(M.prefill, cfg))


@functools.lru_cache(maxsize=None)
def _decode_fn(cfg: ModelConfig):
    return jax.jit(_bound(M.decode_step, cfg))


def _paged_step(cfg: ModelConfig, params, caches, tokens, seq_pos, page_table,
                active):
    logits, new_caches = M.decode_step_paged(
        cfg, params, caches, tokens, seq_pos, page_table, active
    )
    # greedy argmax on-device (same fp32 math as Server._sample): the
    # continuous engine must sync every step to make scheduling
    # decisions, so keep that sync to one small (B,) transfer
    greedy = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
    return greedy.astype(jnp.int32), logits, new_caches


def sample_token(key, row, rid, k, temperature):
    """Token ``k`` of request ``rid`` from its (V,) logits row: a draw of
    ``softmax(row / T)`` as ``argmax(row / T + Gumbel)`` under the key
    ``fold_in(fold_in(key, rid), k)``.  The key is per request and
    position, so a draw does not depend on the slot or step that makes it."""
    key = jax.random.fold_in(jax.random.fold_in(key, rid), k)
    return jax.random.categorical(
        key, row.astype(jnp.float32) / temperature
    ).astype(jnp.int32)


def sample_step(key, logits, rids, ks, temperature):
    """:func:`sample_token` for each slot of the decode step's (B, 1, V)
    logits: (B,) int32.  Idle slots draw too; their tokens are ignored."""
    return jax.vmap(sample_token, (None, 0, 0, 0, None))(
        key, logits[:, -1], rids, ks, temperature
    )


# the device sampler: its own programs beside the step programs, which they
# follow on the same stream (no host sync in between); they touch no pool
_sample_step_fn = jax.jit(sample_step)
_sample_token_fn = jax.jit(sample_token)


def _donate_caches() -> tuple:
    # donate the cache pytree (arg 1 of the partial-bound step fns): the
    # page pool is the dominant buffer and the engine always replaces its
    # reference with the step's output, so the update must happen in place —
    # without donation every token would copy (and briefly double) the whole
    # multi-layer pool.  XLA:CPU honors donation on this jax pin (verified
    # by the aliasing regression test in tests/test_serve.py), so ask
    # everywhere.
    return (1,)


@functools.lru_cache(maxsize=None)
def _decode_paged_fn(cfg: ModelConfig):
    return jax.jit(_bound(_paged_step, cfg), donate_argnums=_donate_caches())


@functools.lru_cache(maxsize=None)
def _prefill_chunk_fn(cfg: ModelConfig):
    return jax.jit(_bound(M.prefill_chunk, cfg), donate_argnums=_donate_caches())


def jitted_step_fns(cfg: ModelConfig) -> Dict[str, tuple]:
    """The continuous engine's jitted hot-path steps, **un-jitted**.

    ``{name: (fn, donate_argnums)}`` — the inventory the compiled-artifact
    linter (:mod:`repro.analysis.jaxcheck`) lowers and compiles ahead of
    time.  These are exactly the callables the engine wraps in
    :func:`_decode_paged_fn` / :func:`_prefill_chunk_fn`; the cache-install
    and COW steps live with the pool they mutate
    (:func:`repro.serve.kvcache.install_step` /
    :func:`repro.serve.kvcache.cow_step`).

    ``cfg.decode_backend`` selects the decode/COW execution path the steps
    trace (jnp gather oracle vs fused pallas kernels) — pass a
    ``dataclasses.replace(cfg, decode_backend="pallas")`` config to
    inventory the kernelized hot loop.
    """
    from repro.serve import kvcache as KV

    return {
        "decode_step": (_bound(_paged_step, cfg), _donate_caches()),
        "prefill_chunk": (_bound(M.prefill_chunk, cfg), _donate_caches()),
        "cow_copy": (KV.cow_step(cfg), KV.POOL_DONATE),
        "install": (KV.install_step(cfg), KV.POOL_DONATE),
    }


class Server:
    """Static-wave batched generation (the pre-paging baseline engine)."""

    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig, mesh=None):
        self.cfg, self.params, self.sc, self.mesh = cfg, params, sc, mesh
        if mesh is not None:
            # resident 2-D TP weights; the step bodies trace under the mesh
            # (AX.traced_under) so the model's activation constraints see
            # the policy — a context around jit *construction* would be gone
            # by (lazy) trace time
            params_shape = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
            )
            self.params = jax.device_put(params, SH.named(
                mesh, SH.param_pspecs(cfg, mesh, params_shape, mode="serve")
            ))
            self._prefill = jax.jit(
                AX.traced_under(mesh, lambda p, b, *a: M.prefill(cfg, p, b, *a))
            )
            self._decode = jax.jit(AX.traced_under(
                mesh, lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos)
            ))
        else:
            self._prefill = _prefill_fn(cfg)
            self._decode = _decode_fn(cfg)

    def _sample(self, logits, key):
        logits = logits[:, -1].astype(jnp.float32)
        if self.sc.temperature <= 0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.sc.temperature, axis=-1
        ).astype(jnp.int32)

    def _grow_cache(self, caches, batch: int, prompt_len: int):
        """Pad prefill caches out to max_len slots (static decode shapes)."""
        full = M.init_cache(self.cfg, batch, self.sc.max_len)

        def fit(small, big):
            if small.shape == big.shape:
                return small.astype(big.dtype)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), (0,) * big.ndim
            )

        return jax.tree.map(fit, caches, full)

    def generate(self, batch: Dict, max_new_tokens: int = 32) -> np.ndarray:
        """batch: prompt inputs (tokens (B, S) + frontend extras)."""
        cfg, sc = self.cfg, self.sc
        tokens = batch["tokens"]
        B, S = tokens.shape
        assert S + max_new_tokens <= sc.max_len, "increase ServeConfig.max_len"
        if sc.prefill_bucket >= 0 and M.supports_padded_prefill(cfg):
            # bucket the prompt length to power-of-two pages: every length
            # shares a handful of jit entries instead of compiling its own.
            # Pad keys are causally masked during prefill and overwritten by
            # decode before their position label becomes reachable, so the
            # logits at last_idx = S - 1 (and everything after) are
            # bit-identical to the exact-shape prefill.
            quantum = sc.prefill_bucket or cfg.block
            Sp = min(bucket_tokens(S, quantum), sc.max_len)
            padded = np.zeros((B, Sp), np.int32)
            padded[:, :S] = np.asarray(tokens)
            logits, caches = self._prefill(
                self.params, {"tokens": jnp.asarray(padded)}, jnp.int32(S - 1)
            )
        else:
            logits, caches = self._prefill(self.params, batch)
        caches = self._grow_cache(caches, B, S)
        key = jax.random.PRNGKey(sc.seed)
        out = []
        done = jnp.zeros((B,), bool)
        # split BEFORE the first sample so no key is ever used twice
        key, sub = jax.random.split(key)
        tok = self._sample(logits, sub)
        for i in range(max_new_tokens):
            out.append(tok)
            if sc.eos_id is not None:
                done = done | (tok == sc.eos_id)
                if bool(done.all()):
                    break
            key, sub = jax.random.split(key)
            logits, caches = self._decode(
                self.params, caches, tok[:, None], jnp.int32(S + i)
            )
            tok = self._sample(logits, sub)
        return np.stack([np.asarray(t) for t in out], axis=1)


# --------------------------------------------------------------------------
# Continuous batching over the block-paged cache
# --------------------------------------------------------------------------

@dataclasses.dataclass
class EngineConfig:
    """Continuous-batching engine knobs.

    ``page_size=0`` derives the page from ``cfg.block`` (the accelerator
    kernel block governs the cache arrangement); ``num_pages=0`` sizes the
    pool for ``max_seqs`` full-length sequences.

    ``prefill_chunk=0`` derives the chunk from the page size (one chunk =
    one page of tokens), lifted onto each adapter's chunk grid (e.g. the
    SSD ``ssm_chunk`` grid — the alignment that keeps chunked prefill
    bit-identical to one-shot).

    ``prefill_tokens_per_step`` is the admission budget: how many prompt
    *tokens* may run per engine step before the decode batch steps — small
    values bound the latency a long prompt can inject between two decode
    steps of the running batch.  The budget is spent page-granularly (the
    chunk is the execution quantum, and chunks are page-sized), so the
    effective budget rounds up to whole chunks.  ``0`` derives it as 4
    chunks.

    ``chunked_prefill=False`` falls back to one-shot prefill per admission
    (still installed through the jitted donating updater).

    ``prefix_sharing`` lets requests with a common page-aligned token
    prefix alias the same physical pages (radix prefix index + refcounts +
    copy-on-write divergence).  Only effective for families whose pages
    the adapter registry declares shareable (dense/GQA, MLA); stateful
    families (SWA rings, SSM rows, enc-dec) fall through to the unshared
    path, and MoE stacks alias pages but recompute every token.

    ``backend`` selects the paged-decode execution path
    (:data:`repro.models.attention.DECODE_BACKENDS`): ``"reference"``
    keeps the jnp gather->attend decode and dense COW copy; ``"pallas"``
    streams pages through the fused paged-attention / paged-copy kernels
    (interpret mode on the CPU platform only).  Folded into
    ``cfg.decode_backend``, so every jitted step cache keys on it.
    """

    max_seqs: int = 4
    max_len: int = 128  # per-request capacity (prompt + generation)
    page_size: int = 0
    num_pages: int = 0
    chunked_prefill: bool = True
    prefill_chunk: int = 0
    prefill_tokens_per_step: int = 0  # 0: 4 chunks
    prefix_sharing: bool = True
    backend: str = "reference"  # paged-decode path: reference | pallas
    temperature: float = 0.0  # 0 = greedy
    eos_id: Optional[int] = None
    seed: int = 0
    # run the PagedKVCache refcount auditor after every step (sanitizer /
    # debugging aid: cross-checks allocator refcounts against slot page
    # tables and the prefix index — free + index-pinned + slot-held == total)
    debug_audit: bool = False
    # deep observability: spans, counters and cheap gauges are always on
    # (host bookkeeping that cannot change outputs); this additionally runs
    # the pool audit every step for the index_pinned/slot_held gauge split
    obs: bool = False


_DEFAULT_CHUNKS_PER_STEP = 4  # chunks a step when no token budget is set


class Engine:
    """Continuous-batching serving engine (scheduler + paged KV cache)."""

    def __init__(self, cfg: ModelConfig, params, ec: EngineConfig, mesh=None):
        # fold the backend selector into the frozen config: every memoized
        # step jit (_decode_paged_fn, _cow_fn, ...) keys on the ModelConfig,
        # so reference and pallas engines coexist without cache collisions.
        # Check eagerly so an unknown name fails here, not mid-trace.
        attn.uses_paged_kernels(ec.backend)
        if ec.backend != cfg.decode_backend:
            cfg = dataclasses.replace(cfg, decode_backend=ec.backend)
        self.cfg, self.params, self.ec, self.mesh = cfg, params, ec, mesh
        if mesh is not None:
            # fail fast at construction: a model-axis size the kv-head axis
            # cannot divide would silently replicate every paged pool
            SH.validate_paged_sharding(cfg, mesh)
        # unsupported families are refused by the PagedKVCache constructor
        # (before any pool is allocated), with the registry's family list
        # recompute families (MoE stacks) rely on prefix chunks replaying
        # the publisher's exact chunk grid for bit-identical page content;
        # one-shot prefill groups the whole prompt per request, so sharing
        # is only sound there for compute-skippable families
        sharing = ec.prefix_sharing and (
            ec.chunked_prefill or A.prefix_compute_skippable(cfg)
        )
        self.kv = PagedKVCache(cfg, PagedCacheConfig(
            max_seqs=ec.max_seqs, max_len=ec.max_len,
            page_size=ec.page_size, num_pages=ec.num_pages,
            prefix_sharing=sharing,
        ), mesh=mesh)
        self.obs = Observability(deep=ec.obs, max_seqs=ec.max_seqs)
        self.obs.deployment(1 if mesh is None else mesh.devices.size,
                            self.kv.cache_bytes_per_device())
        self.sched = Scheduler(self.kv, ec.max_seqs, obs=self.obs)
        self.chunk_size = self._resolve_chunk(ec.prefill_chunk)
        if ec.prefill_tokens_per_step < 0:
            raise ValueError("prefill_tokens_per_step must be >= 0")
        # token-level admission budget
        self.tokens_per_step = (
            ec.prefill_tokens_per_step
            or _DEFAULT_CHUNKS_PER_STEP * self.chunk_size
        )
        # adapters installing request-level context once at admission
        # (enc-dec encoder K/V) — resolved from the registry, not by family
        self._admission_ads = A.admission_adapters(cfg)

        if mesh is not None:
            # per-instance sharded closures (the sjit idiom): explicit
            # in/out shardings so pool donation composes with GSPMD
            # partitioning, bodies traced under the mesh (AX.traced_under)
            # so activation constraints and the pallas shard_map dispatch
            # see the policy at trace time.  Small host-fed inputs (tokens,
            # positions, page tables, scalars) are replicated.
            param_sh, pool_sh, rep = SH.serve_shardings(
                cfg, mesh, params, self.kv.data
            )
            self.params = jax.device_put(params, param_sh)
            self._prefill = jax.jit(
                AX.traced_under(mesh, _bound(M.prefill, cfg))
            )
            self._chunk_fn = jax.jit(
                AX.traced_under(mesh, _bound(M.prefill_chunk, cfg)),
                in_shardings=(
                    param_sh, pool_sh, rep, rep, rep, rep, rep, rep, rep
                ),
                out_shardings=(rep, pool_sh),
                donate_argnums=_donate_caches(),
            )
            self._decode = jax.jit(
                AX.traced_under(mesh, _bound(_paged_step, cfg)),
                in_shardings=(param_sh, pool_sh, rep, rep, rep, rep),
                out_shardings=(rep, rep, pool_sh),
                donate_argnums=_donate_caches(),
            )
        else:
            self._prefill = _prefill_fn(cfg)
            self._chunk_fn = _prefill_chunk_fn(cfg)
            self._decode = _decode_paged_fn(cfg)
        # per-slot last sampled token, kept ON DEVICE: the decode loop feeds
        # each step's tokens (greedy or sampled) straight back in, syncing to
        # host only at scheduling events (first token, finish, preemption, EOS)
        self._last_tok = jnp.zeros((ec.max_seqs,), jnp.int32)
        # the sampler's base key and temperature, on the device once
        self._key = jax.random.PRNGKey(ec.seed)
        self._temperature = jnp.float32(ec.temperature)
        # deferred token log: (device (B,) tokens, [(slot, req), ...])
        self._pending: List[tuple] = []
        self._rid_counter = 0
        self.step_count = 0
        self.decode_steps = 0
        self.prefill_tokens = 0
        self.prefill_chunks = 0  # chunk steps actually run (sharing skips)

    # -- request intake -----------------------------------------------------

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        *,
        rid: Optional[int] = None,
        arrival_step: int = 0,
        extras: Optional[Dict] = None,
    ) -> Request:
        """``extras``: per-request modality inputs beyond the token prompt
        (e.g. a (1, encoder_seq, d_model) ``audio_embeds`` for enc-dec).
        Missing entries are stub-filled at prefill time, matching the
        static-wave baseline; extras survive preemption (re-admission
        re-runs the encoder — recompute discipline)."""
        if rid is None:
            rid = self._rid_counter
        self._rid_counter = max(self._rid_counter, rid) + 1
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        req = Request(
            rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
            arrival_step=arrival_step, extras=extras,
        )
        self.sched.submit(req)
        return req

    def _extras_batch(self, req: Request) -> Dict:
        """The request's modality inputs, stub-filled where missing."""
        batch = dict(req.extras or {})
        return frontend_extras(self.cfg, batch, 1, req.prompt_len)

    # -- sampling -----------------------------------------------------------

    def _sample(self, row_logits: jnp.ndarray, req: Request) -> int:  # repro: hot-loop
        """Sample one token from a (V,) logits row (fp32, greedy or temp).

        Only first tokens come here: the decode batch's tokens stay on the
        device (:meth:`_decode_once`), and this sync runs at admissions."""
        if self.ec.temperature <= 0:
            lf = row_logits.astype(jnp.float32)
            tok = jnp.argmax(lf)
        else:
            # the decode sampler's draw for one row: one program, one sync
            tok = _sample_token_fn(
                self._key, row_logits, np.uint32(req.rid),
                np.uint32(req.n_generated), self._temperature,
            )
            self.obs.sampled(1)
        # the host needs the value now: scheduling reads every first token
        with self.obs.sync():
            return int(tok)  # repro: noqa RPR002 -- sanctioned sync

    def _append_token(self, slot: int, req: Request, tok: int) -> None:
        req.out_tokens.append(tok)
        self._last_tok = self._last_tok.at[slot].set(tok)
        if req.done or (self.ec.eos_id is not None and tok == self.ec.eos_id):
            self.sched.finish(slot, self.step_count)

    def _flush_pending(self) -> None:  # repro: hot-loop
        """Materialize the deferred on-device tokens into out_tokens.

        All logged arrays are already computed (or in flight) on the device,
        so this drains the async queue once instead of once per step."""
        if not self._pending:
            return
        with self.obs.span("serve.flush"):
            # THE deferred-sync point: the only place the decode loop
            # pays device->host, amortized over every step since the last flush
            with self.obs.sync(len(self._pending)):
                rows = np.stack(  # repro: noqa RPR002 -- sanctioned deferred sync
                    [np.asarray(g) for g, _ in self._pending]  # repro: noqa RPR002
                )
            for row, (_, running) in zip(rows, self._pending):
                for slot, req in running:
                    req.out_tokens.append(int(row[slot]))  # repro: noqa RPR002 -- host ndarray
                    req.n_pending -= 1
            self._pending.clear()

    # -- prefill ------------------------------------------------------------

    def _resolve_chunk(self, requested: int) -> int:
        """Prefill chunk size: page-sized by default, adapter-grid-aligned.

        Every adapter reports the grid its chunk boundaries must sit on
        (the SSD ``ssm_chunk`` grid for SSM states — the grid the one-shot
        prefill uses, so every chunk reproduces the exact per-chunk ops of
        the one-shot path, bit-exactness); attention families accept any
        boundary (grid 1).
        """
        return resolve_chunk_size(self.cfg, self.kv.page_size, requested)

    def _last_chunk_len(self, n: int) -> int:
        """Jit shape for a final (ragged) chunk of ``n`` real tokens.

        Dense/GQA buckets to the next power of two (pad keys land in the
        null page / the decode page and are masked or overwritten before
        they become visible — bit-exact); SWA ring packing and SSM final
        states need the exact length, which is still capped by the chunk
        size, so shapes stay bounded either way.
        """
        return final_chunk_len(self.cfg, self.chunk_size, n)

    def _install_admission_context(self, slot: int, req: Request) -> None:
        """Run the registry's admission-time installs for a fresh slot
        (e.g. enc-dec: one encoder pass -> immutable cross rows).  Happens
        again after a preemption — recompute discipline."""
        for ad in self._admission_ads:
            src = ad.admission_src(self.cfg, self.params,
                                   self._extras_batch(req))
            self.kv.install_partial(slot, src)

    def _prefill_one_chunk(self, slot: int, req: Request) -> int:  # repro: hot-loop
        """Feed the next chunk of a slot's prompt through the paged caches.

        The chunk step donates the cache pytree — the pool is written in
        place — and on the final chunk samples the request's first token.
        Returns the number of real prompt tokens consumed (the admission
        budget's unit).
        """
        with self.obs.span("serve.prefill"):
            prompt = req.effective_prompt
            off = req.prefill_pos
            n = min(self.chunk_size, len(prompt) - off)
            # full chunks share ONE jit shape; the final ragged chunk draws
            # from the small bucketed/exact shape set (bounded by the chunk size)
            n_pad = self._last_chunk_len(n) if off + n >= len(prompt) else n
            toks = np.zeros((1, n_pad), np.int32)
            toks[0, :n] = prompt[off : off + n]
            phys_tok, off_tok = self.kv.token_targets(slot, off, n_pad)
            self.obs.chunk_begin(req, self.step_count, off, n)
            logits, self.kv.data = self._chunk_fn(
                self.params, self.kv.data, jnp.asarray(toks), jnp.int32(slot),
                jnp.int32(off), phys_tok, off_tok, self.kv.table_row(slot),
                jnp.int32(n - 1),
            )
            req.prefill_pos += n
            self.prefill_tokens += n
            self.prefill_chunks += 1
            self.obs.chunk_end(req, self.step_count)
            # publish newly completed full pages: from here on, prompts
            # sharing this prefix alias these pages instead of recomputing them
            self.kv.commit_prefix(slot, prompt, req.prefill_pos)
        if not req.prefilling:  # final chunk: sample the first token
            self._first_token(slot, req, logits[0, -1])
        return n

    def _first_token(self, slot: int, req: Request, row_logits) -> None:  # repro: hot-loop
        """Sample an admission's first token and stamp the milestone once
        the token is on the host.  The prefill span closes and the decode
        span opens before the token is appended: with max_new == 1 it
        finishes the request, and finish must close an open decode span."""
        with self.obs.span("serve.sample"):
            tok = self._sample(row_logits, req)
            self.obs.prefill_complete(req, self.step_count)
            self._append_token(slot, req, tok)

    def _prefill_full(self, slot: int, req: Request) -> None:
        """One-shot prefill + jitted donating install (unchunked path)."""
        with self.obs.span("serve.prefill"):
            prompt = req.effective_prompt
            S = len(prompt)
            extras = self._extras_batch(req)
            if M.supports_padded_prefill(self.cfg):
                # clamp to the per-slot capacity: positions past max_len can
                # never be used, so padding beyond it would only waste
                # compute and compile an oversized shape
                Sp = min(bucket_tokens(S, self.kv.page_size), self.kv.max_len)
                toks = np.zeros((1, Sp), np.int32)
                toks[0, :S] = prompt
                logits, caches = self._prefill(
                    self.params, {"tokens": jnp.asarray(toks), **extras},
                    jnp.int32(S - 1),
                )
            else:
                logits, caches = self._prefill(
                    self.params, {"tokens": jnp.asarray(prompt)[None], **extras}
                )
            self.kv.install_prefill(slot, caches)
            req.prefill_pos = req.prefill_target
            self.prefill_tokens += S
            self.kv.commit_prefix(slot, prompt, S)
        self._first_token(slot, req, logits[0, -1])

    # -- engine steps -------------------------------------------------------

    def _admit_and_prefill(self) -> None:  # repro: hot-loop
        with self.obs.span("serve.schedule"):
            self.sched.poll_arrivals(self.step_count)
            admitted = self.sched.admit(self.step_count)
            if self.ec.chunked_prefill:
                # request-level admission context (enc-dec encoder K/V)
                # installs at admission, not on the first chunk: a
                # shared-prefix admission may resume its chunking mid-prompt
                # and never see offset 0
                for slot, req in admitted:
                    self._install_admission_context(slot, req)
        if not self.ec.chunked_prefill:
            for slot, req in admitted:
                self._prefill_full(slot, req)
            return
        # token budget: oldest admission first (FIFO toward first token);
        # whatever is left after the budget waits for the next engine step,
        # with the decode batch stepping in between — a max-length prompt
        # can no longer stall in-flight decodes for its whole prefill.
        # Spending is page-granular (chunks are page-sized): a chunk may
        # start while any budget remains, so a step overshoots by at most
        # one chunk.
        budget = self.tokens_per_step
        for slot, req in self.sched.prefilling:
            while budget > 0 and req.prefilling:
                budget -= self._prefill_one_chunk(slot, req)
            if budget <= 0:
                break

    def _decode_once(self) -> None:  # repro: hot-loop
        with self.obs.span("serve.decode"):
            decoding = self.sched.decoding
            deficit = sum(
                self.kv.growth_deficit(slot, req.next_pos)
                for slot, req in decoding
            ) if decoding else 0
            # available_pages walks the prefix tree — consult it only when
            # the free list alone cannot cover the round's growth
            if (deficit > self.kv.num_free_pages
                    and deficit > self.kv.available_pages):
                # the growth round below may preempt: victims must carry
                # their full token history back to the queue, so sync first
                self._flush_pending()
            self.sched.grow_for_decode(self.step_count)
            decoding = self.sched.decoding
            self.obs.decode_batch(len(decoding))
            if not decoding:
                return
            seq_pos = np.zeros((self.ec.max_seqs,), np.int32)  # idle slots -> 0
            active = np.zeros((self.ec.max_seqs,), bool)  # idle/prefilling: False
            for slot, req in decoding:
                seq_pos[slot] = req.next_pos
                active[slot] = True
            tokens, logits, self.kv.data = self._decode(
                self.params, self.kv.data, self._last_tok[:, None],
                jnp.asarray(seq_pos), self.kv.page_table(), jnp.asarray(active),
            )
        self.decode_steps += 1
        if self.ec.temperature > 0:
            # draw the batch on the device, queued right behind the step:
            # token k of each request, k counting tokens not yet on the host
            with self.obs.span("serve.sample"):
                rids = np.zeros((self.ec.max_seqs,), np.uint32)  # idle -> 0
                ks = np.zeros((self.ec.max_seqs,), np.uint32)
                for slot, req in decoding:
                    rids[slot] = req.rid
                    ks[slot] = req.n_generated
                tokens = _sample_step_fn(
                    self._key, logits, rids, ks, self._temperature
                )
                self.obs.sampled(len(decoding))
        self._last_tok = tokens  # feed back on-device; no host round-trip
        self._pending.append((tokens, decoding))
        for slot, req in decoding:
            req.n_pending += 1
        if self.ec.eos_id is not None:
            # early-stop decisions need token values every step
            self._flush_pending()
            for slot, req in decoding:
                if req.state == "running" and (
                    req.done or req.out_tokens[-1] == self.ec.eos_id
                ):
                    self.sched.finish(slot, self.step_count)
            return
        # max_new completion is pure length bookkeeping: no sync needed
        for slot, req in decoding:
            if req.done:
                self.sched.finish(slot, self.step_count)

    def step(self) -> None:  # repro: hot-loop
        """One engine iteration: arrivals -> admissions (prefill) -> decode."""
        obs = self.obs
        with obs.step_span(self.step_count):
            t0 = obs.step_begin()
            self._admit_and_prefill()
            self._decode_once()
            self.step_count += 1
            with obs.span("serve.bookkeeping"):
                audit = None
                if self.ec.debug_audit or obs.deep:
                    audit = self.kv.audit()
                obs.step_end(self, t0, audit)

    def run(self, max_steps: int = 1_000_000) -> List[Request]:
        """Drive until every submitted request finishes; returns the
        requests that finished during THIS call (rid order, stats
        populated) — a reused engine doesn't re-report earlier batches."""
        already = set(self.sched.finished)
        while self.sched.has_work():
            if self.step_count >= max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")
            before = self.step_count
            self.step()
            assert self.step_count > before
        self._flush_pending()
        return [
            self.sched.finished[rid]
            for rid in sorted(set(self.sched.finished) - already)
        ]

    # -- convenience --------------------------------------------------------

    def metrics(self) -> Dict[str, Any]:
        """JSON-ready snapshot of the engine's metrics registry."""
        return self.obs.registry.snapshot()

    def export_trace(self, path: str) -> Dict[str, Any]:
        """Write the recorded spans as Chrome-trace JSON (Perfetto-loadable)
        to ``path``; returns the trace object."""
        return self.obs.export_chrome_trace(path)

    def generate(self, batch: Dict, max_new_tokens: int = 32) -> np.ndarray:
        """Drop-in for Server.generate: all prompts arrive at step 0.

        Non-token batch entries with a leading batch axis (e.g. enc-dec
        ``audio_embeds``) are split into per-request extras.  With
        ``eos_id`` set, requests that stop early are right-padded with the
        eos token so the result stays rectangular.
        """
        tokens = np.asarray(batch["tokens"])
        for b in range(tokens.shape[0]):
            extras = {
                k: np.asarray(v)[b : b + 1]
                for k, v in batch.items() if k != "tokens"
            }
            self.submit(tokens[b], max_new_tokens, extras=extras or None)
        reqs = self.run()
        # always exactly max_new columns so downstream indexing never
        # changes shape between batches (Server can return fewer only when
        # every slot eos-stops early)
        pad = self.ec.eos_id if self.ec.eos_id is not None else 0
        out = np.full((len(reqs), max_new_tokens), pad, np.int32)
        for i, r in enumerate(reqs):
            toks = r.out_tokens[:max_new_tokens]
            out[i, : len(toks)] = toks
        return out


def run_static_waves(
    server: Server, requests: Sequence[dict], max_seqs: int
) -> Dict[int, np.ndarray]:
    """Drive the static-wave :class:`Server` over a multi-request workload.

    The pre-paging serving story: requests are grouped in arrival order into
    waves of ``max_seqs``; each wave prefills together and decodes in
    lockstep for the wave's **longest** generation length (finished slots
    burn decode steps on padding), and the next wave waits for the drain.
    Used as the baseline in ``benchmarks/serve_throughput.py``.

    Requests must share one prompt length (the static engine has no ragged
    batching).  Returns {rid: generated tokens, trimmed to the request's own
    ``max_new_tokens``}.
    """
    order = sorted(requests, key=lambda r: (r["arrival_step"], r["rid"]))
    lens = {len(r["prompt"]) for r in order}
    if len(lens) > 1:
        raise ValueError(f"static waves need one prompt length, got {sorted(lens)}")
    outs: Dict[int, np.ndarray] = {}
    for w in range(0, len(order), max_seqs):
        wave = order[w : w + max_seqs]
        toks = jnp.asarray(np.stack([r["prompt"] for r in wave]))
        batch = frontend_extras(
            server.cfg, {"tokens": toks}, toks.shape[0], toks.shape[1]
        )
        out = server.generate(batch, max(r["max_new_tokens"] for r in wave))
        for r, row in zip(wave, out):
            outs[r["rid"]] = np.asarray(row[: r["max_new_tokens"]], np.int32)
    return outs


def make_requests(
    vocab_size: int,
    num_requests: int,
    *,
    prompt_len: int = 16,
    max_new: int = 32,
    mean_interarrival: float = 0.0,
    vary_lengths: bool = True,
    seed: int = 0,
) -> List[dict]:
    """Deterministic 'Poisson-ish' smoke workload: exponential inter-arrival
    gaps (in decode-step units) and per-request generation lengths, all from
    one seeded generator.  Returns plain dicts so both engines can consume."""
    rng = np.random.default_rng(seed)
    reqs, step = [], 0
    for i in range(num_requests):
        if i and mean_interarrival > 0:
            step += int(rng.exponential(mean_interarrival))
        # generation lengths spread over [2, max_new]: realistic serving
        # traffic is length-heterogeneous, which is precisely what lockstep
        # waves pay for and slot re-fill does not
        n_new = (
            int(rng.integers(2, max_new + 1)) if vary_lengths else max_new
        )
        reqs.append({
            "rid": i,
            "prompt": rng.integers(0, vocab_size, size=(prompt_len,)).astype(np.int32),
            "max_new_tokens": n_new,
            "arrival_step": step,
        })
    return reqs
