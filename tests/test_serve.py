"""Serving subsystem tests: paged KV cache units + continuous-batching parity.

The headline guarantee: continuous batching is a *scheduling* change, not a
*numerics* change — every request's greedy output is bit-identical to the
single-request static-wave baseline, across the dense/GQA (paged), SWA
(ring) and SSM (state) cache families, including slot re-fill and
preemption-with-recompute.
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model as M
from repro.serve import (
    Engine,
    EngineConfig,
    PageAllocator,
    PagedCacheConfig,
    PagedKVCache,
    ServeConfig,
    Server,
    bucket_tokens,
    make_requests,
)
from repro.serve.engine import sample_step


# --------------------------------------------------------------------------
# Page allocator / cache manager units
# --------------------------------------------------------------------------

def test_page_allocator_refcount_cycle():
    a = PageAllocator(8)  # 7 usable pages (page 0 reserved)
    assert a.num_free == 7
    got = a.alloc(3)
    assert len(got) == 3 and a.num_free == 4
    assert 0 not in got  # null page never handed out
    assert all(a.refcount(p) == 1 for p in got)
    assert a.alloc(5) is None  # short pool: no partial allocation
    assert a.num_free == 4  # failed alloc left the pool untouched
    # aliasing: a second reference keeps the page out of the free list
    a.ref(got[:1])
    assert a.refcount(got[0]) == 2
    assert a.unref(got) == got[1:]  # first page survives its extra ref
    assert a.num_free == 6
    assert a.unref(got[:1]) == got[:1]  # last reference drops -> freed
    assert a.num_free == 7
    with pytest.raises(ValueError):
        a.unref([0])  # null page is never tracked
    with pytest.raises(ValueError):
        a.ref([got[0]])  # cannot alias a free page
    got2 = a.alloc(1)
    a.unref(got2)
    with pytest.raises(ValueError):
        a.unref(got2)  # double free


def _paged_cfg(**over):
    cfg = C.get_config("minicpm-2b", smoke=True, dtype=jnp.float32)
    return dataclasses.replace(cfg, **over)


def _idle_pages(kv):
    """Pages owned by no request: the free list plus the prefix cache.

    A drained engine must account for every page — with sharing on,
    finished prompts deliberately leave their full pages pinned in the
    prefix index (one index-owned reference each), so 'no leaks' means
    free + index-pinned == total and no slot holds references.  The full
    refcount cross-check lives in the shared auditor
    (:meth:`PagedKVCache.audit`); this helper only adds the drained-engine
    requirement that no slot holds pages."""
    stats = kv.audit()
    assert stats.slot_held == 0 and not kv._pages, (
        f"slots still hold pages: {kv._pages}"
    )
    return stats.free + stats.index_pinned


def test_kvcache_page_size_derived_from_kernel_block():
    cfg = _paged_cfg(block=16)
    kv = PagedKVCache(cfg, PagedCacheConfig(max_seqs=2, max_len=32))
    assert kv.page_size == cfg.block == 16
    # explicit override still honored
    kv2 = PagedKVCache(cfg, PagedCacheConfig(max_seqs=2, max_len=32, page_size=8))
    assert kv2.page_size == 8 and kv2.max_pages_per_seq == 4


def test_kvcache_admission_accounting():
    cfg = _paged_cfg(block=4)
    # pool of 5 usable pages, 4-token pages
    kv = PagedKVCache(cfg, PagedCacheConfig(max_seqs=2, max_len=16, num_pages=6))
    assert kv.pages_for(1) == 1 and kv.pages_for(4) == 1 and kv.pages_for(5) == 2
    assert kv.can_admit(10)  # needs ceil(11/4) = 3 <= 5
    assert kv.admit(0, 10) is not None
    assert kv.num_free_pages == 2
    assert not kv.can_admit(10)  # 3 > 2 remaining
    assert kv.admit(1, 10) is None  # OOM admission refused, pool untouched
    assert kv.num_free_pages == 2
    # growth: slot 0 already maps positions 0..11; position 12 needs page 4
    assert kv.ensure_capacity(0, 11)
    assert kv.num_free_pages == 2  # no-op, already mapped
    assert kv.ensure_capacity(0, 12)
    assert kv.num_free_pages == 1
    kv.release(0)
    assert kv.num_free_pages == 5
    # page table row reset to the null page
    assert int(np.asarray(kv.page_table()).max()) == 0


def test_page_table_upload_is_a_snapshot():
    """A device page table keeps the rows it was uploaded with: the host's
    later in-place edits (growth, release) must not reach a step already
    dispatched with it.  The CPU backend aliases only some (aligned) host
    arrays, so the check repeats the cycle until aliasing would show."""
    kv = PagedKVCache(_paged_cfg(block=4),
                      PagedCacheConfig(max_seqs=2, max_len=16, num_pages=6))
    for _ in range(20):
        kv.admit(0, 10)
        dev = kv.page_table()
        before = np.asarray(dev).copy()
        assert kv.ensure_capacity(0, 12)
        kv.release(0)
        np.testing.assert_array_equal(np.asarray(dev), before)


def test_kvcache_rejects_unservable_request():
    cfg = _paged_cfg(block=4)
    kv = PagedKVCache(cfg, PagedCacheConfig(max_seqs=1, max_len=8, num_pages=3))
    assert kv.fits(8) and not kv.fits(9)  # max_len bound
    eng_cfg = C.get_config("minicpm-2b", smoke=True, dtype=jnp.float32)
    params = M.init_params(eng_cfg, jax.random.PRNGKey(0))
    eng = Engine(eng_cfg, params, EngineConfig(max_seqs=1, max_len=8, page_size=4))
    with pytest.raises(ValueError):
        eng.submit(np.zeros(6, np.int32), 8)  # 14 tokens can never fit


def test_engine_rejects_unsupported_family():
    """Only the vision frontend is left outside the adapter registry, and
    the refusal must list exactly the families the registry reports."""
    from repro.models import adapters as A

    cfg = C.get_config("qwen2-vl-72b", smoke=True, dtype=jnp.float32)
    with pytest.raises(NotImplementedError) as ei:
        PagedKVCache(cfg, PagedCacheConfig())
    for family in A.supported_families():
        assert family in str(ei.value)


def test_adapter_registry_covers_all_other_archs():
    """Every arch except the vision frontend resolves to adapters."""
    from repro.models import adapters as A

    for arch in C.arch_ids():
        cfg = C.get_config(arch, smoke=True, dtype=jnp.float32)
        reason = A.unsupported_reason(cfg)
        if arch == "qwen2-vl-72b":
            assert reason is not None
        else:
            assert reason is None, (arch, reason)
            assert A.all_adapters(cfg)  # at least one family adapter


def test_adapter_chunk_grid():
    """SSM segments force prefill chunks onto the SSD chunk grid."""
    from repro.models import adapters as A

    assert A.prefill_chunk_multiple(
        C.get_config("minicpm-2b", smoke=True)) == 1
    mamba = C.get_config("mamba2-130m", smoke=True)
    assert A.prefill_chunk_multiple(mamba) == mamba.ssm_chunk


# --------------------------------------------------------------------------
# Continuous batching == single-request greedy baseline (bit-identical)
# --------------------------------------------------------------------------

def _single_request_baseline(cfg, params, prompts, max_new):
    srv = Server(cfg, params, ServeConfig(max_len=64))
    return [
        srv.generate({"tokens": jnp.asarray(p)[None]}, max_new)[0]
        for p in prompts
    ]


@pytest.mark.parametrize("arch", [
    "minicpm-2b",        # dense MHA -> block-paged cache
    "h2o-danube-3-4b",   # SWA + GQA -> per-slot ring buffer
    "mamba2-130m",       # SSM       -> per-slot O(1) state
    pytest.param("hymba-1.5b", marks=pytest.mark.slow),  # hybrid ring+state
])
def test_continuous_batching_matches_single_request(arch):
    """3 requests through 2 slots (forcing a slot re-fill): every request's
    greedy tokens must equal its single-request generate() exactly."""
    cfg = C.get_config(arch, smoke=True, dtype=jnp.float32)
    cfg = dataclasses.replace(cfg, block=8)  # page = kernel block = 8 tokens
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in (12, 9, 14)
    ]
    max_new = 8
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(max_seqs=2, max_len=32, page_size=8))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival_step=2 * i)  # staggered
    reqs = eng.run()
    assert len(reqs) == 3 and all(r.state == "finished" for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    # the third request re-filled a slot vacated by an earlier one
    assert _idle_pages(eng.kv) == eng.kv.allocator.num_pages - 1


def test_preemption_recompute_preserves_outputs():
    """A pool too small for all growth preempts LIFO; the preempted request
    re-prefills (prompt + generated) and still matches the baseline."""
    cfg = _paged_cfg(block=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(10,)).astype(np.int32)
               for _ in range(3)]
    max_new = 10
    base = _single_request_baseline(cfg, params, prompts, max_new)
    # 3 requests x 5 pages full-length = 15 > 8-page pool -> forced preemption
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=3, max_len=20, page_size=4, num_pages=9,
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i)
    reqs = eng.run()
    assert sum(r.stats.n_preemptions for r in reqs) >= 1
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    assert _idle_pages(eng.kv) == 8  # every page accounted for


def test_oom_admission_queues_until_pages_free():
    cfg = _paged_cfg(block=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32)
               for _ in range(2)]
    # pool admits exactly one request at a time (3 usable pages)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=12, page_size=4, num_pages=4,
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, 4, rid=i)
    reqs = eng.run()
    assert all(len(r.out_tokens) == 4 for r in reqs)
    assert reqs[0].stats.queue_steps == 0
    assert reqs[1].stats.queue_steps > 0  # blocked on the page budget
    assert reqs[1].stats.admitted_step > reqs[0].stats.finish_step - 1


def test_eos_early_stop_matches_baseline_prefix():
    """The eos path disables the deferred sync (token values drive finish):
    each request must stop exactly where the single-request baseline first
    emits the eos token, keeping the prefix bit-identical."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=(11,)).astype(np.int32)
               for _ in range(3)]
    max_new = 10
    base = _single_request_baseline(cfg, params, prompts, max_new)
    # choose an eos that actually appears mid-stream in some baseline output
    eos = int(base[0][max_new // 2])
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=32, page_size=8, eos_id=eos,
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i)
    reqs = eng.run()
    hit_early = 0
    for r, b in zip(reqs, base):
        b = np.asarray(b)
        idx = np.flatnonzero(b == eos)
        expect = b[: idx[0] + 1] if idx.size else b
        np.testing.assert_array_equal(np.asarray(r.out_tokens), expect)
        hit_early += len(expect) < max_new
    assert hit_early >= 1  # the chosen eos truncated at least one request


# eos 70 is request 0's third draw under seed 11: with it set, a request
# stops early and the step's tokens come over each step; without it they
# stay deferred, so a draw's position counts tokens not yet on the host
@pytest.mark.parametrize("eos_id", [None, 70], ids=["deferred", "eos"])
def test_temperature_sampling_schedule_independent(eos_id):
    """Per-request fold_in(seed, rid, position) keys: sampled outputs must
    not depend on slot count / scheduling interleave."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=(9,)).astype(np.int32)
               for _ in range(3)]

    def sample_with(max_seqs, eos=eos_id):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=max_seqs, max_len=24, page_size=8,
            temperature=0.8, seed=11, eos_id=eos,
        ))
        for i, p in enumerate(prompts):
            eng.submit(p, 6, rid=i)
        return {r.rid: list(r.out_tokens) for r in eng.run()}

    serial = sample_with(1)  # fully sequential scheduling
    batched = sample_with(3)  # all three interleaved
    assert serial == batched
    # and distinct requests don't share a key stream
    assert len({tuple(v) for v in serial.values()}) > 1
    if eos_id is not None:
        assert serial[0][-1] == eos_id and len(serial[0]) < 6
    # an eos no token equals brings every step's tokens to the host at once:
    # deferred or not, each request draws the same stream
    stepwise = sample_with(3, eos=-1)
    assert all(stepwise[r][:len(v)] == v for r, v in serial.items())


def test_batch_sampler_draws_each_rows_token():
    """The device sampler's batch is, bit for bit, each row's own
    ``categorical(fold_in(fold_in(PRNGKey(seed), rid), k), row / T)``:
    idle rows with dummy keys, distinct positions, pad columns masked as
    the model masks them."""
    seed, T, V, Vp = 11, 0.7, 500, 512
    rows = jax.random.normal(jax.random.PRNGKey(3), (5, Vp)) * 3
    rows = rows.at[:, V:].set(jnp.finfo(jnp.float32).min)
    rids = np.array([7, 0, 7, 123456, 0], np.uint32)  # rows 1, 4 idle
    ks = np.array([0, 0, 5, 2, 0], np.uint32)
    got = jax.jit(sample_step)(jax.random.PRNGKey(seed), rows[:, None],
                               rids, ks, jnp.float32(T))
    assert got.dtype == jnp.int32 and got.shape == (5,)
    for i in range(5):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), int(rids[i])),
            int(ks[i]))
        assert int(got[i]) == int(jax.random.categorical(key, rows[i] / T)) < V


def test_continuous_batching_step_efficiency():
    """Deterministic slot-step accounting: on a staggered, length-varied
    workload the continuous engine does no more decode slot-steps than the
    static wave (usually strictly fewer) for the same useful tokens."""
    cfg = C.get_config("minicpm-2b", smoke=True, dtype=jnp.float32)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    max_seqs, max_new = 2, 12
    reqs = make_requests(cfg.vocab_size, 6, prompt_len=10, max_new=max_new,
                         mean_interarrival=3.0, seed=0)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=max_seqs, max_len=10 + max_new + 1, page_size=8,
    ))
    for r in reqs:
        eng.submit(r["prompt"], r["max_new_tokens"],
                   rid=r["rid"], arrival_step=r["arrival_step"])
    eng.run()
    continuous_slot_steps = eng.decode_steps * max_seqs
    order = sorted(reqs, key=lambda r: (r["arrival_step"], r["rid"]))
    static_slot_steps = 0
    for w in range(0, len(order), max_seqs):
        wave = order[w : w + max_seqs]
        static_slot_steps += len(wave) * max(r["max_new_tokens"] for r in wave)
    assert continuous_slot_steps <= static_slot_steps


def test_engine_reuse_and_duplicate_rids():
    """A reused engine reports only the current batch, keeps shape
    (B, max_new), and rejects duplicate request ids.

    Token values are deliberately not compared across engine instances
    here: threaded XLA CPU matmuls are not call-to-call bitwise stable, and
    this workload's random-params logits can sit on argmax near-ties — the
    numerics parity gates live in the tests above, whose workloads are
    tie-free.
    """
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(max_seqs=2, max_len=24, page_size=8))
    rng = np.random.default_rng(5)
    b1 = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    b2 = rng.integers(0, cfg.vocab_size, size=(3, 8)).astype(np.int32)
    out1 = eng.generate({"tokens": b1}, 5)
    out2 = eng.generate({"tokens": b2}, 5)
    # only the current batch is reported, at the full (B, max_new) width
    assert out1.shape == (2, 5) and out2.shape == (3, 5)
    assert sorted(eng.sched.finished) == [0, 1, 2, 3, 4]
    # every page returned after both batches (reuse leaks nothing)
    assert _idle_pages(eng.kv) == eng.kv.allocator.num_pages - 1
    with pytest.raises(ValueError):
        eng.submit(b1[0], 4, rid=0)  # rid 0 already finished


# --------------------------------------------------------------------------
# Chunked + donating prefill
# --------------------------------------------------------------------------

def test_bucket_tokens():
    assert bucket_tokens(1, 8) == 8
    assert bucket_tokens(8, 8) == 8
    assert bucket_tokens(9, 8) == 16
    assert bucket_tokens(17, 8) == 32  # 3 pages -> 4
    assert bucket_tokens(33, 8) == 64


@pytest.mark.parametrize("arch", [
    "minicpm-2b",        # dense MHA -> paged chunk scatter + gather attention
    "h2o-danube-3-4b",   # SWA       -> ring rows carried across chunks
    "mamba2-130m",       # SSM       -> state carried on the ssm_chunk grid
    pytest.param("hymba-1.5b", marks=pytest.mark.slow),  # hybrid ring+state
])
def test_chunked_prefill_matches_unchunked(arch):
    """Chunked prefill is a *data-movement* change, not a numerics change:
    multi-chunk prompts must produce greedy outputs bit-identical to both
    the unchunked engine and single-request generate()."""
    cfg = C.get_config(arch, smoke=True, dtype=jnp.float32)
    cfg = dataclasses.replace(cfg, block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    # lengths straddle chunk boundaries: < 1 chunk, exact multiple, ragged
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (5, 16, 19, 27)]
    max_new = 6
    base = _single_request_baseline(cfg, params, prompts, max_new)

    def run_engine(chunked):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=2, max_len=40, page_size=8, chunked_prefill=chunked,
        ))
        for i, p in enumerate(prompts):
            eng.submit(p, max_new, rid=i, arrival_step=i)
        return eng, {r.rid: list(r.out_tokens) for r in eng.run()}

    eng_c, chunked = run_engine(True)
    _, unchunked = run_engine(False)
    assert eng_c.chunk_size >= 8
    for i, b in enumerate(base):
        assert chunked[i] == list(np.asarray(b)), f"chunked != baseline (rid {i})"
        assert unchunked[i] == list(np.asarray(b)), f"unchunked != baseline (rid {i})"


def test_mid_prefill_preemption_and_resume():
    """A request preempted in the middle of its chunked prefill must restart
    cleanly on re-admission (recompute discipline) and still match the
    baseline bit for bit."""
    cfg = _paged_cfg(block=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    short = rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    long = rng.integers(0, cfg.vocab_size, size=(16,)).astype(np.int32)
    max_new = 8
    base = _single_request_baseline(cfg, params, [short, long], max_new)
    # pool: short needs 3 pages at admit and grows to 4; long needs 5.
    # 8 usable pages admit both, then short's growth preempts long (LIFO)
    # while long is still several chunks from its first token.
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=24, page_size=4, num_pages=9,
        prefill_tokens_per_step=4,
    ))
    a = eng.submit(short, max_new, rid=0)
    b = eng.submit(long, max_new, rid=1)
    saw_mid_prefill = False
    was_preempted_mid_prefill = False
    for _ in range(200):
        if not eng.sched.has_work():
            break
        prefilling_before = b.prefilling and 0 < b.prefill_pos
        eng.step()
        saw_mid_prefill |= prefilling_before
        if prefilling_before and b.state == "waiting":
            was_preempted_mid_prefill = True
    eng._flush_pending()
    assert saw_mid_prefill, "long prompt never observed mid-prefill"
    assert was_preempted_mid_prefill, "no preemption landed mid-prefill"
    assert b.stats.n_preemptions >= 1 and b.prefill_pos == b.prefill_target
    np.testing.assert_array_equal(np.asarray(a.out_tokens), base[0])
    np.testing.assert_array_equal(np.asarray(b.out_tokens), base[1])
    assert _idle_pages(eng.kv) == 8


def test_long_prompt_admission_does_not_stall_decode():
    """The point of chunked admission: while a max-length prompt works
    through its chunks, the in-flight request keeps emitting tokens every
    engine step (deterministic step accounting, no wall clock)."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    short = rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    long = rng.integers(0, cfg.vocab_size, size=(64,)).astype(np.int32)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=80, page_size=8, prefill_tokens_per_step=8,
    ))
    a = eng.submit(short, 24, rid=0, arrival_step=0)
    b = eng.submit(long, 4, rid=1, arrival_step=2)
    tokens_during_prefill = []
    for _ in range(200):
        if not eng.sched.has_work():
            break
        mid = b.prefilling
        before = a.n_generated
        eng.step()
        if mid:
            tokens_during_prefill.append(a.n_generated - before)
    eng._flush_pending()
    n_chunks = math.ceil(len(long) / eng.chunk_size)
    assert n_chunks >= 8
    # the long admission spans n_chunks engine steps...
    assert (b.stats.first_token_step - b.stats.admitted_step) >= n_chunks - 1
    # ...and the short request decoded one token in EVERY one of them
    assert len(tokens_during_prefill) >= n_chunks - 1
    assert all(n == 1 for n in tokens_during_prefill)
    # sanity: outputs still match the single-request baseline
    srv = Server(cfg, params, ServeConfig(max_len=96))
    for req, n_new in ((a, 24), (b, 4)):
        base = srv.generate(
            {"tokens": jnp.asarray(req.prompt)[None]}, n_new
        )[0]
        np.testing.assert_array_equal(np.asarray(req.out_tokens), base)


def test_server_bucketed_prefill_exact():
    """Power-of-two prompt bucketing (dense/GQA) is bit-exact: padded keys
    are masked during prefill and overwritten by decode before their
    position label becomes reachable."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    for n in (3, 8, 11, 17, 25):
        prompt = rng.integers(0, cfg.vocab_size, size=(1, n)).astype(np.int32)
        exact = Server(cfg, params, ServeConfig(max_len=64, prefill_bucket=-1))
        bucketed = Server(cfg, params, ServeConfig(max_len=64))
        out_e = exact.generate({"tokens": jnp.asarray(prompt)}, 8)
        out_b = bucketed.generate({"tokens": jnp.asarray(prompt)}, 8)
        np.testing.assert_array_equal(out_e, out_b, err_msg=f"prompt_len={n}")


def test_prefill_jit_cache_bounded():
    """Chunked prefill must not compile per prompt length: many distinct
    lengths share one full-chunk shape + a few final-chunk shapes."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(max_seqs=2, max_len=80, page_size=8))
    # fresh jit instance: the memoized one is shared across engines with
    # different geometries, which would pollute the entry count
    eng._chunk_fn = jax.jit(
        functools.partial(M.prefill_chunk, cfg), donate_argnums=(1,)
    )
    rng = np.random.default_rng(9)
    for i, n in enumerate(range(1, 41, 2)):  # 20 distinct prompt lengths
        eng.submit(rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32),
                   2, rid=i)
    eng.run()
    # dense/GQA final chunks bucket to powers of two <= chunk size, so 20
    # lengths share at most {full chunk} + {1, 2, 4, 8} jit entries
    assert eng._chunk_fn._cache_size() <= 1 + int(math.log2(eng.chunk_size)) + 1


def test_admission_zero_pool_copy():
    """Admission must never copy the pool: the chunk step's donated cache
    pytree is updated in place (the output aliases the input buffers), and
    the compiled step allocates no pool-sized scratch."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # oversized pool: at production scale the pool dwarfs every activation,
    # so "no pool-sized allocation" must mean scratch stays O(activations)
    # while the pool grows — 256 usable pages makes that separation visible
    # even at smoke scale
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=64, page_size=8, num_pages=257,
    ))
    rng = np.random.default_rng(3)
    req = eng.submit(rng.integers(0, cfg.vocab_size, size=(20,)).astype(np.int32), 4)
    eng.sched.poll_arrivals(0)
    [(slot, _)] = eng.sched.admit(0)
    pool_before = eng.kv.data["seg0"]["attn"]["k_pages"]
    ptr_before = pool_before.unsafe_buffer_pointer()
    eng._prefill_one_chunk(slot, req)
    pool_after = eng.kv.data["seg0"]["attn"]["k_pages"]
    # donation aliased the pool: same device buffer, no copy
    assert pool_after.unsafe_buffer_pointer() == ptr_before
    with pytest.raises(RuntimeError):
        pool_before.block_until_ready()  # old reference was consumed

    # compiled-memory regression: the donated caches alias the output in
    # full (zero *persistent* pool-sized allocation per admission — the old
    # eager path allocated a fresh pool copy per layer per admission), and
    # the chunk step's scratch is no worse than the long-accepted decode
    # step's (XLA:CPU stages the scanned pool in temp for both; that is a
    # backend scan artifact, not an admission copy)
    from repro.serve.engine import _paged_step
    pool_bytes = eng.kv.cache_bytes()
    toks = jnp.zeros((1, eng.chunk_size), jnp.int32)
    phys, off = eng.kv.token_targets(slot, 0, eng.chunk_size)
    ma = jax.jit(
        functools.partial(M.prefill_chunk, cfg), donate_argnums=(1,)
    ).lower(params, eng.kv.data, toks, jnp.int32(slot), jnp.int32(0),
            phys, off, eng.kv.table_row(slot), jnp.int32(eng.chunk_size - 1)
            ).compile().memory_analysis()
    ma_dec = jax.jit(
        functools.partial(_paged_step, cfg), donate_argnums=(1,)
    ).lower(params, eng.kv.data, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), eng.kv.page_table(),
            jnp.ones((2,), bool)).compile().memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < pool_bytes / 8
    assert ma.temp_size_in_bytes <= 1.25 * ma_dec.temp_size_in_bytes

    # the unchunked install path donates the same way
    eng2 = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=64, page_size=8, chunked_prefill=False,
    ))
    req2 = eng2.submit(rng.integers(0, cfg.vocab_size, size=(12,)).astype(np.int32), 2)
    eng2.sched.poll_arrivals(0)
    [(slot2, _)] = eng2.sched.admit(0)
    ptr2 = eng2.kv.data["seg0"]["attn"]["k_pages"].unsafe_buffer_pointer()
    eng2._prefill_full(slot2, req2)
    assert eng2.kv.data["seg0"]["attn"]["k_pages"].unsafe_buffer_pointer() == ptr2


# --------------------------------------------------------------------------
# MLA latent pages (CacheAdapter: LatentMLAAdapter)
# --------------------------------------------------------------------------

def _mla_dense_cfg(**over):
    """DeepSeek-shaped MLA attention over a dense FFN stack: isolates the
    latent-page adapter from the MoE capacity dispatch (whose drop pattern
    is sequence-length dependent, so *multi-chunk* MoE prefill is not
    bit-reproducible against one-shot — see the deepseek test below)."""
    cfg = C.get_config("deepseek-v3-671b", smoke=True, dtype=jnp.float32)
    over = {"block": 8, **over}
    return dataclasses.replace(
        cfg, family="dense", n_experts=0, n_shared_experts=0, top_k=0,
        moe_d_ff=0, first_k_dense=0, mtp_depth=0, d_ff=96, **over,
    )


@pytest.mark.parametrize("chunked", [True, False])
def test_mla_latent_pages_match_single_request(chunked):
    """MLA through the paged engine: latent (c_kv + k_rope) pages, absorbed-
    matmul decode — greedy outputs bit-identical to single-request
    generate(), including multi-chunk prompts and a slot re-fill."""
    cfg = _mla_dense_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (12, 9, 14)]
    max_new = 8
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=32, page_size=8, chunked_prefill=chunked,
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival_step=2 * i)
    reqs = eng.run()
    assert len(reqs) == 3 and all(r.state == "finished" for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    assert _idle_pages(eng.kv) == eng.kv.allocator.num_pages - 1
    # the latent pool really is the latent: rank + rope dims, not K/V heads
    pool = eng.kv.data["seg0"]["attn"]
    assert set(pool) == {"ckv_pages", "krope_pages"}
    assert pool["ckv_pages"].shape[-1] == cfg.kv_lora_rank


def test_mla_preemption_recompute_preserves_outputs():
    """LIFO preemption + re-prefill over latent pages stays bit-identical."""
    cfg = _mla_dense_cfg(block=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=(10,)).astype(np.int32)
               for _ in range(3)]
    max_new = 10
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=3, max_len=20, page_size=4, num_pages=9,
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i)
    reqs = eng.run()
    assert sum(r.stats.n_preemptions for r in reqs) >= 1
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    assert _idle_pages(eng.kv) == 8


def test_deepseek_v3_engine_parity_single_chunk():
    """The full DeepSeek-V3 shape (MLA + MoE + MTP) through the engine.

    Prompts fit one prefill chunk: the MoE capacity dispatch then sees the
    exact one-shot token group and outputs are bit-identical (multi-chunk
    MoE prefill changes the dispatch grouping — a property of capacity
    dispatch, not of the latent-page adapter; use chunked_prefill=False
    for bitwise multi-chunk MoE serving)."""
    cfg = dataclasses.replace(
        C.get_config("deepseek-v3-671b", smoke=True, dtype=jnp.float32),
        block=8,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (8, 7, 6)]
    max_new = 6
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(max_seqs=2, max_len=32, page_size=8))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival_step=i)
    reqs = eng.run()
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)


# --------------------------------------------------------------------------
# Encoder-decoder (CacheAdapter: CrossAttnAdapter + paged self-attention)
# --------------------------------------------------------------------------

def _encdec_setup(seed=2, n_prompts=3):
    cfg = dataclasses.replace(
        C.get_config("whisper-tiny", smoke=True, dtype=jnp.float32), block=8
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (12, 9, 14)[:n_prompts]]
    embeds = [rng.normal(size=(1, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
              for _ in prompts]
    return cfg, params, prompts, embeds


def _encdec_baseline(cfg, params, prompts, embeds, max_new):
    srv = Server(cfg, params, ServeConfig(max_len=60))
    return [
        srv.generate(
            {"tokens": jnp.asarray(p)[None], "audio_embeds": jnp.asarray(e)},
            max_new,
        )[0]
        for p, e in zip(prompts, embeds)
    ]


@pytest.mark.parametrize("chunked", [True, False])
def test_encdec_engine_matches_single_request(chunked):
    """Whisper through the paged engine: per-request encoder contexts in
    immutable cross rows, decoder self-attention paged — bit-identical to
    the single-request baseline, including a slot re-fill."""
    cfg, params, prompts, embeds = _encdec_setup()
    max_new = 8
    base = _encdec_baseline(cfg, params, prompts, embeds, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=32, page_size=8, chunked_prefill=chunked,
    ))
    for i, (p, e) in enumerate(zip(prompts, embeds)):
        eng.submit(p, max_new, rid=i, arrival_step=i,
                   extras={"audio_embeds": e})
    reqs = eng.run()
    assert len(reqs) == 3 and all(r.state == "finished" for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    assert _idle_pages(eng.kv) == eng.kv.allocator.num_pages - 1


def test_encdec_mid_prefill_preemption_and_resume():
    """An enc-dec request preempted mid-chunked-prefill re-runs its encoder
    on re-admission (recompute discipline: the cross rows belong to the
    slot, not the request) and still matches the baseline bit for bit."""
    cfg, params, _, _ = _encdec_setup()
    rng = np.random.default_rng(9)
    short = rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    long = rng.integers(0, cfg.vocab_size, size=(16,)).astype(np.int32)
    embeds = [rng.normal(size=(1, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
              for _ in range(2)]
    max_new = 8
    base = _encdec_baseline(cfg, params, [short, long], embeds, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=24, page_size=4, num_pages=9,
        prefill_tokens_per_step=4,
    ))
    a = eng.submit(short, max_new, rid=0, extras={"audio_embeds": embeds[0]})
    b = eng.submit(long, max_new, rid=1, extras={"audio_embeds": embeds[1]})
    was_preempted_mid_prefill = False
    for _ in range(200):
        if not eng.sched.has_work():
            break
        mid = b.prefilling and 0 < b.prefill_pos
        eng.step()
        if mid and b.state == "waiting":
            was_preempted_mid_prefill = True
    eng._flush_pending()
    assert was_preempted_mid_prefill, "no preemption landed mid-prefill"
    assert b.stats.n_preemptions >= 1
    np.testing.assert_array_equal(np.asarray(a.out_tokens), base[0])
    np.testing.assert_array_equal(np.asarray(b.out_tokens), base[1])
    assert _idle_pages(eng.kv) == 8


# --------------------------------------------------------------------------
# Token-level admission budget
# --------------------------------------------------------------------------

def test_prefill_token_budget_paces_admission():
    """prefill_tokens_per_step bounds the prompt tokens admitted per engine
    step, spent in whole chunks; 0 derives 4 chunks."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    long = rng.integers(0, cfg.vocab_size, size=(32,)).astype(np.int32)

    def admit_span(**knobs):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=1, max_len=48, page_size=8, **knobs,
        ))
        req = eng.submit(long, 2, rid=0)
        eng.run()
        return eng, req.stats.first_token_step - req.stats.admitted_step

    # 32-token prompt = 4 page-sized chunks
    eng, span = admit_span(prefill_tokens_per_step=8)
    assert eng.tokens_per_step == 8 and span == 3  # one chunk per step
    eng, span = admit_span(prefill_tokens_per_step=16)
    assert eng.tokens_per_step == 16 and span == 1  # two chunks per step
    # a budget between chunk multiples rounds up to whole chunks
    eng, span = admit_span(prefill_tokens_per_step=12)
    assert eng.tokens_per_step == 12 and span == 1  # two chunks per step
    eng, span = admit_span()  # defaults: 4 chunks x 8 tokens
    assert eng.tokens_per_step == 32 and span == 0


# --------------------------------------------------------------------------
# Shared-prefix paged KV: refcounted pages, radix prefix index, COW
# --------------------------------------------------------------------------

def test_prefix_index_radix_unit():
    """PrefixIndex unit: page-aligned lookup, full-tail partial match,
    insert dedup, leaf-first LRU eviction, reclaimable accounting."""
    from repro.serve import PrefixIndex

    a = PageAllocator(10)
    idx = PrefixIndex(2, a)  # 2-token pages
    pages = a.alloc(3)
    toks = np.array([1, 2, 3, 4, 5], np.int32)
    idx.insert(toks, pages, 4)  # two full pages; token 5 is a partial tail
    assert idx.num_pages == 2
    assert a.refcount(pages[0]) == 2 and a.refcount(pages[1]) == 2
    assert a.refcount(pages[2]) == 1  # partial page never enters the index
    # full-page walk
    assert idx.lookup(np.array([1, 2, 3, 4])) == ([pages[0], pages[1]], 4)
    # the tail [3] matches the first token of the cached (3, 4) page: the
    # partially-consumed page is aliased too and the match covers the
    # whole prompt (the COW-on-divergence setup)
    assert idx.lookup(np.array([1, 2, 3])) == ([pages[0], pages[1]], 3)
    # a mid-prompt mismatch stops the walk at the page boundary
    assert idx.lookup(np.array([1, 2, 9, 4])) == ([pages[0]], 2)
    assert idx.lookup(np.array([9, 9])) == ([], 0)
    # re-insert dedups: the first publisher's pages win
    dup = a.alloc(2)
    idx.insert(toks, dup, 4)
    assert idx.num_pages == 2 and a.refcount(dup[0]) == 1
    a.unref(dup)

    # second branch, inserted after the (3, 4) leaf's last touch; then
    # touch the (1, 2) root so recency orders (3,4) < (7,8) < (1,2)
    br = a.alloc(1)
    idx.insert(np.array([7, 8]), br, 2)
    idx.lookup(np.array([1, 2]))
    a.unref(pages)  # drop the slot's references; the index keeps its own
    a.unref(br)
    assert a.refcount(pages[0]) == 1
    # an excluded page (about to be aliased by an admission) is not
    # reclaimable, and shields its ancestors too
    assert idx.reclaimable_count(exclude=[pages[1]]) == 1  # only (7, 8)
    assert idx.reclaimable_count() == 3
    # LRU, leaf-first: coldest leaf (3, 4) goes first, then (7, 8); the
    # (1, 2) root page goes only once its child is gone
    assert idx.evict_lru() == pages[1]
    assert idx.evict_lru() == br[0]
    assert idx.evict_lru() == pages[0]
    assert idx.evict_lru() is None and idx.num_pages == 0
    assert a.num_free == 9


def test_audit_balances_through_admission_lifecycle():
    """The shared auditor tracks every accounting phase: cold admission
    (slot-held), publication (index-pinned while slot-held), release
    (index-pinned only), aliased re-admission, and full drain."""
    cfg = _paged_cfg(block=4)
    kv = PagedKVCache(cfg, PagedCacheConfig(max_seqs=2, max_len=16, num_pages=6))
    assert dataclasses.astuple(kv.audit()) == (5, 5, 0, 0)  # (total, free, index_pinned, slot_held)
    A = np.arange(8, dtype=np.int32)
    kv.admit(0, A)
    assert dataclasses.astuple(kv.audit()) == (5, 2, 0, 3)
    kv.commit_prefix(0, A, 8)  # 2 full pages published; pins count as index
    assert dataclasses.astuple(kv.audit()) == (5, 2, 2, 1)
    kv.release(0)
    assert dataclasses.astuple(kv.audit()) == (5, 3, 2, 0)
    kv.admit(1, A)  # aliases both cached pages + 1 fresh tail page
    assert dataclasses.astuple(kv.audit()) == (5, 2, 2, 1)
    kv.release(1)
    assert dataclasses.astuple(kv.audit()) == (5, 3, 2, 0)


def test_audit_detects_refcount_corruption():
    """The auditor must catch each way the accounting can break: a stray
    extra reference, a leaked (vanished) reference, and a free-list /
    refcount disagreement."""
    cfg = _paged_cfg(block=4)

    def fresh():
        kv = PagedKVCache(
            cfg, PagedCacheConfig(max_seqs=2, max_len=16, num_pages=6)
        )
        kv.admit(0, np.arange(8, dtype=np.int32))
        kv.commit_prefix(0, np.arange(8, dtype=np.int32), 8)
        return kv

    kv = fresh()
    kv.audit()  # sane before corruption
    kv.allocator._ref[kv._pages[0][0]] += 1  # stray reference
    with pytest.raises(AssertionError, match="refcount"):
        kv.audit()

    kv = fresh()
    kv.allocator._ref[kv._pages[0][0]] -= 1  # leaked reference
    with pytest.raises(AssertionError, match="refcount"):
        kv.audit()

    kv = fresh()
    free_page = kv.allocator._free[-1]
    kv.allocator._ref[free_page] = 1  # referenced page left on the free list
    with pytest.raises(AssertionError, match="free-list|refcount"):
        kv.audit()


def test_kvcache_admission_aliases_cached_prefix():
    """kv-level: admit -> commit -> release leaves the prefix cached; the
    next admission aliases it (refcount 2) and LRU eviction reclaims only
    when the free list is exhausted."""
    cfg = _paged_cfg(block=4)
    kv = PagedKVCache(cfg, PagedCacheConfig(max_seqs=2, max_len=16, num_pages=6))
    assert kv.sharing and kv.skip_prefill
    A = np.arange(8, dtype=np.int32)
    assert kv.admit(0, A) == 0  # cold
    pages_a = list(kv._pages[0])
    kv.commit_prefix(0, A, 8)
    assert kv.prefix_cache_pages == 2
    kv.release(0)
    assert kv.num_free_pages == 3 and kv.prefix_cache_pages == 2
    # same prompt: both full pages alias (refcount 2 = slot + index)
    assert kv.admit(1, A) == 8
    assert kv._pages[1][:2] == pages_a[:2]
    assert kv.allocator.refcount(pages_a[0]) == 2
    kv.release(1)
    # a distinct prompt needing more than the free list evicts LRU
    B = np.arange(100, 112, dtype=np.int32)
    assert kv.can_admit(B)  # 4 pages: 3 free + evictable prefix
    assert kv.admit(0, B) == 0
    assert kv.prefix_cache_pages == 1  # deepest page evicted, root kept
    kv.release(0)
    # the surviving page still serves lookups up to its boundary
    assert kv.admit(1, A) == 4
    kv.release(1)


@pytest.mark.parametrize("mla", [False, True])
def test_shared_prefix_outputs_bit_identical(mla):
    """The tentpole guarantee: prefix sharing is a *data-placement* change,
    not a numerics change.  Requests aliasing a cached prefix — including a
    partially-consumed tail page whose first decode write diverges through
    COW — produce greedy outputs bit-identical to both the non-shared
    engine and single-request generate(), for dense/GQA and MLA latent
    pages."""
    cfg = _mla_dense_cfg() if mla else _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, size=(24,)).astype(np.int32)
    pa = np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=(3,))
                         ]).astype(np.int32)
    pb = np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=(5,))
                         ]).astype(np.int32)
    pc = shared[:20].copy()  # prefix incl. a partial page -> COW divergence
    prompts = [pa, pb, pc]
    max_new = 8
    base = _single_request_baseline(cfg, params, prompts, max_new)

    def run_engine(sharing):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=2, max_len=48, page_size=8, prefix_sharing=sharing,
        ))
        for i, p in enumerate(prompts):
            eng.submit(p, max_new, rid=i, arrival_step=4 * i)
        return eng, eng.run()

    eng_s, reqs_s = run_engine(True)
    eng_u, reqs_u = run_engine(False)
    for rs, ru, b in zip(reqs_s, reqs_u, base):
        np.testing.assert_array_equal(np.asarray(rs.out_tokens), b)
        np.testing.assert_array_equal(np.asarray(ru.out_tokens), b)
    # rid 1 aliased all 3 full prefix pages; rid 2 also aliased the partial
    # tail page (its whole prompt was cached) and diverged through COW
    assert [r.stats.cached_prompt_tokens for r in reqs_s] == [0, 24, 20]
    assert eng_s.kv.cow_copies >= 1
    assert eng_s.prefill_chunks < eng_u.prefill_chunks
    assert eng_s.kv.allocator.pages_allocated < eng_u.kv.allocator.pages_allocated
    assert not eng_u.kv.sharing and eng_u.kv.cow_copies == 0
    # refcounts exact after drain: free + index-pinned covers the pool
    assert _idle_pages(eng_s.kv) == eng_s.kv.allocator.num_pages - 1


def test_shared_prefix_zero_recompute_suffix_chunks():
    """The compute-saving contract, pinned in chunk units: an admission
    whose prefix is fully cached runs EXACTLY the suffix's chunks — one
    chunk for a one-page suffix, and a single 1-token logits chunk (write
    null-routed) when the entire prompt is cached."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    A = rng.integers(0, cfg.vocab_size, size=(32,)).astype(np.int32)
    eng = Engine(cfg, params, EngineConfig(max_seqs=1, max_len=48, page_size=8))
    srv = Server(cfg, params, ServeConfig(max_len=64))

    eng.submit(A, 4, rid=0)
    eng.run()
    assert eng.prefill_chunks == 4  # cold: ceil(32 / 8)

    # suffix-only: 32 cached tokens + 8 new -> ONE chunk
    before = eng.prefill_chunks
    B = np.concatenate([A, rng.integers(0, cfg.vocab_size, size=(8,))
                        ]).astype(np.int32)
    rb = eng.submit(B, 4, rid=1)
    eng.run()
    assert eng.prefill_chunks - before == 1
    assert rb.stats.cached_prompt_tokens == 32
    np.testing.assert_array_equal(
        np.asarray(rb.out_tokens),
        srv.generate({"tokens": jnp.asarray(B)[None]}, 4)[0],
    )

    # fully cached: one 1-token chunk recomputes only the last position's
    # logits (its K/V write is null-routed — the cache already has it)
    before = eng.prefill_chunks
    rc = eng.submit(A.copy(), 4, rid=2)
    eng.run()
    assert eng.prefill_chunks - before == 1
    assert rc.stats.cached_prompt_tokens == 32
    np.testing.assert_array_equal(
        np.asarray(rc.out_tokens),
        srv.generate({"tokens": jnp.asarray(A)[None]}, 4)[0],
    )


def test_shared_prefix_mid_prefill_preemption_resumes_suffix():
    """A preempted suffix prefill resumes: pages the victim already
    published to the prefix index survive its release (one index-owned
    reference), so re-admission aliases them and chunks only what is left
    — with refcounts exact and outputs bit-identical throughout."""
    cfg = _paged_cfg(block=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    short = rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    long = rng.integers(0, cfg.vocab_size, size=(16,)).astype(np.int32)
    max_new = 8
    base = _single_request_baseline(cfg, params, [short, long], max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=24, page_size=4, num_pages=9,
        prefill_tokens_per_step=4,
    ))
    a = eng.submit(short, max_new, rid=0)
    b = eng.submit(long, max_new, rid=1)
    was_preempted_mid_prefill = False
    for _ in range(200):
        if not eng.sched.has_work():
            break
        mid = b.prefilling and 0 < b.prefill_pos
        eng.step()
        if mid and b.state == "waiting":
            was_preempted_mid_prefill = True
    eng._flush_pending()
    assert was_preempted_mid_prefill, "no preemption landed mid-prefill"
    assert b.stats.n_preemptions >= 1
    # the re-admission found the preempted prefill's published pages and
    # resumed at the first uncached page boundary instead of recomputing
    assert b.stats.cached_prompt_tokens >= 4
    assert b.stats.cached_prompt_tokens % 4 == 0
    np.testing.assert_array_equal(np.asarray(a.out_tokens), base[0])
    np.testing.assert_array_equal(np.asarray(b.out_tokens), base[1])
    assert _idle_pages(eng.kv) == 8


def test_prefix_sharing_capability_matrix():
    """Shareability is a per-family CacheAdapter capability — the registry
    refuses nothing: stateful families just fall through to the unshared
    path, and MoE stacks alias pages without skipping compute."""
    from repro.models import adapters as A

    expect = {
        "minicpm-2b": (True, True),  # dense/GQA: full sharing
        "qwen1.5-110b": (True, True),
        "starcoder2-7b": (True, True),
        "granite-moe-3b-a800m": (True, False),  # MoE: alias, recompute
        "deepseek-v3-671b": (True, False),  # MLA pages + MoE FFN
        "mamba2-130m": (False, False),  # SSM state rows are slot-local
        "h2o-danube-3-4b": (False, False),  # SWA rings are slot-local
        "hymba-1.5b": (False, False),  # hybrid ring+state
        "whisper-tiny": (False, False),  # audio side inputs taint the stack
    }
    for arch, (share, skip) in expect.items():
        cfg = C.get_config(arch, smoke=True, dtype=jnp.float32)
        assert A.prefix_shareable(cfg) == share, arch
        assert A.prefix_compute_skippable(cfg) == skip, arch
    # MLA over a dense FFN stack (the latent-page parity config) skips too
    assert A.prefix_compute_skippable(_mla_dense_cfg())
    # non-shareable families run with sharing requested but disabled —
    # today's path, no refusal
    ssm = C.get_config("mamba2-130m", smoke=True, dtype=jnp.float32)
    kv = PagedKVCache(ssm, PagedCacheConfig(max_seqs=1, max_len=16))
    assert not kv.sharing and kv.index is None


def test_moe_stack_shares_pages_but_recomputes():
    """'Mixed stacks share the paged segments and recompute the rest': a
    MoE config aliases prefix pages (memory dedup) while running every
    prefill chunk, so its outputs stay bit-identical to the non-shared
    chunked engine — sharing must not widen the documented multi-chunk
    MoE caveat."""
    cfg = dataclasses.replace(
        C.get_config("granite-moe-3b-a800m", smoke=True, dtype=jnp.float32),
        block=8,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, size=(24,)).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=(3,))
                        ]).astype(np.int32),
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=(5,))
                        ]).astype(np.int32),
        # the prefix of an already-cached longer page run, ending mid-page:
        # a recompute family must NOT alias the partial tail page (its
        # content was dispatched under the publisher's longer chunk — the
        # regroup caveat), so the match clamps to the full-page walk
        shared[:20].copy(),
    ]

    def run_engine(sharing):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=1, max_len=40, page_size=8, prefix_sharing=sharing,
        ))
        for i, p in enumerate(prompts):
            eng.submit(p, 6, rid=i)
        return eng, eng.run()

    eng_s, reqs_s = run_engine(True)
    eng_u, reqs_u = run_engine(False)
    assert eng_s.kv.sharing and not eng_s.kv.skip_prefill
    for rs, ru in zip(reqs_s, reqs_u):  # bit-identical to non-shared
        assert rs.out_tokens == ru.out_tokens, rs.rid
    assert [r.stats.cached_prompt_tokens for r in reqs_s] == [0, 24, 16]
    assert eng_s.kv.cow_copies == 0  # no partial-tail alias -> no COW
    assert eng_s.prefill_chunks == eng_u.prefill_chunks  # no compute skip
    assert eng_s.kv.allocator.pages_allocated < eng_u.kv.allocator.pages_allocated
    # one-shot prefill groups the whole prompt per request, which a
    # recompute family cannot replay bit-exactly: sharing gates itself off
    eng_o = Engine(cfg, params, EngineConfig(
        max_seqs=1, max_len=40, page_size=8, chunked_prefill=False,
    ))
    assert not eng_o.kv.sharing
    # ...but compute-skippable families keep sharing under one-shot prefill
    assert Engine(
        _paged_cfg(block=8), M.init_params(_paged_cfg(block=8),
                                           jax.random.PRNGKey(0)),
        EngineConfig(max_seqs=1, max_len=40, page_size=8,
                     chunked_prefill=False),
    ).kv.sharing


def test_moe_capacity_dispatch_regroups_across_chunks():
    """Pin the *mechanism* of the documented multi-chunk MoE prefill
    caveat, so the known limit cannot silently widen (or silently start
    applying to single-chunk prompts): capacity dispatch ranks tokens
    within their expert per forward call, so a 16-token sequence whose
    tokens all pick one hot expert drops the overflow half when run
    one-shot but keeps it when run as two 8-token chunks.  Tokens inside
    the capacity window are bit-identical either way — which is exactly
    why single-chunk prompts and ``chunked_prefill=False`` stay exact."""
    from repro.models import ffn as ffnm

    cfg = dataclasses.replace(
        C.get_config("granite-moe-3b-a800m", smoke=True, dtype=jnp.float32),
        capacity_factor=1.0,
    )
    p = ffnm.moe_init(jax.random.PRNGKey(0), cfg)
    # near-identical tokens: every token routes to the same hot expert, so
    # 16 one-shot tokens overflow Cg = 8 while each 8-token chunk fits
    base = jax.random.normal(jax.random.PRNGKey(1), (cfg.d_model,), jnp.float32)
    noise = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg.d_model),
                              jnp.float32)
    x = jnp.broadcast_to(base, (1, 16, cfg.d_model)) + 1e-2 * noise
    logits = np.asarray(x[0].astype(jnp.float32) @ p["router"])
    top1 = logits.argmax(-1)
    assert (top1 == top1[0]).all(), "setup: tokens must share a hot expert"
    full, _ = ffnm.moe_forward(p, cfg, x)
    c1, _ = ffnm.moe_forward(p, cfg, x[:, :8])
    c2, _ = ffnm.moe_forward(p, cfg, x[:, 8:])
    chunked = jnp.concatenate([c1, c2], axis=1)
    # within capacity: identical dispatch, identical bits
    np.testing.assert_array_equal(np.asarray(full[:, :8]),
                                  np.asarray(chunked[:, :8]))
    # past capacity: the one-shot run dropped these tokens' hot-expert
    # contribution, the per-chunk runs kept it — outputs must differ
    assert not np.array_equal(np.asarray(full[:, 8:]),
                              np.asarray(chunked[:, 8:]))


def test_moe_unchunked_multi_page_engine_parity():
    """The caveat's boundary from the other side: with chunking off, a
    multi-page MoE prompt through the paged engine sees the one-shot
    dispatch grouping and stays bit-identical to the baseline."""
    cfg = dataclasses.replace(
        C.get_config("granite-moe-3b-a800m", smoke=True, dtype=jnp.float32),
        block=8,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (17, 20)]
    base = _single_request_baseline(cfg, params, prompts, 6)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=32, page_size=8, chunked_prefill=False,
    ))
    for i, pr in enumerate(prompts):
        eng.submit(pr, 6, rid=i)
    for r, b in zip(eng.run(), base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)


# --------------------------------------------------------------------------
# Pallas paged-decode backend (fused kernels; interpret mode off-TPU)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("chunked", [True, False])
def test_pallas_backend_matches_single_request(chunked):
    """Dense/GQA engine under backend="pallas": the fused paged-attention
    decode + COW kernels are a *data-movement* change, not a numerics
    change — greedy tokens equal the single-request generate() baseline
    exactly, including multi-chunk prompts and a slot re-fill."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (12, 9, 14)]
    max_new = 8
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=32, page_size=8, chunked_prefill=chunked,
        backend="pallas",
    ))
    assert eng.cfg.decode_backend == "pallas"  # folded into the jit key
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival_step=2 * i)
    reqs = eng.run()
    assert len(reqs) == 3 and all(r.state == "finished" for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    assert _idle_pages(eng.kv) == eng.kv.allocator.num_pages - 1


@pytest.mark.parametrize("chunked", [True, False])
def test_pallas_backend_mla_matches_single_request(chunked):
    """MLA under backend="pallas": absorbed-matmul decode over streamed
    latent pages matches the single-request baseline token-for-token."""
    cfg = _mla_dense_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (12, 9, 14)]
    max_new = 8
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=32, page_size=8, chunked_prefill=chunked,
        backend="pallas",
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival_step=2 * i)
    reqs = eng.run()
    assert len(reqs) == 3 and all(r.state == "finished" for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    assert _idle_pages(eng.kv) == eng.kv.allocator.num_pages - 1


@pytest.mark.parametrize("mla", [False, True])
def test_pallas_backend_cow_divergence(mla):
    """Shared-prefix serving under backend="pallas": the COW copy runs
    through the scalar-prefetched page-copy kernel and the post-divergence
    decode reads through the fused attention kernel — outputs stay equal to
    the baseline, and at least one COW actually fired."""
    cfg = _mla_dense_cfg() if mla else _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, size=(24,)).astype(np.int32)
    pa = np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=(3,))
                         ]).astype(np.int32)
    pc = shared[:20].copy()  # partial tail page -> COW on first decode write
    prompts = [pa, pc]
    max_new = 8
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=48, page_size=8, prefix_sharing=True,
        backend="pallas",
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival_step=4 * i)
    reqs = eng.run()
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)
    assert eng.kv.cow_copies >= 1
    assert [r.stats.cached_prompt_tokens for r in reqs] == [0, 20]
    assert _idle_pages(eng.kv) == eng.kv.allocator.num_pages - 1


def test_pallas_backend_ring_swa_fallback_unchanged():
    """Families without paged decode (SWA ring buffer) ignore the backend
    selector: backend="pallas" still runs the ring path and stays
    bit-identical to the baseline."""
    cfg = C.get_config("h2o-danube-3-4b", smoke=True, dtype=jnp.float32)
    cfg = dataclasses.replace(cfg, block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (12, 9)]
    max_new = 8
    base = _single_request_baseline(cfg, params, prompts, max_new)
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=2, max_len=32, page_size=8, backend="pallas",
    ))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i)
    for r, b in zip(eng.run(), base):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), b)


def test_engine_rejects_unknown_backend():
    """An unknown backend name fails at Engine construction (eager
    resolve), not mid-trace inside a jitted step."""
    cfg = _paged_cfg(block=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="unknown backend"):
        Engine(cfg, params, EngineConfig(
            max_seqs=1, max_len=16, page_size=8, backend="cuda",
        ))


def test_make_requests_deterministic():
    a = make_requests(100, 5, mean_interarrival=3.0, seed=7)
    b = make_requests(100, 5, mean_interarrival=3.0, seed=7)
    for ra, rb in zip(a, b):
        assert ra["arrival_step"] == rb["arrival_step"]
        assert ra["max_new_tokens"] == rb["max_new_tokens"]
        np.testing.assert_array_equal(ra["prompt"], rb["prompt"])
    assert a[-1]["arrival_step"] > 0  # arrivals actually stagger


@pytest.mark.parametrize("d_head", [16, 128],
                         ids=["pages-hkv-dh-page", "pages-page-hkv-dh"])
def test_pallas_and_reference_engines_agree_through_mixed_run(d_head):
    """One run that mixes multi-chunk prefill, decode, preemption with
    recompute and a copy-on-write page copy gives bit-identical greedy
    tokens on the reference and pallas backends, for both stored page
    orders: a head dim short of the 128 lanes stores pages (hkv, dh,
    page), one that fills them (hkv, page, dh).  Both backends read and
    write the same layer-stacked pools carried through the layer scan."""
    cfg = _paged_cfg(block=8, d_head=d_head)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, size=(20,)).astype(np.int32)
    prompts = [shared,
               shared[:12].copy(),  # partial tail page: COW on its decode
               rng.integers(0, cfg.vocab_size, size=(17,)).astype(np.int32)]
    outs = {}
    for backend in ("reference", "pallas"):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=3, max_len=32, page_size=8, num_pages=9,
            prefix_sharing=True, backend=backend,
        ))
        for i, p in enumerate(prompts):
            eng.submit(p, 12, rid=i, arrival_step=2 * i)
        reqs = eng.run()
        assert all(r.state == "finished" for r in reqs)
        assert sum(r.stats.n_preemptions for r in reqs) >= 1
        assert eng.kv.cow_copies >= 1
        assert eng.prefill_chunks > len(prompts)
        eng.kv.audit()
        outs[backend] = [np.asarray(r.out_tokens) for r in reqs]
    for a, b in zip(outs["reference"], outs["pallas"]):
        np.testing.assert_array_equal(a, b)
