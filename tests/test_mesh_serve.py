"""Tensor-parallel paged serving: simulated-mesh parity with single-device.

The tentpole guarantee: mesh sharding is a *placement* change, not a
numerics change — the continuous engine's greedy outputs on a CPU-simulated
``(data, model)`` mesh are bit-identical to the single-device engine, for
every cache family (dense/GQA pages, MLA latent pages, MoE stacks),
including chunked prefill, preemption/recompute, shared-prefix COW, and
both decode backends (jnp gather oracle and pallas kernels).

Simulated meshes need ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
set **before jax initializes** (the CI ``mesh`` job does); with fewer
devices every test here self-skips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_serve_mesh
from repro.models import model as M
from repro.serve import Engine, EngineConfig, ServeConfig, Server

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def _dense_cfg(**over):
    """minicpm (dense MHA), heads lifted to divide a 4-way model axis."""
    cfg = C.get_config("minicpm-2b", smoke=True, dtype=jnp.float32)
    over = {"block": 8, **over}
    return dataclasses.replace(cfg, n_heads=8, n_kv_heads=8, **over)


def _starcoder2_cfg(**over):
    """starcoder2 (LayerNorm, biases, tanh-GELU, untied head) with 4 KV
    heads of 128: on a 4-way model axis each device holds one, in the
    (hkv, page, dh) page order that lane-filling heads are stored in."""
    cfg = C.get_config("starcoder2-7b", smoke=True, dtype=jnp.float32)
    over = {"block": 8, **over}
    return dataclasses.replace(cfg, n_heads=8, n_kv_heads=4, d_head=128,
                               **over)


def _moe_cfg(**over):
    """granite MoE, GQA heads lifted to divide a 4-way model axis."""
    cfg = C.get_config("granite-moe-3b-a800m", smoke=True, dtype=jnp.float32)
    return dataclasses.replace(
        cfg, block=8, n_heads=8, n_kv_heads=4, **over
    )


def _mla_cfg(**over):
    """The full DeepSeek-V3 shape (MLA latent pages + MoE + MTP).  Latent
    pools have no head axis and replicate; head parallelism is activation-
    side only, so the stock smoke head count serves any mesh."""
    cfg = C.get_config("deepseek-v3-671b", smoke=True, dtype=jnp.float32)
    return dataclasses.replace(cfg, block=8, **over)


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in sizes]


def _run_engine(cfg, params, prompts, max_new, ec, mesh=None, stagger=2):
    eng = Engine(cfg, params, ec, mesh=mesh)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival_step=stagger * i)
    reqs = eng.run()
    assert all(r.state == "finished" for r in reqs)
    return eng, [np.asarray(r.out_tokens) for r in reqs]


def _assert_mesh_parity(cfg, prompts, max_new, ec, mesh):
    """Greedy tokens on ``mesh`` == single-device, token for token."""
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    _, base = _run_engine(cfg, params, prompts, max_new, ec)
    eng, out = _run_engine(cfg, params, prompts, max_new, ec, mesh=mesh)
    for b, o in zip(base, out):
        np.testing.assert_array_equal(o, b)
    return eng


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_mesh_parity_dense_chunked_prefill(backend):
    """Dense/GQA paged pools head-shard 4-way; chunked admission, slot
    re-fill, and both decode backends stay bit-identical — and each device
    holds 1/4 of the pool (minicpm's pools are all head-sharded)."""
    cfg = _dense_cfg(decode_backend=backend)
    mesh = make_serve_mesh("1x4")
    eng = _assert_mesh_parity(
        cfg, _prompts(cfg, (12, 9, 14)), 8,
        EngineConfig(max_seqs=2, max_len=32, page_size=8, backend=backend),
        mesh,
    )
    assert eng.kv.cache_bytes_per_device() == eng.kv.cache_bytes() // 4


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_mesh_parity_one_kv_head_per_device(backend):
    """starcoder2's block with one KV head per device (TP=4 over 4 KV
    heads, as the four-chip benchmark cell serves it): chunked prefill and
    decode stay bit-identical to one device under both backends, and each
    device holds one head's pages."""
    cfg = _starcoder2_cfg(decode_backend=backend)
    assert not cfg.tie_embeddings and cfg.qkv_bias
    eng = _assert_mesh_parity(
        cfg, _prompts(cfg, (12, 9, 14)), 8,
        EngineConfig(max_seqs=2, max_len=32, page_size=8, backend=backend),
        make_serve_mesh("1x4"),
    )
    assert eng.prefill_chunks > 3
    pool = eng.kv.data["seg0"]["attn"]["k_pages"]
    assert pool.shape[2:] == (4, 8, 128)
    assert pool.addressable_shards[0].data.shape[2:] == (1, 8, 128)
    assert eng.kv.cache_bytes_per_device() == eng.kv.cache_bytes() // 4


def test_mesh_parity_server_static_waves():
    """The static-wave baseline engine on the same mesh: resident-TP
    weights, same greedy tokens."""
    cfg = _dense_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, (12, 12))
    batch = {"tokens": jnp.asarray(np.stack(prompts))}
    base = Server(cfg, params, ServeConfig(max_len=64)).generate(batch, 8)
    out = Server(
        cfg, params, ServeConfig(max_len=64), mesh=make_serve_mesh("1x4")
    ).generate(batch, 8)
    np.testing.assert_array_equal(out, base)


def test_mesh_parity_moe_stack():
    """MoE (granite): expert FFN shards under the serve policy while the
    GQA pools head-shard.  Single-chunk prompts so the capacity dispatch
    sees one-shot token groups (the documented MoE chunking caveat —
    orthogonal to the mesh)."""
    cfg = _moe_cfg()
    _assert_mesh_parity(
        cfg, _prompts(cfg, (8, 7, 6), seed=1), 6,
        EngineConfig(max_seqs=2, max_len=32, page_size=8),
        make_serve_mesh("1x4"),
    )


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_mesh_parity_mla_latent_pages(backend):
    """DeepSeek MLA: latent pools replicate (no head axis) yet outputs stay
    bit-identical on the mesh, both backends."""
    cfg = _mla_cfg(decode_backend=backend)
    eng = _assert_mesh_parity(
        cfg, _prompts(cfg, (8, 7, 6), seed=1), 6,
        EngineConfig(max_seqs=2, max_len=32, page_size=8, backend=backend),
        make_serve_mesh("1x4"),
    )
    # replicated latent pools: every device holds the full pool
    assert eng.kv.cache_bytes_per_device() == eng.kv.cache_bytes()


def test_mesh_preemption_recompute_parity():
    """LIFO preemption + re-prefill over head-sharded pools: the recompute
    path (admission installs, COW, donation) stays bit-identical."""
    cfg = _dense_cfg(block=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, (10, 10, 10))
    ec = EngineConfig(max_seqs=3, max_len=20, page_size=4, num_pages=9)
    _, base = _run_engine(cfg, params, prompts, 10, ec, stagger=0)
    eng, out = _run_engine(
        cfg, params, prompts, 10, ec, mesh=make_serve_mesh("1x4"), stagger=0
    )
    assert sum(r.n_preemptions for r in (
        eng.sched.finished[i].stats for i in range(3))) >= 1
    for b, o in zip(base, out):
        np.testing.assert_array_equal(o, b)


def test_mesh_shared_prefix_cow_parity():
    """Prefix aliasing + copy-on-write divergence across sharded pools: the
    COW page copy runs per-shard (pallas) / partitioned (reference) and the
    diverged request still matches single-device."""
    cfg = _dense_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, size=(24,)).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=(3,))
                        ]).astype(np.int32),
        shared[:20].copy(),  # partial tail page -> COW divergence
    ]
    ec = EngineConfig(max_seqs=2, max_len=48, page_size=8)
    _, base = _run_engine(cfg, params, prompts, 8, ec, stagger=4)
    eng, out = _run_engine(
        cfg, params, prompts, 8, ec, mesh=make_serve_mesh("1x4"), stagger=4
    )
    assert eng.kv.cow_copies >= 1 and eng.kv.pages_aliased >= 1
    for b, o in zip(base, out):
        np.testing.assert_array_equal(o, b)


def test_mesh_rejects_nondividing_kv_heads():
    """Satellite fix: EngineConfig validation fails at construction — with
    an actionable message — when the paged kv-head axis cannot divide the
    mesh's model axis, instead of silently replicating every pool."""
    cfg = dataclasses.replace(
        C.get_config("minicpm-2b", smoke=True, dtype=jnp.float32), block=8
    )  # stock heads: 6 % 4 != 0
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="n_kv_heads=6.*model-axis size 4"):
        Engine(cfg, params, EngineConfig(max_seqs=2, max_len=32, page_size=8),
               mesh=make_serve_mesh("1x4"))
    # a dividing mesh constructs fine
    Engine(cfg, params, EngineConfig(max_seqs=2, max_len=32, page_size=8),
           mesh=make_serve_mesh("1x2"))
