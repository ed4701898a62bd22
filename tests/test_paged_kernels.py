"""Paged-decode kernel parity: fused Pallas kernels vs the jnp gather oracle.

Kernel-level counterpart of the engine-level backend tests in
``tests/test_serve.py``: each fused kernel (interpret mode on CPU — the
identical grids/BlockSpecs the TPU lowering uses) is swept per page count
and per ``seq_pos`` edge against the reference gather->attend functions it
replaces, reading one layer of a layer-stacked pool whose pages are stored
in the order the engine stores them (``kv_page_order``).  Attention parity
is gated at 1e-6 (online-softmax reassociation — the PR-1 BWMA
tolerance); the COW page copy must be bit-exact.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.kernels.paged_attention import (
    kv_page_order,
    mla_paged_attention_decode,
    paged_attention_decode,
    paged_copy,
)
from repro.models.attention import (
    cow_copy_page,
    mla_paged_gather_attend,
    paged_attend,
    paged_gather_attend,
)

PAGE = 8
MAXP = 4
LAYERS, LAYER = 3, 1  # stacked pools; the kernels read the middle layer
TOL = 1e-6


def _kv_block(hkv, dh, page=PAGE):
    """One K/V page's shape in the engine's stored order."""
    logical = (page, hkv, dh)
    return tuple(logical[a] for a in kv_page_order(dh))


def _table_and_pool(rng, B, maxp, used_pages, leaf_shapes):
    """A paged layout: per-slot table rows mapping ``used_pages`` distinct
    physical pages (page 0 is the null page, never mapped), plus random
    layer-stacked pool leaves ``(LAYERS, num_pages, *shape)``.  Unused
    table entries point at the null page like the engine's reset rows."""
    num_pages = B * maxp + 1
    table = np.zeros((B, maxp), np.int32)
    phys = rng.permutation(np.arange(1, num_pages))
    k = 0
    for b in range(B):
        table[b, :used_pages] = phys[k:k + used_pages]
        k += used_pages
    pools = [
        jnp.asarray(rng.standard_normal((LAYERS, num_pages) + s),
                    jnp.float32)
        for s in leaf_shapes
    ]
    return jnp.asarray(table), pools


def _edge_positions(used_pages):
    """seq_pos edges within the last used page: page boundary start, an
    interior partial fill, and the fully-written page."""
    last = (used_pages - 1) * PAGE
    return sorted({0, last, last + PAGE // 2, used_pages * PAGE - 1})


@pytest.mark.parametrize("groups", [1, 2, 4])
@pytest.mark.parametrize("used_pages", [1, 2, 4])
def test_paged_decode_matches_gather(used_pages, groups):
    rng = np.random.default_rng(used_pages * 10 + groups)
    B, H, dh = 2, 4, 16
    hkv = H // groups
    table, (k_pages, v_pages) = _table_and_pool(
        rng, B, MAXP, used_pages, [_kv_block(hkv, dh)] * 2
    )
    q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), jnp.float32)
    for pos in _edge_positions(used_pages):
        seq_pos = jnp.full((B,), pos, jnp.int32)
        ref = paged_gather_attend(q, k_pages, v_pages, table, seq_pos, LAYER)
        out = paged_attention_decode(
            q, k_pages, v_pages, table, seq_pos, LAYER, interpret=True
        )
        assert out.shape == ref.shape and out.dtype == ref.dtype
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err <= TOL, (used_pages, groups, pos, err)


@pytest.mark.parametrize("H,hkv,dh", [(36, 36, 64), (36, 4, 128),
                                      (9, 1, 128)],
                         ids=["minicpm-2b-heads", "starcoder2-heads",
                              "starcoder2-tp4-shard-heads"])
def test_paged_decode_head_geometries(H, hkv, dh):
    """The benchmark head geometries over the stacked pool: minicpm-2b's
    (36, 64) pages stored (hkv, dh, page), starcoder2's (4, 128) stored
    (hkv, page, dh), and the one KV head of 128 (with 9 query heads) that
    each device holds of starcoder2 at TP=4.  Each layer of the stack is
    read through the layer operand, and the oracle reads the same
    page-table rows out of that layer."""
    assert kv_page_order(dh) == ((1, 2, 0) if dh == 64 else (1, 0, 2))
    rng = np.random.default_rng(dh)
    B = 2
    table, (k_pages, v_pages) = _table_and_pool(
        rng, B, MAXP, 3, [_kv_block(hkv, dh)] * 2
    )
    q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), jnp.float32)
    seq_pos = jnp.asarray([2 * PAGE + 3, PAGE - 1], jnp.int32)
    outs = []
    for layer in range(LAYERS):
        ref = paged_gather_attend(q, k_pages, v_pages, table, seq_pos, layer)
        out = paged_attention_decode(
            q, k_pages, v_pages, table, seq_pos, jnp.int32(layer),
            interpret=True,
        )
        # reassociation error grows with the dot length: TOL holds the
        # 16-wide heads of the sweeps above, scaled to these head dims
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err <= TOL * dh / 16, (layer, err)
        outs.append(out)
    # the layer operand selects: each layer's pages give another answer
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-3


def test_paged_decode_ragged_positions():
    """Slots at different fill levels in one batched call — each row masks
    by its own seq_pos (null pages in unused table slots stay masked)."""
    rng = np.random.default_rng(3)
    B, H, dh = 3, 4, 16
    table, (k_pages, v_pages) = _table_and_pool(
        rng, B, MAXP, MAXP, [_kv_block(2, dh)] * 2
    )
    q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), jnp.float32)
    seq_pos = jnp.asarray([0, PAGE - 1, MAXP * PAGE - 1], jnp.int32)
    ref = paged_gather_attend(q, k_pages, v_pages, table, seq_pos, LAYER)
    out = paged_attention_decode(
        q, k_pages, v_pages, table, seq_pos, LAYER, interpret=True
    )
    assert float(jnp.max(jnp.abs(out - ref))) <= TOL


@pytest.mark.parametrize("used_pages", [1, 2, 4])
def test_mla_paged_decode_matches_gather(used_pages):
    rng = np.random.default_rng(used_pages)
    B, H, r, dr = 2, 4, 16, 8
    scale = (24 + dr) ** -0.5  # absorbed qk_nope + rope dims, as in MLA
    table, (ckv_pages, krope_pages) = _table_and_pool(
        rng, B, MAXP, used_pages, [(PAGE, r), (PAGE, dr)]
    )
    q_lat = jnp.asarray(rng.standard_normal((B, 1, H, r)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, 1, H, dr)), jnp.float32)
    for pos in _edge_positions(used_pages):
        seq_pos = jnp.full((B,), pos, jnp.int32)
        ref = mla_paged_gather_attend(
            q_lat, q_rope, ckv_pages, krope_pages, table, seq_pos, LAYER,
            scale=scale,
        )
        out = mla_paged_attention_decode(
            q_lat, q_rope, ckv_pages, krope_pages, table, seq_pos, LAYER,
            scale=scale, interpret=True,
        )
        assert out.shape == ref.shape and out.dtype == ref.dtype
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err <= TOL, (used_pages, pos, err)


def test_paged_copy_bit_exact():
    """The COW kernel is a pure data movement: dst page becomes src page
    bit-for-bit, every other page untouched, dtype preserved."""
    rng = np.random.default_rng(7)
    pool = jnp.asarray(
        rng.standard_normal((3, 5, PAGE, 2, 6)), jnp.float32
    )
    out = paged_copy(pool, 1, 3, interpret=True)
    expect = pool.at[:, 3].set(pool[:, 1])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    assert out.dtype == pool.dtype


def test_paged_copy_survives_donating_jit():
    """Inside a donating jit — the engine's COW step shape — the aliased
    pool update stays bit-exact (and the alias is what jaxcheck RPJ101
    budgets; here we only pin numerics)."""
    rng = np.random.default_rng(8)
    pool = jnp.asarray(rng.standard_normal((2, 4, PAGE, 3)), jnp.float32)
    expect = pool.at[:, 2].set(pool[:, 1])

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(p, s, d):
        return paged_copy(p, s, d, interpret=True)

    out = step(pool, jnp.int32(1), jnp.int32(2))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_backend_dispatch_parity():
    """The decode path's one owner, ``repro.models.attention``: its
    reference and pallas paths agree on GQA decode and on the
    dict-of-pools COW copy."""
    rng = np.random.default_rng(11)
    B, H, dh = 2, 4, 16
    table, (k_pages, v_pages) = _table_and_pool(
        rng, B, MAXP, 2, [_kv_block(2, dh)] * 2
    )
    q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), jnp.float32)
    seq_pos = jnp.asarray([5, 13], jnp.int32)
    cfg = C.get_config("minicpm-2b", smoke=True)
    ref, pal = (dataclasses.replace(cfg, decode_backend=b)
                for b in ("reference", "pallas"))  # interpreted off-TPU
    a = paged_attend(ref, q, k_pages, v_pages, table, seq_pos, LAYER)
    b = paged_attend(pal, q, k_pages, v_pages, table, seq_pos, LAYER)
    assert float(jnp.max(jnp.abs(a - b))) <= TOL
    # the layer-stacked pools themselves, page-id axis 1 — the adapters'
    # COW layout
    pools = {"k_pages": k_pages, "v_pages": v_pages}
    got = cow_copy_page(pal, pools, 1, 2)
    want = cow_copy_page(ref, pools, 1, 2)
    assert set(got) == set(want)
    for name in got:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))
