"""Backend parity + microbench: RWMA vs BWMA through the *actual kernels*.

Earlier benchmarks compared the pure-jnp blockwise operators against
row-major XLA — a layout comparison, not an execution one.  This section
runs the full blocked encoder through each registered execution backend
("reference" = jnp blockwise, "pallas" = the Pallas BWMA kernels, interpret
mode off-TPU) and reports wall time plus max abs error against the row-major
baseline, so the paper's RWMA-vs-BWMA claim is finally measured on the
kernel path it describes.

The paged-decode section does the same for the serving hot loop: the fused
paged-attention kernels (dense/GQA and MLA) and the COW page copy are swept
per page count against the jnp gather->attend oracle they replace, emitting
wall time per backend and max abs error (the BWMA-table format).  Attention
errors stay within online-softmax reassociation (<= 1e-6); the page copy is
bit-exact.

Note on CPU numbers: interpret mode executes the kernel body per grid step
in Python — its wall time is a correctness/dispatch-overhead signal, not a
performance claim.  On TPU the same BlockSpecs compile natively.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.core import encoder as enc
from repro.core.backend import BACKENDS, resolve_backend
from repro.kernels.paged_attention import kv_page_order


def run(scale: float = 1.0, block: int = 128):
    print("# backend parity: blocked encoder through each execution backend")
    seq = max(128, int(512 * min(scale, 1.0)))
    cfg = enc.EncoderConfig(
        seq_len=seq, d_model=768, n_heads=12, d_head=64, d_ff=3072,
        n_layers=1, block=block,
    )
    params = enc.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (cfg.seq_len, cfg.d_model))
    bp = enc.block_params(params, cfg)

    y_rwma, us_rwma = timed(lambda: np.asarray(enc.encoder_rwma(params, x, cfg)))
    emit("backend/rwma_jnp/us", us_rwma, f"seq={seq} block={block}")

    for name in sorted(BACKENDS):
        y, us = timed(
            lambda name=name: np.asarray(enc.encoder_bwma(bp, x, cfg, backend=name))
        )
        err = float(np.abs(y - y_rwma).max())
        emit(f"backend/{name}/us", us, f"max_abs_err_vs_rwma={err:.2e}")

    run_paged(scale)


def _paged_layout(rng, B, maxp, page, leaf_shapes, layers=2):
    """A serving-shaped paged layout: per-slot table rows of distinct
    physical pages (page 0 reserved as the null page) + random
    layer-stacked pools ``(layers, num_pages, *shape)``."""
    num_pages = B * maxp + 1
    table = np.zeros((B, maxp), np.int32)
    phys = rng.permutation(np.arange(1, num_pages))
    for b in range(B):
        table[b] = phys[b * maxp:(b + 1) * maxp]
    pools = [
        jnp.asarray(rng.standard_normal((layers, num_pages) + s),
                    jnp.float32)
        for s in leaf_shapes
    ]
    return jnp.asarray(table), pools


def run_paged(scale: float = 1.0, page: int = 8):
    """Per-page-count sweep: fused paged-decode kernels vs the gather oracle.

    Each row doubles the slots' mapped history (seq_pos fills every mapped
    page), so the reference gather bytes grow linearly while the kernel
    streams the same pages tile-by-tile.
    """
    print("# paged decode: fused kernels vs jnp gather oracle per page count")
    B, H, hkv, dh = 2, 8, 4, 32
    kv_block = tuple((page, hkv, dh)[a] for a in kv_page_order(dh))
    layer = jnp.int32(1)  # the pools are stacked; read their second layer
    r, dr = 32, 16
    scale_mla = (r + dr) ** -0.5
    ref, pal = resolve_backend("reference"), resolve_backend("pallas")
    # one jitted callable per (backend, op); each maxp is a fresh shape and
    # traces once into the same cache
    f_gqa = {
        "reference": jax.jit(ref.paged_attention_decode),
        "pallas": jax.jit(pal.paged_attention_decode),
    }
    f_mla = {
        "reference": jax.jit(
            lambda *a: ref.mla_paged_attention_decode(*a, scale=scale_mla)),
        "pallas": jax.jit(
            lambda *a: pal.mla_paged_attention_decode(*a, scale=scale_mla)),
    }
    for maxp in (1, 2, 4, 8):
        rng = np.random.default_rng(maxp)
        seq_pos = jnp.full((B,), maxp * page - 1, jnp.int32)
        # dense/GQA
        table, (k_pages, v_pages) = _paged_layout(
            rng, B, maxp, page, [kv_block] * 2
        )
        q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), jnp.float32)
        y_ref, us_ref = timed(lambda: np.asarray(
            f_gqa["reference"](q, k_pages, v_pages, table, seq_pos, layer)))
        y_pal, us_pal = timed(lambda: np.asarray(
            f_gqa["pallas"](q, k_pages, v_pages, table, seq_pos, layer)))
        err = float(np.abs(y_pal - y_ref).max())
        gathered = 2 * B * maxp * page * hkv * dh * 4  # ref K+V HBM bytes
        emit(f"paged/gqa_p{maxp}/reference_us", us_ref,
             f"gather_bytes={gathered}")
        emit(f"paged/gqa_p{maxp}/pallas_us", us_pal,
             f"max_abs_err_vs_reference={err:.2e}")
        # MLA (absorbed latent scoring)
        table, (ckv_pages, kr_pages) = _paged_layout(
            rng, B, maxp, page, [(page, r), (page, dr)]
        )
        q_lat = jnp.asarray(rng.standard_normal((B, 1, H, r)), jnp.float32)
        q_rope = jnp.asarray(rng.standard_normal((B, 1, H, dr)), jnp.float32)
        y_ref, us_ref = timed(lambda: np.asarray(
            f_mla["reference"](q_lat, q_rope, ckv_pages, kr_pages, table,
                               seq_pos, layer)))
        y_pal, us_pal = timed(lambda: np.asarray(
            f_mla["pallas"](q_lat, q_rope, ckv_pages, kr_pages, table,
                            seq_pos, layer)))
        err = float(np.abs(y_pal - y_ref).max())
        emit(f"paged/mla_p{maxp}/reference_us", us_ref, "")
        emit(f"paged/mla_p{maxp}/pallas_us", us_pal,
             f"max_abs_err_vs_reference={err:.2e}")
    # COW page copy (page-count independent: one page moves)
    rng = np.random.default_rng(0)
    pool = {"k_pages": jnp.asarray(
        rng.standard_normal((4, 9) + kv_block), jnp.float32)}
    f_ref = jax.jit(ref.paged_copy_page)
    f_pal = jax.jit(pal.paged_copy_page)
    y_ref, us_ref = timed(lambda: np.asarray(
        f_ref(pool, jnp.int32(1), jnp.int32(2))["k_pages"]))
    y_pal, us_pal = timed(lambda: np.asarray(
        f_pal(pool, jnp.int32(1), jnp.int32(2))["k_pages"]))
    exact = bool(np.array_equal(y_pal, y_ref))
    emit("paged/cow_copy/reference_us", us_ref, "")
    emit("paged/cow_copy/pallas_us", us_pal, f"bit_exact={exact}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    run()
