"""TPU-adaptation evidence: Pallas kernel DMA contiguity + VMEM report.

No real TPU here, so instead of wall-time we report the *structural*
quantities that govern TPU performance and that the BWMA layout changes:
per-grid-step DMA descriptor count (contiguous runs the BlockSpec fetch
decomposes into), bytes per descriptor, and VMEM working set — plus a
wall-clock microbench of the pure-jnp blocked ops (XLA:CPU) as a sanity
signal.
"""
import jax
import numpy as np

from benchmarks.common import emit, timed
from repro.core import blockwise as bw
from repro.core.backend import resolve_backend
from repro.core.layout import BlockLayout


def dma_descriptors(block_shape, array_shape, esize=2):
    """How many contiguous HBM runs one BlockSpec step touches.

    For a trailing-dims-contiguous block (BWMA 4-D layout) this is 1; for a
    2-D row-major operand it is the number of non-contiguous row segments.
    """
    # RWMA (bm, bk) block of (M, K): bm separated row segments.  BWMA
    # (1,1,bm,bk) of (gm, gk, bm, bk): trailing dims contiguous, 1 segment.
    if len(block_shape) == 2:
        return block_shape[0]
    return 1


def run(scale: float = 1.0, backend: str = "reference"):
    print("# kernel report: DMA contiguity + VMEM per BlockSpec step")
    bm = bk = bn = 128
    M = K = 1024
    esize = 2  # bf16
    rwma_desc = dma_descriptors((bm, bk), (M, K))
    bwma_desc = dma_descriptors((1, 1, bm, bk), (M // bm, K // bk, bm, bk))
    emit("kernel/rwma_gemm/dma_descriptors_per_step", 0.0, str(rwma_desc))
    emit("kernel/bwma_gemm/dma_descriptors_per_step", 0.0, str(bwma_desc))
    emit("kernel/descriptor_reduction", 0.0, f"{rwma_desc/bwma_desc:.0f}x")
    emit("kernel/bytes_per_descriptor_rwma", 0.0, f"{bk*esize}")
    emit("kernel/bytes_per_descriptor_bwma", 0.0, f"{bm*bk*esize}")
    vmem = (bm * bk + bk * bn + bm * bn) * 4  # f32 accum
    emit("kernel/vmem_working_set_bytes", 0.0,
         f"{vmem} ({vmem/2**20:.2f} MiB of ~16 MiB)")

    # paged-decode structural numbers: per grid step the fused decode kernel
    # fetches exactly one physical K/V page — a trailing-dims-contiguous
    # (1, hkv, page, dh) tile, one DMA descriptor — while the reference path
    # first materializes every slot's gathered history in HBM
    # (max_pages * page tokens per slot, K and V).  Serving-shaped numbers
    # (page=128 tokens, 8 KV heads, d_head=128, bf16):
    page_tok, hkv, dh = 128, 8, 128
    maxp = 8  # 1k-token history
    tile = page_tok * hkv * dh * esize
    gathered = 2 * maxp * tile  # K+V, per slot per layer per decode step
    emit("kernel/paged_decode/dma_descriptors_per_step", 0.0, "1")
    emit("kernel/paged_decode/bytes_per_descriptor", 0.0, f"{tile}")
    emit("kernel/paged_decode/reference_gather_bytes", 0.0,
         f"{gathered} (per slot/layer/step; kernel streams, never lands)")
    vmem_paged = (2 * page_tok * hkv * dh + 2 * page_tok) * 4
    emit("kernel/paged_decode/vmem_working_set_bytes", 0.0,
         f"{vmem_paged} ({vmem_paged/2**20:.2f} MiB of ~16 MiB)")

    # blocked GEMM wall time through the selected execution backend
    # ("reference" = pure-jnp on XLA:CPU; "pallas" = the BWMA kernels,
    # interpret mode off-TPU — a dispatch/correctness signal there).
    be = resolve_backend(backend)
    lo = BlockLayout(128, 128)
    m = int(512 * max(scale, 0.25))
    a = jax.random.normal(jax.random.PRNGKey(0), (m, 768))
    b = jax.random.normal(jax.random.PRNGKey(1), (768, 768))
    ab, bb = bw.block(a, lo), bw.block(b, lo)
    f_b = jax.jit(lambda x, y: be.matmul(x, y).data)
    _, us_b = timed(lambda: np.asarray(f_b(ab, bb)))
    f_r = jax.jit(lambda x, y: x @ y)
    _, us_r = timed(lambda: np.asarray(f_r(a, b)))
    emit(f"kernel/bw_matmul_{be.name}", us_b, f"rwma_jnp={us_r:.0f}us")


if __name__ == "__main__":
    import argparse

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--backend", default="reference",
                    help="execution backend: reference | pallas")
    args = ap.parse_args()
    run(scale=args.scale, backend=args.backend)
