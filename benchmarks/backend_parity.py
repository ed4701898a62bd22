"""Backend parity + microbench: RWMA vs BWMA through the *actual kernels*.

Earlier benchmarks compared the pure-jnp blockwise operators against
row-major XLA — a layout comparison, not an execution one.  This section
runs the full blocked encoder through each registered execution backend
("reference" = jnp blockwise, "pallas" = the Pallas BWMA kernels, interpret
mode off-TPU) and reports wall time plus max abs error against the row-major
baseline, so the paper's RWMA-vs-BWMA claim is finally measured on the
kernel path it describes.

Note on CPU numbers: interpret mode executes the kernel body per grid step
in Python — its wall time is a correctness/dispatch-overhead signal, not a
performance claim.  On TPU the same BlockSpecs compile natively.
"""
import jax
import numpy as np

from benchmarks.common import emit, timed
from repro.core import encoder as enc
from repro.core.backend import BACKENDS


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


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    run()
