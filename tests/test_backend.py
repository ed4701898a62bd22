"""Execution-backend parity: pallas kernels vs reference blockwise vs RWMA.

The acceptance bar for the kernel-backed path: ``encoder_bwma`` with
``backend="pallas"`` (interpret mode on CPU) must match both the row-major
baseline and the reference blockwise backend to <= 1e-4 max abs error on
BERT-base-shaped inputs, including ragged (non-block-multiple) shapes.
"""
import ast
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import blockwise as bw
from repro.core import encoder as enc
from repro.core.backend import (
    BACKENDS,
    Backend,
    PallasBackend,
    ReferenceBackend,
    resolve_backend,
)
from repro.core.layout import BlockLayout
from repro.models import attention as attn

SRC = Path(__file__).resolve().parents[1] / "src"


def _cfg(**kw):
    base = dict(seq_len=64, d_model=96, n_heads=3, d_head=32, d_ff=128,
                n_layers=1, block=16)
    base.update(kw)
    return enc.EncoderConfig(**base)


def _outputs(cfg, seed=0):
    params = enc.init_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (cfg.seq_len, cfg.d_model))
    bp = enc.block_params(params, cfg)
    y_rwma = enc.encoder_rwma(params, x, cfg)
    y_ref = enc.encoder_bwma(bp, x, cfg, backend="reference")
    y_pal = enc.encoder_bwma(bp, x, cfg, backend="pallas", interpret=True)
    return np.asarray(y_rwma), np.asarray(y_ref), np.asarray(y_pal)


def test_resolve_backend():
    assert set(BACKENDS) >= {"reference", "pallas"}
    assert isinstance(resolve_backend(None), ReferenceBackend)
    assert isinstance(resolve_backend("reference"), ReferenceBackend)
    pb = resolve_backend("pallas", interpret=True)
    assert isinstance(pb, PallasBackend) and pb.interpret
    assert resolve_backend(pb) is pb
    # auto (None) and the explicitly-resolved value share one instance/cache
    assert resolve_backend("pallas") is resolve_backend(
        "pallas", interpret=jax.default_backend() == "cpu"
    )
    with pytest.raises(ValueError):
        resolve_backend("no-such-backend")
    with pytest.raises(ValueError):
        resolve_backend("reference", interpret=True)  # not a silent no-op


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", False),
])
def test_interpret_only_on_cpu(monkeypatch, platform, interpret):
    """Interpret mode is the CPU test path only: any other platform gets
    compiled kernels (or a compile error), never interpreted XLA posing as
    the kernel path."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert PallasBackend().interpret is interpret
    assert PallasBackend(interpret=True).interpret
    assert attn.kernels_interpreted() is interpret  # the paged kernels'


def _imported_modules(path: Path):
    """Every module a source file imports, function-level imports
    included, relative ones resolved against its package."""
    package = ".".join(path.relative_to(SRC).parent.parts)
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package.rsplit(".", node.level - 1)[0] if node.level else ""
            mod = ".".join(filter(None, (base, node.module)))
            yield mod
            yield from (f"{mod}.{alias.name}" for alias in node.names)


def test_core_and_kernels_import_nothing_above_them():
    """The paper's blocked encoder (``repro.core``) and the kernels sit
    below the models, the engine and the sharding rules: none of their
    modules imports those, and the BWMA ``Backend`` carries no serving
    (paged) op; the paged-decode path belongs to ``repro.models.attention``.
    """
    above = ("repro.models", "repro.serve", "repro.distributed")
    found = [
        f"{path.relative_to(SRC)}: {mod}"
        for pkg in ("core", "kernels")
        for path in sorted((SRC / "repro" / pkg).glob("*.py"))
        for mod in _imported_modules(path)
        if mod.startswith(above)
    ]
    assert not found, found
    assert not [m for m in dir(Backend) if "paged" in m]


def test_pallas_matches_reference_ragged():
    """seq_len, d_model AND d_head all non-multiples of the block: the
    padding/masking path (incl. the per-head padded merge) end to end."""
    cfg = _cfg(seq_len=45, d_model=72, n_heads=2, d_head=20, d_ff=80,
               n_layers=2, block=16)
    y_rwma, y_ref, y_pal = _outputs(cfg, seed=2)
    np.testing.assert_allclose(y_ref, y_rwma, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(y_pal, y_rwma, rtol=5e-4, atol=5e-4)
    assert np.abs(y_pal - y_ref).max() <= 1e-4


def test_pallas_matches_reference_bert_base_shaped():
    """The paper's evaluation shape (512 x 768, 12 heads x 64, ff 3072) at
    the TPU-native 128 block — d_head 64 exercises the padded head merge."""
    cfg = enc.EncoderConfig(seq_len=512, d_model=768, n_heads=12, d_head=64,
                            d_ff=3072, n_layers=1, block=128)
    y_rwma, y_ref, y_pal = _outputs(cfg, seed=4)
    np.testing.assert_allclose(y_ref, y_rwma, rtol=5e-4, atol=5e-4)
    assert np.abs(y_pal - y_ref).max() <= 1e-4
    assert np.abs(y_pal - y_rwma).max() <= 5e-4


def test_batched_input_both_backends():
    """Leading batch dims run as one batched kernel call per op."""
    cfg = _cfg(seq_len=32, d_model=48, n_heads=2, d_head=16, d_ff=64)
    params = enc.init_params(jax.random.PRNGKey(6), cfg)
    bp = enc.block_params(params, cfg)
    xB = jax.random.normal(jax.random.PRNGKey(7), (2, cfg.seq_len, cfg.d_model))
    per_sample = np.stack([
        np.asarray(enc.encoder_bwma(bp, xB[i], cfg)) for i in range(2)
    ])
    for backend in ("reference", "pallas"):
        kw = {"interpret": True} if backend == "pallas" else {}
        yB = enc.encoder_bwma(bp, xB, cfg, backend=backend, **kw)
        assert yB.shape == (2, cfg.seq_len, cfg.d_model)
        np.testing.assert_allclose(np.asarray(yB), per_sample, rtol=2e-5, atol=2e-5)


def test_backend_ops_headwise_parity():
    """Op-level parity with a heads leading dim (the collapsed per-head loop)."""
    lo = BlockLayout(16, 16)
    h, s, dh = 3, 48, 32
    ref, pal = ReferenceBackend(), PallasBackend(interpret=True)
    q, k, v = (
        bw.Blocked(jax.random.normal(jax.random.PRNGKey(i), (h, s // 16, dh // 16, 16, 16)),
                   (s, dh), lo)
        for i in (8, 9, 10)
    )
    got = pal.attention(q, k, v, scale=0.125)
    want = ref.attention(q, k, v, scale=0.125)
    np.testing.assert_allclose(np.asarray(got.data), np.asarray(want.data),
                               rtol=2e-5, atol=2e-5)
    sm_got, sm_want = pal.softmax(q), ref.softmax(q)
    np.testing.assert_allclose(np.asarray(sm_got.data), np.asarray(sm_want.data),
                               rtol=2e-5, atol=2e-5)
