"""CPU self-check of the plain reference against ``repro.models``, at the
configurations' rehearsal (smoke) sizes.  Run by hand from the repo root:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_reference.py

The reference is independent of the program; these tests show that the two
compute the same function of the same weights, so that a gap the chip's
check reads is the program's rounding or a fault, and not a difference of
definitions.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import model as MB  # noqa: E402
import reference as R  # noqa: E402

CONFIGS = ["minicpm-2b", "starcoder2-7b-pp2"]
SEED = 2**31 + 12345  # wider than 32 signed bits, as run seeds may be
# The program runs bf16 weights and bf16 activations between its f32
# norms, softmax and accumulations; the reference runs f32 throughout on
# the same bf16 weight values.  At two layers of the smoke sizes their
# logits differ by bf16 rounding of a few activations: a few 1e-3 of the
# logits' range.  A wrong scaling, rotation, bias or norm moves them by
# tens of percent.
LOGIT_RTOL = 0.03


def spec_of(name):
    return MB.load(HERE / "configs" / f"{name}.json", rehearse=True)


@pytest.mark.parametrize("name", CONFIGS)
def test_logits_match_program(name):
    """Full-sequence logits of repro's model on the converted weights
    against the reference's, at every position."""
    from repro.models import model as M

    spec = spec_of(name)
    cfg = MB.model_config(spec)
    params = MB.program_params(spec, cfg, SEED)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, spec["vocab_size"], 40).astype(np.int32)
    got, _, _ = M.forward_train(cfg, params, {"tokens": jnp.asarray(toks)[None]},
                                remat=False)
    got = np.asarray(got[0, :, : spec["vocab_size"]], np.float32)
    ref = R.Reference(spec, SEED)
    (h,) = ref.hidden([toks])
    want = np.asarray(ref.logits(h, np.arange(len(toks))))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < LOGIT_RTOL, err
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.8


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_regenerate_exactly(name):
    """The layer-by-layer regeneration gives the values the one-jit loader
    stacked (leaves the loader does not scale)."""
    spec = spec_of(name)
    cfg = MB.model_config(spec)
    params = MB.program_params(spec, cfg, SEED)
    for layer in range(spec["num_hidden_layers"]):
        W = jax.jit(lambda l: MB.layer_weights(spec, SEED, l))(layer)
        for ours, theirs in (("self_attn.q_proj.weight", ("attn", "wq")),
                             ("self_attn.k_proj.weight", ("attn", "wk")),
                             ("input_layernorm.weight", ("ln1", "w"))):
            a = params["seg0"][theirs[0]][theirs[1]][layer]
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(W[ours], np.float32))


def test_noise_is_the_engines_sampler():
    """argmax(logits / T + gumbel_noise) is what ``jax.random.categorical``
    draws under the engine's per-(request, position) key."""
    vp, T, seed, rid = 512, 0.7, MB.seed31(SEED), 17
    logits = jax.random.normal(jax.random.PRNGKey(1), (vp,)) * 2
    ks = np.arange(6)
    noise = R.gumbel_noise({"seed": seed}, rid, ks, vp)
    for k in ks:
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), rid), k)
        want = int(jax.random.categorical(key, logits / T))
        assert int(jnp.argmax(logits / T + noise[k])) == want


def test_fp8_control_is_coarser():
    """The control's logits differ from the reference's by far more than the
    program's rounding does."""
    spec = spec_of("minicpm-2b")
    toks = np.random.default_rng(1).integers(0, spec["vocab_size"], 40)
    a = R.Reference(spec, SEED)
    b = R.Reference(spec, SEED, quant="fp8")
    rows = np.arange(len(toks))
    za = np.asarray(a.logits(a.hidden([toks])[0], rows))
    zb = np.asarray(b.logits(b.hidden([toks])[0], rows))
    assert np.abs(za - zb).max() / np.abs(za).max() > 0.02
