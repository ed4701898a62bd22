"""The architecture lives in one module per family, found by name.

Run by hand from the repo root:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_families.py

* The dense family reads the numbers the benchmark read before its code
  moved into ``perfbench/families/dense.py``: digests of the program's
  parameters, of the reference's logits (float32 and the float8 control)
  and gaps, and the work counts, recorded at the commit before the move.
* A family written into a fresh families directory, with layers of two
  kinds, drives the configuration, the program's parameters and the
  reference end to end: an architecture is added by new files alone.
* An unknown family fails naming its missing file.
* No benchmark file outside ``families/`` reads an architecture key.
"""
import hashlib
import shutil
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

SEED = 2**31 + 12345
CONFIGS = ["minicpm-2b", "starcoder2-7b-pp2"]
# recorded before the move (rehearsal sizes for the arrays; the full and
# rehearsal files for the counts), with the code that this module replaced
BEFORE = {
    "minicpm-2b": {
        "params": "4d2333a6cc63c1f0a16c", "logits_f32": "0e95cd78037edbbaf999",
        "logits_fp8": "9bc6c215d56bce6755b8", "gaps": "7ffdad0985c8a55f39e7",
        "work_full": (3067453440, 3057500160), "flops_full": 1308635859456,
        "work_rehearsal": (4260352, 4246528), "flops_rehearsal": 62409216,
    },
    "starcoder2-7b-pp2": {
        "params": "9f4afe12a523fd3f9edf", "logits_f32": "baf526aa0355049f20eb",
        "logits_fp8": "ed4e9295f7b5c393234a", "gaps": "b16ff597fa305ac08f7a",
        "work_full": (279740416, 2446000128), "flops_full": 1843080855552,
        "work_rehearsal": (2137088, 4246528), "flops_rehearsal": 49531392,
    },
}
DECODE_CTX = [[300] * 12, [301] * 12, [5, 77, 1000]]
CHUNKS = [(0, 128, False), (128, 100, True), (0, 7, True)]
ARCH_KEYS = ("hidden_act", "num_key_value_heads", "intermediate_size",
             "scale_emb", "norm_type", "use_bias", "vocab_size")


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:20]


def spec_of(name, rehearse=True):
    return MB.load(HERE / "configs" / f"{name}.json", rehearse=rehearse)


def prompt(spec):
    return np.random.default_rng(0).integers(0, spec["vocab_size"], 40).astype(np.int32)


@pytest.mark.parametrize("name", CONFIGS)
def test_program_params_unchanged(name):
    spec = spec_of(name)
    params = MB.program_params(spec, MB.model_config(spec), SEED)
    assert digest(params) == BEFORE[name]["params"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_unchanged(name):
    spec = spec_of(name)
    toks = prompt(spec)
    for quant in ("f32", "fp8"):
        ref = R.Reference(spec, SEED, quant=quant)
        (h,) = ref.hidden([toks])
        assert digest(ref.logits(h, np.arange(40))) == BEFORE[name]["logits_" + quant]
    gaps = R.Reference(spec, SEED).gaps(
        [toks[:39]], [{"rid": 3, "start": 20, "tokens": toks[20:40]}],
        {"seed": MB.seed31(SEED), "temperature": 0.7})
    assert digest(np.asarray(gaps)) == BEFORE[name]["gaps"]


@pytest.mark.parametrize("size", ["full", "rehearsal"])
@pytest.mark.parametrize("name", CONFIGS)
def test_work_counts_unchanged(name, size):
    spec = spec_of(name, rehearse=size == "rehearsal")
    fam = MB.family(spec)
    assert fam.decode_work(spec, DECODE_CTX) == BEFORE[name]["work_" + size]
    assert fam.step_flops(spec, DECODE_CTX, CHUNKS) == BEFORE[name]["flops_" + size]


def test_default_family_is_dense():
    spec = spec_of("minicpm-2b")
    assert "family" not in spec
    assert MB.family(spec) is MB.load_family("dense")
    assert MB.family(spec).decode_kernel(spec_of("minicpm-2b", False)) == (
        "paged_attention_decode", 40)


def test_unknown_family_names_its_file():
    spec = {**spec_of("minicpm-2b"), "family": "no_such_family"}
    with pytest.raises(FileNotFoundError, match=r"families/no_such_family\.py"):
        MB.model_config(spec)


def test_no_architecture_key_outside_families():
    """Only ``perfbench/families/`` reads the published architecture: no
    other benchmark file names one of its keys (``cfg.vocab_size``, the
    program's own configuration, is not the file's key)."""
    seen = []
    for path in sorted(HERE.rglob("*.py")):
        rel = path.relative_to(HERE)
        if rel.parts[0] in ("families", "tests"):
            continue
        text = path.read_text()
        seen += [f"{rel}: {k}" for k in ARCH_KEYS
                 if f'"{k}"' in text or f"'{k}'" in text]
    assert not seen, seen


# A mixture-of-experts family written from nothing but a new file: the
# first ``first_k_dense_replace`` layers are the dense family's, the rest
# route each token to ``num_experts_per_tok`` of ``n_routed_experts``
# SwiGLU experts (softmax router, top-k weights renormalised), as
# ``repro.models``' MoE computes them where no expert overflows.
TINY_MOE = '''
import jax
import jax.numpy as jnp

import model as MB
import reference as R

dense = MB.load_family("dense")
dims, vocab, role_dense = dense.dims, dense.vocab, dense.role
global_shapes, program_globals = dense.global_shapes, dense.program_globals
embed, head, decode_kernel, decode_work = (dense.embed, dense.head,
                                           dense.decode_kernel, dense.decode_work)
EXPERTS = ("mlp.experts.gate_proj.weight", "mlp.experts.up_proj.weight",
           "mlp.experts.down_proj.weight")


def layer_kinds(spec):
    k = spec["first_k_dense_replace"]
    return ["dense"] * k + ["moe"] * (spec["num_hidden_layers"] - k)


def layer_shapes(spec, kind):
    s = dense.layer_shapes(spec)
    if kind == "dense":
        return s
    d, E, f = spec["hidden_size"], spec["n_routed_experts"], spec["moe_intermediate_size"]
    s = {n: x for n, x in s.items() if not n.startswith("mlp.")}
    s.update({"mlp.gate.weight": (d, E), EXPERTS[0]: (E, d, f),
              EXPERTS[1]: (E, d, f), EXPERTS[2]: (E, f, d)})
    return s


def role(name):
    return "matrix" if name in EXPERTS or name == "mlp.gate.weight" else role_dense(name)


def model_config(spec):
    import dataclasses

    dense.check_supported(spec)
    E = spec["n_routed_experts"]
    return dataclasses.replace(
        dense.model_config(spec), family="moe", n_experts=E,
        top_k=spec["num_experts_per_tok"], moe_d_ff=spec["moe_intermediate_size"],
        first_k_dense=spec["first_k_dense_replace"], capacity_factor=float(E))


def program_segment(spec, kind, stacked):
    if kind == "dense":
        return dense.program_segment(spec, kind, stacked)
    return {"ln1": {"w": stacked["input_layernorm.weight"]},
            "attn": {"wq": stacked["self_attn.q_proj.weight"],
                     "wk": stacked["self_attn.k_proj.weight"],
                     "wv": stacked["self_attn.v_proj.weight"],
                     "wo": stacked["self_attn.o_proj.weight"]},
            "ln2": {"w": stacked["post_attention_layernorm.weight"]},
            "moe": {"router": stacked["mlp.gate.weight"].astype(jnp.float32),
                    "w_gate": stacked[EXPERTS[0]], "w_up": stacked[EXPERTS[1]],
                    "w_down": stacked[EXPERTS[2]]}}


def layer(spec, kind, W, h, quant):
    if kind == "dense":
        return dense.layer(spec, kind, W, h, quant)
    m = dims(spec)
    f32 = {n: x.astype(jnp.float32) for n, x in W.items()}
    S = h.shape[0]
    pos = jnp.arange(S)
    x = dense.norm(spec, h, f32["input_layernorm.weight"])
    q = R.matmul(x, f32["self_attn.q_proj.weight"], quant).reshape(S, m["H"], m["dh"])
    k = R.matmul(x, f32["self_attn.k_proj.weight"], quant).reshape(S, m["Hkv"], m["dh"])
    v = R.matmul(x, f32["self_attn.v_proj.weight"], quant).reshape(S, m["Hkv"], m["dh"])
    theta = float(spec["rope_theta"])
    a = R.attention(dense.rope(q, pos, theta), dense.rope(k, pos, theta), v, quant)
    h = h + R.matmul(a.reshape(S, -1), f32["self_attn.o_proj.weight"], quant)
    x = dense.norm(spec, h, f32["post_attention_layernorm.weight"])
    probs = jax.nn.softmax(R.matmul(x, f32["mlp.gate.weight"], quant), -1)
    top, idx = jax.lax.top_k(probs, spec["num_experts_per_tok"])
    gate = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(
        top / top.sum(-1, keepdims=True))
    y = 0.0
    for e in range(spec["n_routed_experts"]):
        g = jax.nn.silu(R.matmul(x, f32[EXPERTS[0]][e], quant))
        u = R.matmul(x, f32[EXPERTS[1]][e], quant)
        y = y + gate[:, e:e + 1] * R.matmul(g * u, f32[EXPERTS[2]][e], quant)
    return h + y


def step_flops(spec, decode_ctx, chunks):
    """2 x the parameters a token uses: attention, dense FFNs, and the
    experts it is routed to, not all of them."""
    m, kinds = dims(spec), layer_kinds(spec)
    d, f, kf = m["d"], m["f"], spec["moe_intermediate_size"]
    attn = d * (m["H"] + 2 * m["Hkv"]) * m["dh"] + m["H"] * m["dh"] * d
    ffn = {"dense": 3 * d * f,
           "moe": d * spec["n_routed_experts"] + spec["num_experts_per_tok"] * 3 * d * kf}
    per_token = 2 * sum(attn + ffn[k] for k in kinds)
    tokens = sum(len(c) for c in decode_ctx) + sum(n for _, n, _ in chunks)
    return per_token * tokens
'''


@pytest.fixture
def tiny_moe(tmp_path, monkeypatch):
    shutil.copy(HERE / "families" / "dense.py", tmp_path / "dense.py")
    (tmp_path / "tiny_moe.py").write_text(TINY_MOE)
    monkeypatch.setattr(MB, "FAMILIES", tmp_path)
    spec = {k: v for k, v in spec_of("minicpm-2b").items()
            if k not in ("scale_emb", "scale_depth", "dim_model_base")}
    spec.update(family="tiny_moe", num_hidden_layers=3, first_k_dense_replace=1,
                n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=32)
    return spec


def test_family_from_new_files(tiny_moe):
    """A family of two layer kinds, found in a fresh directory, drives the
    program's configuration and parameters (checked against the program's
    own layout: a dense segment and an expert segment) and the reference;
    and the reference computes what the program computes."""
    from repro.models import model as M

    spec = tiny_moe
    fam = MB.family(spec)
    assert Path(fam.__file__).parent == MB.FAMILIES
    assert MB.segments(spec) == [("dense", 0, 1), ("moe", 1, 2)]
    cfg = MB.model_config(spec)
    assert (cfg.family, cfg.n_experts, cfg.first_k_dense) == ("moe", 4, 1)
    params = MB.program_params(spec, cfg, SEED)
    assert params["seg1"]["moe"]["w_gate"].shape == (2, 4, 64, 32)
    toks = prompt(spec)
    got, _, _ = M.forward_train(cfg, params, {"tokens": jnp.asarray(toks)[None]},
                                remat=False)
    got = np.asarray(got[0, :, : spec["vocab_size"]], np.float32)
    ref = R.Reference(spec, SEED)
    (h,) = ref.hidden([toks])
    want = np.asarray(ref.logits(h, np.arange(len(toks))))
    assert np.abs(got - want).max() / np.abs(want).max() < 0.03
    gaps = ref.gaps([toks[:39]], [{"rid": 3, "start": 20, "tokens": toks[20:40]}],
                    {"seed": MB.seed31(SEED), "temperature": 0.7})
    assert gaps.shape == (20,) and np.isfinite(gaps).all()
    # the expert layers count the experts a token uses, not all of them
    every = {**spec, "num_experts_per_tok": spec["n_routed_experts"]}
    assert 0 < fam.step_flops(spec, DECODE_CTX, CHUNKS) < fam.step_flops(
        every, DECODE_CTX, CHUNKS)
