"""The dense decoder family: Llama-style MiniCPM and StarCoder2.

All that the benchmark knows of this architecture (the interface is in
:mod:`model`'s docstring).  The reference's math is written from the
published descriptions, not from ``repro.models``, and imports nothing of
the program:

* RMSNorm (MiniCPM) or LayerNorm with bias (StarCoder2), eps from the file;
* rotary embeddings on the two halves of each head (``rotate_half``);
* multi-head or grouped-query attention, causal, within ``sliding_window``;
* SwiGLU (MiniCPM) or a biased tanh-GELU MLP (StarCoder2);
* MiniCPM's ``scale_emb``, ``scale_depth / sqrt(L)`` and
  ``hidden / dim_model_base`` scalings;
* a head tied to the embedding table or its own.

repro's model has no term for MiniCPM's scalings: :func:`program_globals`
and :func:`program_segment` fold them into the weights.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import model as MB
import reference as R

# what the program's norms and activations compute (repro.models.common)
PROGRAM_EPS = 1e-5
ACTS = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}
KERNEL = "paged_attention_decode"  # the Pallas decode kernel's name
BYTES = 2  # bf16


# ---------------------------------------------------------------------------
# Shapes of the published architecture
# ---------------------------------------------------------------------------


def dims(spec: Dict) -> Dict:
    d = spec["hidden_size"]
    H = spec["num_attention_heads"]
    return {
        "L": spec["num_hidden_layers"], "d": d, "H": H,
        "Hkv": spec.get("num_key_value_heads", H),
        "dh": spec.get("head_dim", d // H), "f": spec["intermediate_size"],
        "V": spec["vocab_size"],
        "Vp": -(-spec["vocab_size"] // 256) * 256,  # the program's padding
    }


def vocab(spec: Dict):
    """(vocabulary, the program's padded vocabulary)."""
    m = dims(spec)
    return m["V"], m["Vp"]


def is_layernorm(spec: Dict) -> bool:
    return spec.get("norm_type") == "layer_norm"


def uses_bias(spec: Dict) -> bool:
    return bool(spec.get("use_bias", False))


def tied(spec: Dict) -> bool:
    return bool(spec["tie_word_embeddings"])


def scalings(spec: Dict) -> Dict[str, float]:
    """MiniCPM's muP scalings (1 where the architecture has none):
    embedding output x ``emb``, each residual branch x ``branch``, final
    hidden state / ``logit_div`` before the head."""
    L, d = spec["num_hidden_layers"], spec["hidden_size"]
    return {
        "emb": float(spec.get("scale_emb", 1.0)),
        "branch": (spec["scale_depth"] / math.sqrt(L)
                   if "scale_depth" in spec else 1.0),
        "logit_div": (d / spec["dim_model_base"]
                      if "dim_model_base" in spec else 1.0),
    }


def layer_kinds(spec: Dict):
    return ["dense"] * spec["num_hidden_layers"]


def layer_shapes(spec: Dict, kind: str = "dense") -> Dict[str, tuple]:
    """Per-layer tensors, named as the published checkpoints name them,
    stored as ``[in, out]`` matrices."""
    m = dims(spec)
    d, H, Hkv, dh, f = m["d"], m["H"], m["Hkv"], m["dh"], m["f"]
    s = {
        "input_layernorm.weight": (d,),
        "self_attn.q_proj.weight": (d, H * dh),
        "self_attn.k_proj.weight": (d, Hkv * dh),
        "self_attn.v_proj.weight": (d, Hkv * dh),
        "self_attn.o_proj.weight": (H * dh, d),
        "post_attention_layernorm.weight": (d,),
    }
    if ACTS[spec["hidden_act"]] == "silu":
        s.update({"mlp.gate_proj.weight": (d, f), "mlp.up_proj.weight": (d, f),
                  "mlp.down_proj.weight": (f, d)})
    else:
        s.update({"mlp.c_fc.weight": (d, f), "mlp.c_proj.weight": (f, d)})
    if is_layernorm(spec):
        s["input_layernorm.bias"] = (d,)
        s["post_attention_layernorm.bias"] = (d,)
    if uses_bias(spec):
        s.update({"self_attn.q_proj.bias": (H * dh,),
                  "self_attn.k_proj.bias": (Hkv * dh,),
                  "self_attn.v_proj.bias": (Hkv * dh,),
                  "self_attn.o_proj.bias": (d,)})
        if "mlp.c_fc.weight" in s:
            s.update({"mlp.c_fc.bias": (f,), "mlp.c_proj.bias": (d,)})
    return s


def global_shapes(spec: Dict) -> Dict[str, tuple]:
    m = dims(spec)
    out = {"embed_tokens.weight": (m["V"], m["d"]), "norm.weight": (m["d"],)}
    if is_layernorm(spec):
        out["norm.bias"] = (m["d"],)
    if not tied(spec):
        out["lm_head.weight"] = (m["d"], m["V"])
    return out


BRANCH_OUT = ("self_attn.o_proj.weight", "mlp.down_proj.weight",
              "mlp.c_proj.weight")


def role(name: str) -> str:
    """How :func:`model._draw` draws the tensor.  The output projection's
    bias is zero: the program's attention has no term for it
    (``assumed``)."""
    if name == "self_attn.o_proj.bias":
        return "zero"
    if "layernorm.weight" in name:
        return "norm"
    if name == "norm.weight":
        return "final_norm"
    if name.endswith(".bias"):
        return "bias"
    if name == "embed_tokens.weight":
        return "embed"
    return "branch_out" if name in BRANCH_OUT else "matrix"


def param_count(spec: Dict) -> Dict[str, int]:
    """Parameters by role: ``layers`` (all layers' matrices), ``head`` (the
    output projection), ``embed`` (the input table, no FLOPs)."""
    m = dims(spec)
    per = sum(math.prod(s) for n, s in layer_shapes(spec).items()
              if n.endswith(".weight") and len(s) == 2)
    return {"layers": m["L"] * per, "head": m["d"] * m["V"],
            "embed": m["V"] * m["d"]}


# ---------------------------------------------------------------------------
# The program's configuration and parameter tree
# ---------------------------------------------------------------------------


def check_supported(spec: Dict) -> None:
    """Refuse what the program would compute differently from the
    published architecture."""
    eps = spec.get("rms_norm_eps", spec.get("norm_epsilon",
                                            spec.get("layer_norm_epsilon")))
    if eps != PROGRAM_EPS:
        raise ValueError(f"norm epsilon {eps} != the program's {PROGRAM_EPS}")
    if spec["hidden_act"] not in ACTS:
        raise ValueError(f"hidden_act {spec['hidden_act']!r} not supported")
    if spec.get("rope_scaling"):
        raise ValueError("rope_scaling is not supported")
    window = spec.get("sliding_window")
    if window is not None and spec["serving"]["max_len"] > window:
        raise ValueError(
            f"max_len {spec['serving']['max_len']} exceeds the sliding window "
            f"{window}: the program's full attention would differ")


def model_config(spec: Dict):
    from repro.configs.base import ModelConfig

    check_supported(spec)
    m = dims(spec)
    return ModelConfig(
        name=spec["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["Hkv"], d_head=m["dh"], d_ff=m["f"],
        vocab_size=m["V"], rope_theta=float(spec["rope_theta"]),
        norm="layernorm" if is_layernorm(spec) else "rmsnorm",
        act=ACTS[spec["hidden_act"]], qkv_bias=uses_bias(spec),
        tie_embeddings=tied(spec), dtype=MB.SERVE_DTYPES[spec["serving"]["dtype"]],
        block=spec["serving"]["page_size"],
    )


def _scaled(spec, x, s):
    dt = MB.SERVE_DTYPES[spec["serving"]["dtype"]]
    return x if s == 1.0 else (x.astype(jnp.float32) * s).astype(dt)


def _norm_params(prefix, src):
    out = {"w": src[prefix + ".weight"]}
    if prefix + ".bias" in src:
        out["b"] = src[prefix + ".bias"]
    return out


def program_segment(spec: Dict, kind: str, stacked: Dict) -> Dict:
    """One segment's stacked layers (leading axis: its layers)."""
    m = dims(spec)
    branch = scalings(spec)["branch"]
    dt = MB.SERVE_DTYPES[spec["serving"]["dtype"]]
    n = stacked["input_layernorm.weight"].shape[0]
    attn = {"wq": stacked["self_attn.q_proj.weight"],
            "wk": stacked["self_attn.k_proj.weight"],
            "wv": stacked["self_attn.v_proj.weight"],
            "wo": _scaled(spec, stacked["self_attn.o_proj.weight"], branch)}
    if uses_bias(spec):
        attn.update(bq=stacked["self_attn.q_proj.bias"],
                    bk=stacked["self_attn.k_proj.bias"],
                    bv=stacked["self_attn.v_proj.bias"])
    if "mlp.gate_proj.weight" in stacked:
        ffn = {"w_gate": stacked["mlp.gate_proj.weight"],
               "w_up": stacked["mlp.up_proj.weight"],
               "w_down": _scaled(spec, stacked["mlp.down_proj.weight"], branch)}
    else:
        zeros = jnp.zeros((n, m["f"]), dt)
        ffn = {"w_up": stacked["mlp.c_fc.weight"],
               "b_up": stacked.get("mlp.c_fc.bias", zeros),
               "w_down": _scaled(spec, stacked["mlp.c_proj.weight"], branch),
               "b_down": _scaled(spec, stacked.get("mlp.c_proj.bias",
                                                   zeros[:, :m["d"]]), branch)}
    return {"ln1": _norm_params("input_layernorm", stacked), "attn": attn,
            "ln2": _norm_params("post_attention_layernorm", stacked), "ffn": ffn}


def program_globals(spec: Dict, g: Dict) -> Dict:
    """The embedding, final norm and head, padded to the program's vocab."""
    m = dims(spec)
    sc = scalings(spec)
    pad = m["Vp"] - m["V"]
    embed = g["embed_tokens.weight"]
    final = _norm_params("norm", g)
    if tied(spec):
        # one table serves the input (x scale_emb) and the head (/ logit_div):
        # scale the table by scale_emb and the final norm by 1 / both
        embed = _scaled(spec, embed, sc["emb"])
        head_div = sc["emb"] * sc["logit_div"]
    else:
        if sc["emb"] != 1.0:
            raise ValueError("scale_emb with untied embeddings is not folded")
        head_div = sc["logit_div"]
    final = {k: _scaled(spec, v, 1.0 / head_div) for k, v in final.items()}
    tree = {"embed": jnp.pad(embed, ((0, pad), (0, 0))), "final_norm": final}
    if not tied(spec):
        tree["lm_head"] = jnp.pad(g["lm_head.weight"], ((0, 0), (0, pad)))
    return tree


# ---------------------------------------------------------------------------
# The plain float32 reference
# ---------------------------------------------------------------------------


def norm(spec, x, w, b=None):
    if is_layernorm(spec):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + spec["norm_epsilon"]) * w + b
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + spec["rms_norm_eps"]) * w


def rope(x, pos, theta):
    """x (S, heads, dh); rotate the two halves of each head by pos * freq."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]  # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(spec, g: Dict, toks):
    """The input rows of one sequence's tokens, float32."""
    return g["embed_tokens.weight"][toks].astype(jnp.float32) * scalings(spec)["emb"]


def layer(spec, kind: str, W: Dict, h, quant):
    """One decoder layer over one sequence h (S, d), float32."""
    m = dims(spec)
    f32 = {n: x.astype(jnp.float32) for n, x in W.items()}
    S = h.shape[0]
    pos = jnp.arange(S)
    branch = scalings(spec)["branch"]
    x = norm(spec, h, f32["input_layernorm.weight"], f32.get("input_layernorm.bias"))

    def proj(name, a):
        y = R.matmul(a, f32[f"{name}.weight"], quant)
        return y + f32[f"{name}.bias"] if f"{name}.bias" in f32 else y

    q = proj("self_attn.q_proj", x).reshape(S, m["H"], m["dh"])
    k = proj("self_attn.k_proj", x).reshape(S, m["Hkv"], m["dh"])
    v = proj("self_attn.v_proj", x).reshape(S, m["Hkv"], m["dh"])
    theta = float(spec["rope_theta"])
    a = R.attention(rope(q, pos, theta), rope(k, pos, theta), v, quant,
                    window=spec.get("sliding_window"))
    h = h + branch * proj("self_attn.o_proj", a.reshape(S, -1))
    x = norm(spec, h, f32["post_attention_layernorm.weight"],
             f32.get("post_attention_layernorm.bias"))
    if "mlp.gate_proj.weight" in f32:
        y = proj("mlp.down_proj",
                 jax.nn.silu(proj("mlp.gate_proj", x)) * proj("mlp.up_proj", x))
    else:
        y = proj("mlp.c_proj", jax.nn.gelu(proj("mlp.c_fc", x), approximate=True))
    return h + branch * y


def head(spec, g: Dict, h, quant):
    """Logits of hidden rows h (rows, d), float32."""
    div = scalings(spec)["logit_div"]
    g = {n: x.astype(jnp.float32) for n, x in g.items()}
    x = norm(spec, h, g["norm.weight"], g.get("norm.bias")) / div
    w = g["embed_tokens.weight"].T if tied(spec) else g["lm_head.weight"]
    return R.matmul(x, w, quant)


# ---------------------------------------------------------------------------
# Work counts of the traced window
# ---------------------------------------------------------------------------


def decode_kernel(spec: Dict):
    """(name, calls per decode step): the paged-decode kernel runs once per
    layer."""
    return KERNEL, dims(spec)["L"]


def decode_work(spec: Dict, decode_ctx):
    """(bytes, flops) of the kernel over the recorded decode calls; each
    entry of ``decode_ctx`` lists the active slots' context lengths.  For
    each call and layer: the K and V of every active slot's context, plus
    its query and output, read or written once; and 4 x heads x head size
    FLOPs per context token."""
    m = dims(spec)
    kv_row = 2 * m["Hkv"] * m["dh"] * BYTES  # K and V of one token, one layer
    qo_row = 2 * m["H"] * m["dh"] * BYTES  # query in, output out
    ctx_tokens = sum(sum(c) for c in decode_ctx)
    slots = sum(len(c) for c in decode_ctx)
    nbytes = m["L"] * (ctx_tokens * kv_row + slots * qo_row)
    flops = m["L"] * 4 * m["H"] * m["dh"] * ctx_tokens
    return nbytes, flops


def step_flops(spec: Dict, decode_ctx, chunks):
    """Model FLOPs of the decode calls and prefill chunks ``(offset,
    tokens, final)``.  A decode token counts 2 x the layers' and the head's
    matrix parameters plus attention over its context (4 x layers x heads
    x head size per context token); a chunk counts its real prompt tokens
    only (no bucket padding): 2 x the layers' parameters per token, causal
    attention over its offset, and the head once, for the first token of
    the answer."""
    m = dims(spec)
    p = param_count(spec)
    att = 4 * m["L"] * m["H"] * m["dh"]  # per (query, key) pair
    dec = sum(len(c) * 2 * (p["layers"] + p["head"]) + att * sum(c)
              for c in decode_ctx)
    pre = sum(2 * p["layers"] * n + att * (n * off + n * (n + 1) // 2)
              + (2 * p["head"] if final else 0) for off, n, final in chunks)
    return dec + pre
