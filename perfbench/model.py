"""Model configurations as the benchmark runs them, and their weights.

A configuration file (``perfbench/configs/<name>.json``) holds the
published ``config.json`` keys of the model at the top level, as they are
run, plus:

* ``serving``: the engine geometry (bf16, page size, slots, pool pages,
  mesh), ``rehearsal``: the same keys cut to a CPU-sized smoke model;
* ``assumed``: values the published file does not state, each with why;
* ``published``: the published value of every key listed in ``reduced``.

Weights are the benchmark's own: every tensor is drawn from ``--seed`` by
name and layer (:func:`layer_weights`, :func:`global_weights`), so the
plain reference (:mod:`reference`) regenerates exactly what the program
was given, layer by layer, without taking anything from the program.
:func:`program_params` converts them into ``repro.models``' parameter tree
inside one jitted call, the way a checkpoint loader would, folding the
published scalings that the program's model has no term for (MiniCPM's
``scale_emb``, ``scale_depth``, ``dim_model_base``) into the weights.
"""
from __future__ import annotations

import json
import math
import zlib
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# bf16 is what the engine serves; the reference upcasts the same values
SERVE_DTYPES = {"bfloat16": jnp.bfloat16}
# what the program's norms and activations compute (repro.models.common)
PROGRAM_EPS = 1e-5
ACTS = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}


def load(path: Path, rehearse: bool = False) -> Dict:
    """The configuration file, with ``rehearsal`` applied when asked."""
    spec = json.loads(Path(path).read_text())
    if rehearse:
        reh = spec["rehearsal"]
        spec = {**spec, **reh, "serving": {**spec["serving"], **reh["serving"]}}
    return spec


def seed31(seed: int) -> int:
    """A 31-bit seed for ``jax.random`` (``--seed`` may exceed 32 bits)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


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


def layer_shapes(spec: Dict) -> Dict[str, tuple]:
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


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def _key(seed: int, name: str, layer):
    k = jax.random.fold_in(jax.random.PRNGKey(seed31(seed)), zlib.crc32(name.encode()))
    return jax.random.fold_in(k, layer)


BRANCH_OUT = ("self_attn.o_proj.weight", "mlp.down_proj.weight",
              "mlp.c_proj.weight")


def _draw(spec: Dict, seed: int, name: str, shape: tuple, layer):
    """One tensor: matrices N(0, 1/fan_in), the embedding N(0, ``embed_std``),
    norm weights 1 + N(0, 0.1), biases N(0, 0.02).  The file's ``init``
    scales the embedding, the residual branches' output projections
    (``branch_gain``) and the final norm (``final_norm_gain``), so that
    the random model's next-token distribution is peaked and is not a copy
    of its input token (see ``PERF.md``).  The output projection's bias is
    zero: the program's attention has no term for it (``assumed``)."""
    dt = SERVE_DTYPES[spec["serving"]["dtype"]]
    init = spec.get("init", {})
    if name == "self_attn.o_proj.bias":
        return jnp.zeros(shape, dt)
    x = jax.random.normal(_key(seed, name, layer), shape, jnp.float32)
    if "layernorm.weight" in name:
        x = 1.0 + 0.1 * x
    elif name == "norm.weight":
        x = init.get("final_norm_gain", 1.0) * (1.0 + 0.1 * x)
    elif name.endswith(".bias"):
        x = 0.02 * x
    elif name == "embed_tokens.weight":
        x = x * init.get("embed_std", spec["initializer_range"])
    else:
        x = x * shape[0] ** -0.5 * (init.get("branch_gain", 1.0)
                                    if name in BRANCH_OUT else 1.0)
    return x.astype(dt)


def layer_weights(spec: Dict, seed: int, layer) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s tensors (``layer`` may be traced)."""
    return {n: _draw(spec, seed, n, s, layer)
            for n, s in layer_shapes(spec).items()}


def global_weights(spec: Dict, seed: int) -> Dict[str, jnp.ndarray]:
    m = dims(spec)
    out = {"embed_tokens.weight": _draw(spec, seed, "embed_tokens.weight",
                                        (m["V"], m["d"]), 0),
           "norm.weight": _draw(spec, seed, "norm.weight", (m["d"],), 0)}
    if is_layernorm(spec):
        out["norm.bias"] = _draw(spec, seed, "norm.bias", (m["d"],), 0)
    if not tied(spec):
        out["lm_head.weight"] = _draw(spec, seed, "lm_head.weight",
                                      (m["d"], m["V"]), 0)
    return out


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
    """``repro``'s ``ModelConfig`` for this file (dense family)."""
    from repro.configs.base import ModelConfig

    check_supported(spec)
    m = dims(spec)
    return ModelConfig(
        name=spec["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["Hkv"], d_head=m["dh"], d_ff=m["f"],
        vocab_size=m["V"], rope_theta=float(spec["rope_theta"]),
        norm="layernorm" if is_layernorm(spec) else "rmsnorm",
        act=ACTS[spec["hidden_act"]], qkv_bias=uses_bias(spec),
        tie_embeddings=tied(spec), dtype=SERVE_DTYPES[spec["serving"]["dtype"]],
        block=spec["serving"]["page_size"],
    )


def _program_tree(spec: Dict, seed: int):
    """Our weights in the program's layout (called inside one jit)."""
    m = dims(spec)
    sc = scalings(spec)
    dt = SERVE_DTYPES[spec["serving"]["dtype"]]
    g = global_weights(spec, seed)
    stacked = jax.lax.map(lambda l: layer_weights(spec, seed, l),
                          jnp.arange(m["L"]))

    def scaled(x, s):
        return x if s == 1.0 else (x.astype(jnp.float32) * s).astype(dt)

    def norm(prefix, src):
        out = {"w": src[prefix + ".weight"]}
        if prefix + ".bias" in src:
            out["b"] = src[prefix + ".bias"]
        return out

    attn = {"wq": stacked["self_attn.q_proj.weight"],
            "wk": stacked["self_attn.k_proj.weight"],
            "wv": stacked["self_attn.v_proj.weight"],
            "wo": scaled(stacked["self_attn.o_proj.weight"], sc["branch"])}
    if uses_bias(spec):
        attn.update(bq=stacked["self_attn.q_proj.bias"],
                    bk=stacked["self_attn.k_proj.bias"],
                    bv=stacked["self_attn.v_proj.bias"])
    if "mlp.gate_proj.weight" in stacked:
        ffn = {"w_gate": stacked["mlp.gate_proj.weight"],
               "w_up": stacked["mlp.up_proj.weight"],
               "w_down": scaled(stacked["mlp.down_proj.weight"], sc["branch"])}
    else:
        zeros = jnp.zeros((m["L"], m["f"]), dt)
        ffn = {"w_up": stacked["mlp.c_fc.weight"],
               "b_up": stacked.get("mlp.c_fc.bias", zeros),
               "w_down": scaled(stacked["mlp.c_proj.weight"], sc["branch"]),
               "b_down": scaled(stacked.get("mlp.c_proj.bias", zeros[:, :m["d"]]),
                                sc["branch"])}
    pad = m["Vp"] - m["V"]
    embed = g["embed_tokens.weight"]
    final = norm("norm", g)
    if tied(spec):
        # one table serves the input (x scale_emb) and the head (/ logit_div):
        # scale the table by scale_emb and the final norm by 1 / both
        embed = scaled(embed, sc["emb"])
        head_div = sc["emb"] * sc["logit_div"]
    else:
        if sc["emb"] != 1.0:
            raise ValueError("scale_emb with untied embeddings is not folded")
        head_div = sc["logit_div"]
    final = {k: scaled(v, 1.0 / head_div) for k, v in final.items()}
    tree = {
        "embed": jnp.pad(embed, ((0, pad), (0, 0))),
        "final_norm": final,
        "seg0": {"ln1": norm("input_layernorm", stacked), "attn": attn,
                 "ln2": norm("post_attention_layernorm", stacked), "ffn": ffn},
    }
    if not tied(spec):
        tree["lm_head"] = jnp.pad(g["lm_head.weight"], ((0, 0), (0, pad)))
    return tree


def program_params(spec: Dict, cfg, seed: int, shardings=None):
    """The program's parameters, made on the device in one jitted call.

    The tree is checked against the shapes ``repro.models.model.init_params``
    declares (only shapes are read from it), so a change of the program's
    layout fails here and not in a wrong answer."""
    from repro.models import model as M

    want = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    fn = jax.jit(lambda: _program_tree(spec, seed), out_shardings=shardings)
    got = jax.eval_shape(fn)
    if jax.tree.structure(got) != jax.tree.structure(want):
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{jax.tree.structure(got)}\n{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"parameter {a.shape} {a.dtype} != {b.shape} {b.dtype}")
    return fn()


def param_count(spec: Dict) -> Dict[str, int]:
    """Parameters by role: ``layers`` (all layers' matrices), ``head`` (the
    output projection), ``embed`` (the input table, no FLOPs)."""
    m = dims(spec)
    per = sum(math.prod(s) for n, s in layer_shapes(spec).items()
              if n.endswith(".weight") and len(s) == 2)
    return {"layers": m["L"] * per, "head": m["d"] * m["V"],
            "embed": m["V"] * m["d"]}
