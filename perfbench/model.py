"""Model configurations as the benchmark runs them, and their weights.

A configuration file (``perfbench/configs/<name>.json``) holds the
published ``config.json`` keys of the model at the top level, as they are
run, plus:

* ``family`` (optional, ``dense`` where absent): the architecture, whose
  module ``perfbench/families/<family>.py`` (:func:`family`) holds all
  that the benchmark knows of it;
* ``serving``: the engine geometry (bf16, page size, slots, pool pages,
  mesh), ``rehearsal``: the same keys cut to a CPU-sized smoke model;
* ``assumed``: values the published file does not state, each with why;
* ``published``: the published value of every key listed in ``reduced``.

This module reads no architecture key.  A family module gives:

* ``check_supported(spec)`` and ``model_config(spec)``: the program's
  ``ModelConfig``, refusing what the program would compute differently;
* ``layer_kinds(spec)``: each layer's kind, in order; consecutive layers
  of one kind are one segment of the program's parameter tree
  (``seg0``, ``seg1``, ...), as ``repro.models`` stacks them;
* ``layer_shapes(spec, kind)``, ``global_shapes(spec)``: the tensors,
  named as the published checkpoints name them, and ``role(name)``: how
  :func:`_draw` draws each (``zero``, ``norm``, ``final_norm``, ``bias``,
  ``embed``, ``matrix``, ``branch_out``);
* ``program_segment(spec, kind, stacked)``, ``program_globals(spec, g)``:
  those tensors in the program's layout (called inside one jit);
* ``vocab(spec)``, ``embed``, ``layer``, ``head``: the plain float32
  reference's math (:mod:`reference` keeps the blocking, the precisions
  and the scoring);
* ``decode_kernel(spec)``: the decode kernel's name in the device trace
  and its calls per decode step; ``decode_work(spec, decode_ctx)``: that
  kernel's algorithmic (bytes, FLOPs); ``step_flops(spec, decode_ctx,
  chunks)``: the model FLOPs of the decode calls and prefill chunks.

Weights are the benchmark's own: every tensor is drawn from ``--seed`` by
name and layer (:func:`layer_weights`, :func:`global_weights`), so the
plain reference (:mod:`reference`) regenerates exactly what the program
was given, layer by layer, without taking anything from the program.
:func:`program_params` converts them into ``repro.models``' parameter tree
inside one jitted call, the way a checkpoint loader would.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import zlib
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FAMILIES = Path(__file__).resolve().parent / "families"
DEFAULT_FAMILY = "dense"
# bf16 is what the engine serves; the reference upcasts the same values
SERVE_DTYPES = {"bfloat16": jnp.bfloat16}


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
# The architecture's module
# ---------------------------------------------------------------------------


def family(spec: Dict):
    """The family module of this configuration."""
    return load_family(spec.get("family", DEFAULT_FAMILY))


def load_family(name: str):
    """``<FAMILIES>/<name>.py``, loaded once."""
    return _module_at(FAMILIES / f"{name}.py")


@functools.lru_cache(maxsize=None)
def _module_at(path: Path):
    if not path.is_file():
        raise FileNotFoundError(
            f"no module for the family {path.stem!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"family_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(spec: Dict):
    """``repro``'s ``ModelConfig`` for this file."""
    return family(spec).model_config(spec)


def segments(spec: Dict) -> List[Tuple[str, int, int]]:
    """(kind, first layer, layers) of each run of consecutive layers of one
    kind: the program's segments."""
    out: List[Tuple[str, int, int]] = []
    for i, kind in enumerate(family(spec).layer_kinds(spec)):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, i, 1))
    return out


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def _key(seed: int, name: str, layer):
    k = jax.random.fold_in(jax.random.PRNGKey(seed31(seed)), zlib.crc32(name.encode()))
    return jax.random.fold_in(k, layer)


def _draw(spec: Dict, seed: int, name: str, shape: tuple, layer, role: str):
    """One tensor, by its role: matrices N(0, 1/fan_in), the embedding
    N(0, ``embed_std``), norm weights 1 + N(0, 0.1), biases N(0, 0.02),
    ``zero`` all zeros.  The file's ``init`` scales the embedding, the
    residual branches' output projections (``branch_out``, by
    ``branch_gain``) and the final norm (``final_norm_gain``), so that the
    random model's next-token distribution is peaked and is not a copy of
    its input token (see ``PERF.md``)."""
    dt = SERVE_DTYPES[spec["serving"]["dtype"]]
    init = spec.get("init", {})
    if role == "zero":
        return jnp.zeros(shape, dt)
    x = jax.random.normal(_key(seed, name, layer), shape, jnp.float32)
    if role == "norm":
        x = 1.0 + 0.1 * x
    elif role == "final_norm":
        x = init.get("final_norm_gain", 1.0) * (1.0 + 0.1 * x)
    elif role == "bias":
        x = 0.02 * x
    elif role == "embed":
        x = x * init.get("embed_std", spec["initializer_range"])
    elif role in ("matrix", "branch_out"):
        x = x * shape[-2] ** -0.5 * (init.get("branch_gain", 1.0)
                                     if role == "branch_out" else 1.0)
    else:
        raise ValueError(f"{name}: unknown role {role!r}")
    return x.astype(dt)


def layer_weights(spec: Dict, seed: int, layer, kind: Optional[str] = None
                  ) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s tensors (``layer`` may be traced, so a family with
    layers of several kinds is told the layer's ``kind``)."""
    fam = family(spec)
    if kind is None:
        kinds = set(fam.layer_kinds(spec))
        if len(kinds) != 1:
            raise ValueError(f"layers of the kinds {sorted(kinds)}: name the "
                             f"kind of layer {layer}")
        (kind,) = kinds
    return {n: _draw(spec, seed, n, s, layer, fam.role(n))
            for n, s in fam.layer_shapes(spec, kind).items()}


def global_weights(spec: Dict, seed: int) -> Dict[str, jnp.ndarray]:
    fam = family(spec)
    return {n: _draw(spec, seed, n, s, 0, fam.role(n))
            for n, s in fam.global_shapes(spec).items()}


# ---------------------------------------------------------------------------
# The program's parameter tree
# ---------------------------------------------------------------------------


def _program_tree(spec: Dict, seed: int):
    """Our weights in the program's layout (called inside one jit)."""
    fam = family(spec)
    tree = fam.program_globals(spec, global_weights(spec, seed))
    for si, (kind, first, n) in enumerate(segments(spec)):
        stacked = jax.lax.map(lambda l: layer_weights(spec, seed, l, kind),
                              jnp.arange(first, first + n))
        tree[f"seg{si}"] = fam.program_segment(spec, kind, stacked)
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
