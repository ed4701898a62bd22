"""Activation sharding constraints, mesh-agnostic model code.

Model code cannot see the mesh at trace time (the ambient abstract mesh is
empty under a plain ``with mesh:`` block), so the launcher installs a
:class:`ShardPolicy` around tracing and the model calls :func:`constrain`
with *logical* dims ("dp" = batch, "tp" = model-parallel).  Outside a policy
(unit tests, single device) it is a no-op.

These constraints are what keep GSPMD from replicating the big activations
(e.g. (B, S, vocab) logits) when a ZeRO-sharded weight's storage layout
conflicts with the activation layout.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P


def abstract_mesh(
    axis_sizes: Sequence[int], axis_names: Sequence[str]
) -> AbstractMesh:
    """A :class:`jax.sharding.AbstractMesh` for mesh-shape validation
    (tests, launch dry-runs) that needs no devices."""
    return AbstractMesh(tuple(int(s) for s in axis_sizes), tuple(axis_names))


@dataclasses.dataclass(frozen=True)
class ShardPolicy:
    dp_axes: tuple
    tp_axis: str
    dp_size: int
    tp_size: int
    ep_axes: tuple = ()   # innermost-data x model (full expert parallelism)
    ep_size: int = 1
    # the concrete Mesh the policy was derived from — needed by trace-time
    # consumers that must name a mesh explicitly (shard_map around the
    # pallas paged-decode kernels).  None under AbstractMesh validation.
    mesh: object = dataclasses.field(default=None, compare=False)


_CURRENT: Optional[ShardPolicy] = None


def current() -> Optional[ShardPolicy]:
    return _CURRENT


@contextlib.contextmanager
def policy(mesh):
    """Install the shard policy derived from ``mesh`` for the trace scope."""
    global _CURRENT
    names = mesh.axis_names
    dp = tuple(n for n in names if n != "model")
    tp = "model" if "model" in names else ""
    prev = _CURRENT
    ep = (dp[-1], tp) if (dp and tp) else ()
    _CURRENT = ShardPolicy(
        dp_axes=dp,
        tp_axis=tp,
        dp_size=int(np.prod([mesh.shape[a] for a in dp])) if dp else 1,
        tp_size=mesh.shape[tp] if tp else 1,
        ep_axes=ep,
        ep_size=(mesh.shape[dp[-1]] * mesh.shape[tp]) if ep else 1,
        mesh=mesh if isinstance(mesh, jax.sharding.Mesh) else None,
    )
    try:
        yield _CURRENT
    finally:
        _CURRENT = prev


def traced_under(mesh, fn):
    """Wrap ``fn`` so its BODY runs under ``with mesh, policy(mesh)``.

    ``jax.jit`` traces lazily at the first call, so a mesh/policy context
    installed around jit *construction* is gone by trace time and every
    :func:`constrain` inside the model silently no-ops.  Wrapping the
    function body instead puts the context where tracing actually happens —
    the engine's sharded step closures are all built through here.
    """
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with mesh, policy(mesh):
            return fn(*args, **kwargs)

    return run


def constrain(x, dims: Sequence[Optional[str]]):
    """Apply with_sharding_constraint using logical dims.

    dims entries: "dp" (batch axes), "tp" (model axis), "dp+tp" (flattened,
    for pure sequence parallelism), or None.  Any entry whose size doesn't
    divide is silently dropped (the rules must hold for every arch).
    """
    pol = _CURRENT
    if pol is None or (pol.dp_size == 1 and pol.tp_size == 1):
        return x
    spec = []
    for dim, size in zip(dims, x.shape):
        if dim == "dp" and pol.dp_axes and size % pol.dp_size == 0:
            spec.append(pol.dp_axes if len(pol.dp_axes) > 1 else pol.dp_axes[0])
        elif dim == "tp" and pol.tp_axis and size >= pol.tp_size:
            # GSPMD pads uneven dims; vocab (e.g. 51865) still shards.
            spec.append(pol.tp_axis)
        elif dim == "ep":
            # expert dim: full (data x model) EP if it divides, else TP
            # (uneven is tolerated: dropping the constraint entirely was
            # measured strictly worse — GSPMD replicates the dispatch buffer:
            # granite prefill temp 24 -> 120 GiB without it).
            if pol.ep_axes and size % pol.ep_size == 0:
                spec.append(pol.ep_axes)
            elif pol.tp_axis and size >= pol.tp_size:
                spec.append(pol.tp_axis)
            else:
                spec.append(None)
        elif dim == "dp+tp" and pol.tp_axis and pol.dp_axes and (
            size % (pol.dp_size * pol.tp_size) == 0
        ):
            spec.append(pol.dp_axes + (pol.tp_axis,))
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
