"""The serving engine's jitted-step inventory, as AOT step specs.

Builds a :class:`StepSpec` per hot-path jit signature of the continuous
engine — the decode step, one prefill chunk per shape in the engine's
chunk-shape closure, the COW page copy, and the unchunked prefill install —
from the same callables the runtime jits
(:func:`repro.serve.engine.jitted_step_fns`).  The decode and COW specs
compile the kernelized (pallas) hot path by default and keep probe-less
``*_reference`` twins for the jnp oracle; see
:attr:`InventoryConfig.backend`.  Arguments are
``ShapeDtypeStruct`` pytrees at a smoke-sized geometry (the same shapes
``tests/test_sanitize.py`` exercises), so everything here lowers and
compiles on CPU without touching real buffers; only the RPJ104 probes
(declared here, run by the rules) allocate real smoke-sized arrays.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.analysis.jaxcheck.harness import ProbeSet, StepSpec
from repro.models import model as M
from repro.serve import engine as E


@dataclasses.dataclass(frozen=True)
class InventoryConfig:
    """Geometry the inventory compiles at (smoke-sized; shapes only)."""

    arch: str = "minicpm-2b"
    max_seqs: int = 2
    max_len: int = 64
    page_size: int = 8
    #: prompt lengths the RPJ104 closure check plans chunks for — a short
    #: prompt (ragged bucket), an exact chunk, and a multi-chunk prompt
    probe_prompt_lens: Tuple[int, ...] = (3, 8, 13)
    #: decode/COW execution path the PRIMARY ``decode_step`` / ``cow_copy``
    #: specs compile (``cfg.decode_backend``).  The default is the
    #: kernelized pallas path — the serving hot loop this analysis exists
    #: to budget: streaming pages through the fused kernel removes the
    #: whole-history ``k_pages[layer, page_table]`` gather from the lowered
    #: step, which is exactly the RPJ102 ``max_gather_bytes`` drop the paper's
    #: arrangement argument predicts.  The jnp oracle path stays gated as
    #: ``decode_step_reference`` / ``cow_copy_reference``.
    backend: str = "pallas"
    #: ``DxM`` mesh spec (e.g. ``"1x2"``): compile the SHARDED inventory —
    #: every spec carries the engine's real in/out shardings (resident-TP
    #: params, head-sharded pools, replicated host-fed inputs) and traces
    #: under the mesh, so RPJ101 proves donation survives sharding and
    #: RPJ106 budgets the partitioned module's collective traffic.  Needs
    #: D*M visible devices; empty = single-device inventory.
    mesh: str = ""


@dataclasses.dataclass
class Inventory:
    """Everything jaxcheck analyzes: step specs + the RPJ104 closure."""

    cfg: Any
    geometry: InventoryConfig
    specs: List[StepSpec]
    chunk_size: int
    chunk_closure: Tuple[int, ...]
    chunk_plans: Dict[int, List[int]]  # probe prompt len -> planned shapes


def model_config(inv: InventoryConfig):
    cfg = C.get_config(inv.arch, smoke=True, dtype=jnp.float32)
    return dataclasses.replace(cfg, block=inv.page_size)


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


class _ProbeArena:
    """Lazily-built real smoke-sized state for the RPJ104 probes.

    Params are never donated by any step, so one copy is shared across all
    probe calls; the cache pool IS donated (consumed) by every step, so
    :meth:`fresh_caches` builds a new pool per call.
    """

    def __init__(self, cfg, inv: InventoryConfig, num_pages: int):
        self.cfg = cfg
        self.inv = inv
        self.num_pages = num_pages
        self._params = None

    def params(self):
        if self._params is None:
            self._params = M.init_params(self.cfg, jax.random.PRNGKey(0))
        return self._params

    def fresh_caches(self):
        return M.init_paged_cache(
            self.cfg, self.inv.max_seqs, self.num_pages,
            self.inv.page_size, self.inv.max_len,
        )


def serving_inventory(inv: Optional[InventoryConfig] = None) -> Inventory:
    inv = inv or InventoryConfig()
    cfg = model_config(inv)
    # two step tables: the kernelized hot path the budgets gate (pallas by
    # default, see InventoryConfig.backend) and the jnp oracle it must keep
    # matching.  Only decode/COW dispatch on decode_backend; prefill chunks
    # and install lower identically, so they come from the reference table.
    cfg_hot = dataclasses.replace(cfg, decode_backend=inv.backend)
    steps = E.jitted_step_fns(cfg)
    steps_hot = E.jitted_step_fns(cfg_hot)
    max_pages = max(1, -(-inv.max_len // inv.page_size))
    num_pages = inv.max_seqs * max_pages + 1
    B = inv.max_seqs
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(
        lambda: M.init_paged_cache(
            cfg, inv.max_seqs, num_pages, inv.page_size, inv.max_len
        )
    )
    chunk_size = E.resolve_chunk_size(cfg, inv.page_size)
    closure = E.chunk_shape_set(cfg, chunk_size)
    plans = {
        n: E.chunk_plan(cfg, chunk_size, n) for n in inv.probe_prompt_lens
    }
    arena = _ProbeArena(cfg, inv, num_pages)
    specs: List[StepSpec] = []

    # -- decode step: one signature, forever -------------------------------
    # the primary spec compiles the kernelized path (inv.backend); the
    # ``_reference`` twin keeps the jnp oracle lowering inventoried so its
    # gather/temp footprint stays visible next to the kernel's.
    decode_fn, decode_donate = steps_hot["decode_step"]
    decode_args = (
        params, caches, _sds((B, 1), jnp.int32), _sds((B,), jnp.int32),
        _sds((B, max_pages), jnp.int32), _sds((B,), jnp.bool_),
    )

    def _decode_args(_key):
        return (
            arena.params(), arena.fresh_caches(),
            jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, max_pages), jnp.int32), jnp.zeros((B,), bool),
        )

    specs.append(StepSpec(
        name="decode_step",
        fn=decode_fn,
        args=decode_args,
        donate_argnums=decode_donate,
        probe=ProbeSet(keys=(0, 1), make_args=_decode_args,
                       expected_entries=1),
    ))
    specs.append(StepSpec(
        name="decode_step_reference",
        fn=steps["decode_step"][0],
        args=decode_args,
        donate_argnums=steps["decode_step"][1],
    ))

    # -- prefill chunk: one spec per shape in the closure -------------------
    chunk_fn, chunk_donate = steps["prefill_chunk"]
    for n in closure:
        specs.append(StepSpec(
            name=f"prefill_chunk_{n}",
            fn=chunk_fn,
            args=(
                params, caches, _sds((1, n), jnp.int32),
                _sds((), jnp.int32), _sds((), jnp.int32),
                _sds((n,), jnp.int32), _sds((n,), jnp.int32),
                _sds((max_pages,), jnp.int32), _sds((), jnp.int32),
            ),
            donate_argnums=chunk_donate,
        ))

    # the probe drives the *planned* chunk sequence for every probe prompt
    # through one fresh jit; entries must equal the distinct planned shapes
    planned = [n for plan in plans.values() for n in plan]

    def _chunk_args(n):
        return (
            arena.params(), arena.fresh_caches(),
            jnp.zeros((1, n), jnp.int32), jnp.int32(0), jnp.int32(0),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
            jnp.zeros((max_pages,), jnp.int32), jnp.int32(0),
        )

    # attach the probe (and the static plan/closure pair) to the full-chunk
    # spec — the one signature every multi-chunk admission exercises
    full = next(s for s in specs if s.name == f"prefill_chunk_{chunk_size}")
    full.probe = ProbeSet(
        keys=tuple(planned), make_args=_chunk_args,
        expected_entries=len(set(planned)),
    )
    full.signature_plan = tuple(planned)
    full.signature_closure = closure

    # -- COW page copy: page ids are traced, one signature ------------------
    cow_fn, cow_donate = steps_hot["cow_copy"]
    cow_args = (caches, _sds((), jnp.int32), _sds((), jnp.int32))

    def _cow_args(key):
        return (arena.fresh_caches(), jnp.int32(1 + key), jnp.int32(2 + key))

    specs.append(StepSpec(
        name="cow_copy",
        fn=cow_fn,
        args=cow_args,
        donate_argnums=cow_donate,
        probe=ProbeSet(keys=(0, 1), make_args=_cow_args, expected_entries=1),
    ))
    specs.append(StepSpec(
        name="cow_copy_reference",
        fn=steps["cow_copy"][0],
        args=cow_args,
        donate_argnums=steps["cow_copy"][1],
    ))

    # -- unchunked install: one full-prefill source structure ---------------
    install_fn, install_donate = steps["install"]
    Sp = 2 * inv.page_size  # a bucketed two-page prompt
    _, src = jax.eval_shape(
        functools.partial(M.prefill, cfg),
        params, {"tokens": _sds((1, Sp), jnp.int32)}, _sds((), jnp.int32),
    )
    specs.append(StepSpec(
        name="install",
        fn=install_fn,
        args=(
            caches, src, _sds((), jnp.int32),
            _sds((Sp,), jnp.int32), _sds((Sp,), jnp.int32),
        ),
        donate_argnums=install_donate,
    ))

    # -- sharded inventory: attach the engine's real mesh shardings ---------
    if inv.mesh:
        _shard_specs(cfg, inv.mesh, params, caches, specs)

    return Inventory(
        cfg=cfg, geometry=inv, specs=specs, chunk_size=chunk_size,
        chunk_closure=closure, chunk_plans=plans,
    )


def _shard_specs(cfg, mesh_spec: str, params, caches, specs) -> None:
    """Turn the single-device specs into the mesh inventory, in place.

    Mirrors exactly what :class:`repro.serve.engine.Engine` builds on a
    mesh: resident-TP weights, adapter-registry pool placement, replicated
    host-fed inputs, explicit out shardings so donation composes — and the
    step bodies wrapped in :func:`repro.distributed.axes.traced_under` so
    activation constraints and the pallas shard_map dispatch see the policy
    at trace time.  The compiled artifacts are then the true partitioned
    modules RPJ101 (donation survives sharding) and RPJ106 (collective
    traffic) gate.
    """
    from repro.distributed import axes as AX
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh(mesh_spec)
    SH.validate_paged_sharding(cfg, mesh)
    param_sh, pool_sh, rep = SH.serve_shardings(cfg, mesh, params, caches)
    by_step = {
        "decode_step": (
            (param_sh, pool_sh, rep, rep, rep, rep), (rep, rep, pool_sh)
        ),
        "prefill_chunk": ((param_sh, pool_sh) + (rep,) * 7, (rep, pool_sh)),
        "cow_copy": ((pool_sh, rep, rep), pool_sh),
        "install": ((pool_sh, rep, rep, rep, rep), pool_sh),
    }
    for spec in specs:
        for prefix, (ins, outs) in by_step.items():
            if spec.name.startswith(prefix):
                spec.in_shardings, spec.out_shardings = ins, outs
                break
        spec.fn = AX.traced_under(mesh, spec.fn)
