"""Continuous batching vs static-wave serving throughput.

A staggered-arrival workload (Poisson-ish gaps, per-request generation
lengths, all from one fixed seed) is driven through both engines:

* **static-wave** — the pre-paging ``Server``: waves of ``max_seqs``
  requests decode in lockstep for the wave's longest generation, finished
  slots burning steps on padding;
* **continuous** — the ``Engine`` over the block-paged KV cache: a finished
  request's slot and pages are re-filled from the queue the same step.

Both run the workload once cold (compile) and once warm (timed).  Reported:
warm tokens/s, decode slot-step efficiency (useful tokens / slot-steps
executed), and greedy-output parity between the engines.  Continuous
batching must come out >= the static wave on tokens/s — that is the
repo-level acceptance gate for the serving subsystem.

``--long-prompt`` switches to the **chunked-admission gate**: a max-length
prompt arrives while short requests are decoding, and the benchmark
measures the longest stall (max wall-clock engine-step time) the in-flight
decodes suffer during that admission — once with chunked prefill (the
default engine) and once with one-shot prefill.  Chunked admission must cut
the worst-case stall: that is the repo-level acceptance gate for chunked
prefill (tests/test_serve.py gates the same property deterministically in
step units; this gate shows it in wall-clock).

``--shared-prefix`` switches to the **prefix-sharing gate**: N requests
share an 8-page prompt prefix (the shared-system-prompt traffic shape) and
run once with the shared-prefix page cache and once without.  Sharing must
cut mean TTFT in engine steps (deterministic — later admissions alias the
cached prefix and chunk-prefill only their suffix) and allocate fewer
pool pages (the prefix is stored once, not once per request): those are
the repo-level acceptance gates for shared-prefix serving.  Outputs must
match between the two runs bit for bit.

``--backend pallas`` runs the continuous engine through the fused
paged-attention / COW kernels (interpret mode off-TPU) instead of the jnp
gather oracle; the static baseline always serves through the reference
path, so the parity check doubles as an engine-level backend gate.

``--mesh DxM`` switches to the **tensor-parallel gate**: the same workload
runs through the continuous engine once single-device and once sharded
over a ``(data, model)`` mesh (simulate on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  The gates are
deterministic, not wall-clock: the sharded run's greedy tokens must be
bit-identical to the single-device run, and when the family's KV pool
head-shards, the per-device pool bytes must be exactly ``total / TP`` —
the memory claim tensor parallelism exists to deliver.  (Wall-clock does
not improve on a simulated mesh: every "device" is a slice of one CPU.)

Every mode also merges its results (ratios, TTFT, tok/s, pool stats) into
the ``BENCH_serve.json`` artifact (``--bench-out``; keyed ``mode:arch``,
with ``:pallas`` appended for non-reference backends and ``:mesh=DxM``
for sharded runs so every variant coexists) — the machine-readable perf
trajectory CI uploads per run.

Usage:  PYTHONPATH=src:. python benchmarks/serve_throughput.py [--arch ...]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from benchmarks.common import emit
from repro.models import model as M
from repro.serve import (
    Engine,
    EngineConfig,
    ServeConfig,
    Server,
    make_requests,
    run_static_waves,
)


def _write_bench(args, mode: str, payload: dict) -> None:
    """Merge one gate's results into the BENCH_serve.json perf artifact.

    Keyed ``mode:arch`` so the three gates (and per-family runs) coexist in
    one file; an existing artifact is updated in place, so a CI job running
    several gates uploads a single trajectory document.
    """
    if not args.bench_out:
        return
    try:
        with open(args.bench_out, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        doc = {}
    # the backend is a real result dimension: a pallas run coexists with the
    # reference run under its own key instead of overwriting it
    key = f"{mode}:{args.arch}"
    if args.backend != "reference":
        key += f":{args.backend}"
    if args.mesh:
        key += f":mesh={args.mesh}"
    doc[key] = {"backend": args.backend, "mesh": args.mesh, **payload}
    with open(args.bench_out, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"# bench artifact [{key}] -> {args.bench_out}")


def _run_static(cfg, params, reqs, args, max_len):
    # bucket by the page size (not cfg.block): the throughput comparison
    # should measure scheduling, not hand the static baseline extra pad work
    srv = Server(cfg, params, ServeConfig(
        max_len=max_len, seed=args.seed, prefill_bucket=args.page_size,
    ))
    t0 = time.perf_counter()
    outs = run_static_waves(srv, reqs, args.max_seqs)
    wall = time.perf_counter() - t0
    # slot-steps: every wave burns its longest generation length in every slot
    slot_steps = 0
    order = sorted(reqs, key=lambda r: (r["arrival_step"], r["rid"]))
    for w in range(0, len(order), args.max_seqs):
        wave = order[w : w + args.max_seqs]
        slot_steps += len(wave) * max(r["max_new_tokens"] for r in wave)
    return outs, wall, slot_steps


def _run_continuous(cfg, params, reqs, args, max_len, mesh=None):
    # chunk granularity trades admission latency for dispatch overhead: the
    # throughput gate uses a few pages per chunk (vLLM-style budget) so the
    # comparison measures scheduling, not per-chunk fixed costs at smoke
    # scale; the --long-prompt gate keeps page-granular chunks for the
    # sharpest decode interleave
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=args.max_seqs, max_len=max_len,
        page_size=args.page_size, seed=args.seed,
        prefill_chunk=args.prefill_chunk, backend=args.backend,
    ), mesh=mesh)
    for r in reqs:
        eng.submit(r["prompt"], r["max_new_tokens"],
                   rid=r["rid"], arrival_step=r["arrival_step"])
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    outs = {r.rid: np.asarray(r.out_tokens, np.int32) for r in done}
    stats = {
        "slot_steps": eng.decode_steps * args.max_seqs,
        "queue_steps": [r.stats.queue_steps for r in done],
        "ttft_steps": [r.stats.ttft_steps for r in done],
        "preemptions": sum(r.stats.n_preemptions for r in done),
        "page_size": eng.kv.page_size,
        "cache_mb": eng.kv.cache_bytes() / 1e6,
        "cache_bytes": eng.kv.cache_bytes(),
        "cache_bytes_per_device": eng.kv.cache_bytes_per_device(),
        "pool": eng.kv.pool_stats(),
    }
    return outs, wall, stats


# one arch per cache-adapter family, so `--family` can point the gate at
# any adapter the registry serves (ISSUE: the CI gate covers MLA too)
FAMILY_ARCHS = {
    "dense": "minicpm-2b",
    "swa": "h2o-danube-3-4b",
    "ssm": "mamba2-130m",
    "hybrid": "hymba-1.5b",
    "mla": "deepseek-v3-671b",
    "encdec": "whisper-tiny",
}


def _scaled_cfg(args, scale):
    # benchmark shape: the smoke config scaled to where a decode step is
    # real device work — at smoke size (2L, d=96) the host-side scheduling
    # overhead swamps the compute and wall-clock measures noise, not the
    # engines.  ~4L/d=256 keeps compile < 10 s on CPU.
    cfg = C.get_config(args.arch, smoke=True, dtype=jnp.float32)
    import dataclasses
    if cfg.family == "dense" and scale >= 0.5:  # repro: noqa RPR004 -- bench sizing table, not a dispatch path
        cfg = dataclasses.replace(
            cfg, n_layers=4, d_model=256, n_heads=8, n_kv_heads=8,
            d_head=32, d_ff=512,
        )
    elif cfg.attn_type == "mla" and scale >= 0.5:  # repro: noqa RPR004 -- bench sizing table, not a dispatch path
        cfg = dataclasses.replace(
            cfg, n_layers=4, d_model=256, n_heads=8, n_kv_heads=8,
            q_lora_rank=96, kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
            v_head_dim=32, d_ff=512, moe_d_ff=128, first_k_dense=1,
        )
    return cfg


def _long_prompt_trial(cfg, params, args, chunked: bool):
    """One long-prompt admission against an in-flight decode batch.

    Returns (max engine-step wall time while the long prompt was being
    admitted, the long request's TTFT in steps, outputs).  Each step syncs
    the device so step walls measure compute, not dispatch.
    """
    max_len = args.long_prompt_len + args.max_new + 1
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=args.max_seqs, max_len=max_len, page_size=args.page_size,
        chunked_prefill=chunked, prefill_tokens_per_step=args.page_size,
        seed=args.seed, backend=args.backend,
    ))
    rng = np.random.default_rng(args.seed)
    victims = [
        eng.submit(
            rng.integers(0, cfg.vocab_size, size=(args.prompt_len,)).astype(np.int32),
            args.max_new, rid=i, arrival_step=0,
        )
        for i in range(args.max_seqs - 1)
    ]
    long_req = eng.submit(
        rng.integers(0, cfg.vocab_size, size=(args.long_prompt_len,)).astype(np.int32),
        4, rid=args.max_seqs - 1, arrival_step=2,
    )
    walls = []
    while eng.sched.has_work():
        t0 = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.kv.data)
        walls.append(time.perf_counter() - t0)
        if eng.step_count > 10_000:
            raise RuntimeError("engine did not drain")
    eng._flush_pending()
    s = long_req.stats
    window = walls[s.admitted_step : s.first_token_step + 1]
    outs = {r.rid: list(r.out_tokens) for r in victims + [long_req]}
    return max(window), s.first_token_step - s.admitted_step, outs


def run_long_prompt(scale: float, args) -> float:
    print("# serve long-prompt admission: chunked vs one-shot prefill "
          f"(arch={args.arch}, long={args.long_prompt_len} tokens, "
          f"{args.max_seqs - 1} in-flight decodes)")
    cfg = _scaled_cfg(args, scale)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # cold pass (compile), then paired trials for a load-robust ratio
    _long_prompt_trial(cfg, params, args, chunked=True)
    _long_prompt_trial(cfg, params, args, chunked=False)
    ratios, ch_stall = [], float("inf")
    un_stall = float("inf")
    match = True
    for _ in range(args.repeats):
        c_stall, c_ttft, c_out = _long_prompt_trial(cfg, params, args, True)
        u_stall, u_ttft, u_out = _long_prompt_trial(cfg, params, args, False)
        ch_stall, un_stall = min(ch_stall, c_stall), min(un_stall, u_stall)
        ratios.append(c_stall / u_stall)
        match = match and c_out == u_out
    ratio = sorted(ratios)[len(ratios) // 2]
    emit("serve/long_prompt/chunked_max_stall_ms", ch_stall * 1e3,
         f"ttft_steps={c_ttft}")
    emit("serve/long_prompt/oneshot_max_stall_ms", un_stall * 1e3,
         f"ttft_steps={u_ttft}")
    emit("serve/long_prompt/stall_ratio", ratio,
         f"outputs_match={match} pair_ratios="
         + "/".join(f"{r:.2f}" for r in sorted(ratios)))
    print(f"# in-flight decode max stall during admission: chunked "
          f"{ch_stall * 1e3:.1f} ms vs one-shot {un_stall * 1e3:.1f} ms "
          f"(median paired ratio {ratio:.2f}, outputs match: {match})")
    _write_bench(args, "long_prompt", {
        "chunked_max_stall_ms": ch_stall * 1e3,
        "oneshot_max_stall_ms": un_stall * 1e3,
        "stall_ratio_median": ratio,
        "pair_ratios": sorted(ratios),
        "chunked_ttft_steps": c_ttft,
        "oneshot_ttft_steps": u_ttft,
        "outputs_match": match,
    })
    return ratio


def _shared_prefix_trial(cfg, params, args, sharing: bool):
    """One shared-system-prompt workload through the engine.

    Returns (mean TTFT in engine steps — deterministic scheduling units,
    not wall clock, pages allocated from the pool, cached prompt tokens,
    outputs).  The first ``max_seqs`` admissions land before any prefix is
    published and miss; every later admission aliases the shared pages.
    """
    prefix_tokens = args.shared_prefix_pages * args.page_size
    max_len = prefix_tokens + args.prompt_len + args.max_new + 1
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=args.max_seqs, max_len=max_len, page_size=args.page_size,
        seed=args.seed, prefix_sharing=sharing, backend=args.backend,
    ))
    rng = np.random.default_rng(args.seed)
    prefix = rng.integers(0, cfg.vocab_size, size=(prefix_tokens,))
    reqs = []
    for i in range(args.num_requests):
        suffix = rng.integers(0, cfg.vocab_size, size=(args.prompt_len,))
        prompt = np.concatenate([prefix, suffix]).astype(np.int32)
        reqs.append(eng.submit(prompt, args.max_new, rid=i, arrival_step=0))
    done = eng.run()
    ttft = [r.stats.ttft_steps for r in done]
    outs = {r.rid: list(r.out_tokens) for r in done}
    return (
        float(np.mean(ttft)),
        eng.kv.allocator.pages_allocated,
        sum(r.stats.cached_prompt_tokens for r in done),
        outs,
    )


def run_shared_prefix(scale: float, args):
    """The prefix-sharing gate: shared page cache vs cold-per-request."""
    prefix_tokens = args.shared_prefix_pages * args.page_size
    print("# serve shared-prefix: prefix page cache vs per-request prefill "
          f"(arch={args.arch}, {args.num_requests} requests sharing "
          f"{args.shared_prefix_pages} pages = {prefix_tokens} tokens)")
    cfg = _scaled_cfg(args, scale)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sh_ttft, sh_pages, sh_cached, sh_out = _shared_prefix_trial(
        cfg, params, args, sharing=True
    )
    un_ttft, un_pages, _un_cached, un_out = _shared_prefix_trial(
        cfg, params, args, sharing=False
    )
    match = sh_out == un_out
    saved = un_pages - sh_pages
    emit("serve/shared_prefix/shared_ttft_steps", sh_ttft,
         f"pages_allocated={sh_pages} cached_tokens={sh_cached}")
    emit("serve/shared_prefix/unshared_ttft_steps", un_ttft,
         f"pages_allocated={un_pages}")
    emit("serve/shared_prefix/pages_saved", saved,
         f"outputs_match={match}")
    print(f"# mean TTFT {sh_ttft:.1f} steps shared vs {un_ttft:.1f} unshared, "
          f"{saved} pool pages saved ({sh_pages} vs {un_pages} allocated), "
          f"outputs match: {match}")
    _write_bench(args, "shared_prefix", {
        "shared_ttft_steps": sh_ttft,
        "unshared_ttft_steps": un_ttft,
        "pages_saved": saved,
        "pages_allocated": {"shared": sh_pages, "unshared": un_pages},
        "cached_tokens": sh_cached,
        "outputs_match": match,
    })
    return sh_ttft, un_ttft, saved, match


def run_mesh(scale: float, args):
    """The tensor-parallel gate: sharded engine vs single-device parity.

    Both gates are deterministic, so the smoke shape is the right one: the
    scaled threaded-matmul shape can flip a near-tie argmax between
    batchings, and wall-clock says nothing on a simulated mesh (every
    "device" is a slice of one CPU).  What must hold exactly: bit-identical
    greedy tokens, and per-device pool bytes == total / TP whenever the
    family's KV pool head-shards on the model axis (MLA latent pools
    replicate by design and must stay byte-identical per device).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh(args.mesh)
    tp = mesh.shape["model"]
    print("# serve mesh: tensor-parallel continuous engine vs single-device "
          f"(arch={args.arch}, mesh={args.mesh}, backend={args.backend}, "
          f"{args.num_requests} requests)")
    cfg = C.get_config(args.arch, smoke=True, dtype=jnp.float32)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = make_requests(
        cfg.vocab_size, args.num_requests,
        prompt_len=args.prompt_len, max_new=args.max_new,
        mean_interarrival=args.mean_interarrival, seed=args.seed,
    )
    max_len = args.prompt_len + args.max_new + 1
    base_out, _, _base = _run_continuous(cfg, params, reqs, args, max_len)
    mesh_out, _, sharded = _run_continuous(
        cfg, params, reqs, args, max_len, mesh=mesh
    )
    match = all(
        np.array_equal(base_out[r["rid"]], mesh_out[r["rid"]]) for r in reqs
    )
    total = sharded["cache_bytes"]
    per_dev = sharded["cache_bytes_per_device"]
    # expectation from the adapter registry's own specs: does this family's
    # pool carry the model axis at all?
    pools = jax.eval_shape(lambda: M.init_paged_cache(
        cfg, args.max_seqs, 1, args.page_size, max_len
    ))
    specs = jax.tree.leaves(
        SH.paged_cache_pspecs(cfg, mesh, pools),
        is_leaf=lambda x: isinstance(x, P),
    )
    head_sharded = any("model" in tuple(s) for s in specs)
    expect = total // tp if head_sharded else total
    emit("serve/mesh/parity", float(match), f"mesh={args.mesh} tp={tp}")
    emit("serve/mesh/pool_bytes_per_device", per_dev,
         f"total={total} expected={expect} head_sharded={head_sharded}")
    print(f"# sharded greedy parity: {match}; pool {total} B total -> "
          f"{per_dev} B/device (expected {expect}, tp={tp}, "
          f"head-sharded={head_sharded})")
    _write_bench(args, "mesh", {
        "outputs_match": match,
        "tp": tp,
        "pool_bytes_total": total,
        "pool_bytes_per_device": per_dev,
        "pool_bytes_per_device_expected": expect,
        "pool_head_sharded": head_sharded,
        "slot_steps": sharded["slot_steps"],
        "preemptions": sharded["preemptions"],
        "page_size": sharded["page_size"],
    })
    return match, per_dev, expect


def run(scale: float = 1.0, argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--family", choices=sorted(FAMILY_ARCHS),
                    help="pick the arch by cache-adapter family instead of "
                         "--arch (one representative per adapter)")
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--max-seqs", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--mean-interarrival", type=float, default=3.0)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prefill chunk tokens for the throughput run "
                         "(0 derives one page)")
    ap.add_argument("--backend", default="reference",
                    choices=("reference", "pallas"),
                    help="paged-decode path for the continuous engine: the "
                         "jnp gather oracle or the fused paged-attention / "
                         "COW kernels (compiled on TPU, interpret mode "
                         "elsewhere).  Recorded in the bench artifact")
    ap.add_argument("--mesh", default="",
                    help="DxM mesh spec (e.g. 1x2): run the tensor-parallel "
                         "gate instead — sharded-vs-single-device greedy "
                         "parity + per-device pool bytes.  Needs D*M visible "
                         "devices (simulate with XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N)")
    ap.add_argument("--long-prompt", action="store_true",
                    help="run the chunked-admission stall gate instead")
    ap.add_argument("--long-prompt-len", type=int, default=512)
    ap.add_argument("--shared-prefix", action="store_true",
                    help="run the prefix-sharing gate instead: N requests "
                         "sharing a multi-page prompt prefix, cache vs cold")
    ap.add_argument("--shared-prefix-pages", type=int, default=8,
                    help="pages of shared prompt prefix for --shared-prefix")
    ap.add_argument("--bench-out", default="BENCH_serve.json",
                    help="merge this run's results (keyed mode:arch) into "
                         "this JSON perf artifact ('' disables)")
    args, _ = ap.parse_known_args(argv)
    if args.repeats < 1:
        ap.error("--repeats must be >= 1")
    if args.family:
        args.arch = FAMILY_ARCHS[args.family]

    if args.mesh:
        return run_mesh(scale, args), None, "mesh"
    if args.long_prompt:
        return run_long_prompt(scale, args), None, None
    if args.shared_prefix:
        return run_shared_prefix(scale, args), None, "shared-prefix"

    print("# serve throughput: continuous batching vs static waves "
          f"(arch={args.arch}, backend={args.backend}, "
          f"{args.num_requests} requests, max_seqs={args.max_seqs})")
    cfg = _scaled_cfg(args, scale)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = make_requests(
        cfg.vocab_size, args.num_requests,
        prompt_len=args.prompt_len, max_new=args.max_new,
        mean_interarrival=args.mean_interarrival, seed=args.seed,
    )
    useful = sum(r["max_new_tokens"] for r in reqs)
    max_len = args.prompt_len + args.max_new + 1

    # cold pass compiles every jit cache both engines need; then time
    # ``repeats`` back-to-back (static, continuous) PAIRS and take the
    # median of per-pair ratios — load bursts on a shared CI runner hit
    # both halves of a pair about equally, so the ratio is far more stable
    # than two independently-timed walls
    st_out, _, st_slot_steps = _run_static(cfg, params, reqs, args, max_len)
    ct_out, _, ct = _run_continuous(cfg, params, reqs, args, max_len)
    st_wall = ct_wall = float("inf")
    ratios = []
    for _ in range(args.repeats):
        _, sw, _ = _run_static(cfg, params, reqs, args, max_len)
        _, cw, _ = _run_continuous(cfg, params, reqs, args, max_len)
        st_wall, ct_wall = min(st_wall, sw), min(ct_wall, cw)
        ratios.append(sw / cw)

    st_tps = useful / st_wall
    ct_tps = useful / ct_wall
    emit("serve/static_wave/tok_s", st_tps,
         f"slot_steps={st_slot_steps} "
         f"efficiency={useful / st_slot_steps:.2f}")
    emit("serve/continuous/tok_s", ct_tps,
         f"slot_steps={ct['slot_steps']} "
         f"efficiency={useful / ct['slot_steps']:.2f} "
         f"preemptions={ct['preemptions']} page={ct['page_size']} "
         f"cache_mb={ct['cache_mb']:.2f}")

    match = all(
        np.array_equal(st_out[r["rid"]], ct_out[r["rid"]]) for r in reqs
    )
    speedup = sorted(ratios)[len(ratios) // 2]  # median of paired ratios
    emit("serve/continuous_vs_static/speedup", speedup,
         f"outputs_match={match} pair_ratios="
         + "/".join(f"{r:.2f}" for r in sorted(ratios)))
    print(f"# continuous {ct_tps:.1f} tok/s vs static {st_tps:.1f} tok/s, "
          f"median paired speedup {speedup:.2f}x, "
          f"greedy outputs match: {match}")
    _write_bench(args, "throughput", {
        "speedup_median": speedup,
        "pair_ratios": sorted(ratios),
        "static_tok_s": st_tps,
        "continuous_tok_s": ct_tps,
        "slot_steps": {"static": st_slot_steps,
                       "continuous": ct["slot_steps"]},
        "efficiency": {"static": useful / st_slot_steps,
                       "continuous": useful / ct["slot_steps"]},
        "queue_steps": ct["queue_steps"],
        "ttft_steps": ct["ttft_steps"],
        "preemptions": ct["preemptions"],
        "page_size": ct["page_size"],
        "cache_mb": ct["cache_mb"],
        "pool": ct["pool"],
        "outputs_match": match,
    })
    if not match:
        # at this (threaded-matmul) shape the two engines prefill at
        # different batch shapes, so XLA CPU may partition the contraction
        # differently and a near-tie argmax can flip; MoE archs additionally
        # regroup the capacity dispatch when prompts batch/chunk differently
        # — the bitwise parity guarantee is asserted in tests/test_serve.py
        # at thread-stable, dispatch-stable shapes; here a mismatch is
        # reported, not fatal
        print("# note: divergence is a near-tie argmax flip under threaded "
              "XLA CPU matmul (MoE: capacity-dispatch regrouping), see "
              "tests/test_serve.py for the parity gate")
    return speedup, ct["slot_steps"], st_slot_steps


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # standalone (CI) gates; the benchmarks.run harness only reports.
    # slot-steps are deterministic — that comparison is hard.  wall clock
    # on a shared runner is not, so the paired-median ratio only fails on a
    # clear regression; typical measured margin is 1.2-2.2x.
    speedup, ct_steps, st_steps = run()
    if st_steps == "mesh":
        # deterministic, so both gates are hard: the sharded engine must
        # reproduce the single-device greedy stream bit for bit, and the
        # per-device pool bytes must match the registry's sharding specs
        # (total/TP when head-sharded, total when replicated).
        match, per_dev, expect = speedup
        if not match:
            raise SystemExit(
                "sharded greedy outputs diverged from single-device"
            )
        if per_dev != expect:
            raise SystemExit(
                f"per-device pool bytes {per_dev} != expected {expect}"
            )
        raise SystemExit(0)
    if st_steps == "shared-prefix":
        # deterministic step/page accounting, so the gates are hard: the
        # shared run must admit later requests to their first token sooner
        # (mean TTFT in engine steps) AND allocate fewer pool pages, with
        # greedy outputs bit-identical between the two runs.
        sh_ttft, un_ttft, saved, match = speedup
        if not match:
            # the bitwise guarantee is gated in tests/test_serve.py at
            # thread-stable shapes; at this scaled shape the shared and
            # unshared runs prefill at different chunk counts, where
            # threaded XLA CPU matmul can flip a near-tie argmax — report,
            # don't fail (same policy as the throughput parity note)
            print("# note: output divergence at scaled shape — see the "
                  "parity gates in tests/test_serve.py")
        if not sh_ttft < un_ttft:
            raise SystemExit(
                f"prefix sharing did not cut mean TTFT "
                f"({sh_ttft:.1f} vs {un_ttft:.1f} engine steps unshared)"
            )
        if not saved > 0:
            raise SystemExit(
                f"prefix sharing saved no pool pages (saved={saved})"
            )
        raise SystemExit(0)
    if ct_steps is None:
        # --long-prompt mode: `speedup` is the chunked/one-shot stall ratio.
        # chunked admission must clearly cut the in-flight decode's worst
        # stall; at the default shape the measured ratio is ~0.1-0.4.
        if speedup > 0.8:
            raise SystemExit(
                f"chunked prefill did not reduce the decode stall during a "
                f"long-prompt admission ({speedup:.2f}x of one-shot)"
            )
        raise SystemExit(0)
    if ct_steps > st_steps:
        raise SystemExit(
            f"continuous used more decode slot-steps ({ct_steps}) than "
            f"static waves ({st_steps})"
        )
    if speedup < 0.85:
        raise SystemExit(
            f"continuous batching clearly slower than static waves "
            f"({speedup:.2f}x median paired)"
        )
