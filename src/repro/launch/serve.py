# repro: noqa-file RPR005 -- CLI driver: the report prints ARE the output
"""Serving entry point: continuous batching over the block-paged KV cache.

Multi-request workload (Poisson-ish staggered arrivals, fixed seeds):

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --smoke \
      --num-requests 6 --max-seqs 2 --prompt-len 12 --max-new 16 \
      --mean-interarrival 4 --page-size 8

MLA (DeepSeek-V3-style) serves through latent pages; enc-dec (whisper)
through immutable per-slot cross rows + paged decoder self-attention:

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v3-671b \
      --smoke --num-requests 6 --max-seqs 2 --prompt-len 8 --max-new 12
  PYTHONPATH=src python -m repro.launch.serve --arch whisper-tiny --smoke \
      --num-requests 6 --max-seqs 2 --prompt-len 8 --max-new 12

``--backend pallas`` serves the paged decode + COW path through the fused
Pallas kernels (interpret mode on the CPU platform, compiled elsewhere):

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --smoke \
      --num-requests 6 --max-seqs 2 --backend pallas

``--mesh DxM`` serves tensor-parallel over a ``(data, model)`` device mesh:
resident sharded weights, head-sharded KV pools, sharded jitted steps
(simulate on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=2``):

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --smoke \
      --num-requests 6 --max-seqs 2 --mesh 1x2

Legacy single-wave batched generation (also the only path for the vision
frontend, which the adapter registry does not cover yet):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-vl-72b --smoke \
      --batch 4 --prompt-len 16 --max-new 32
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.launch.compile_cache import use_compile_cache
from repro.models import adapters as A
from repro.models import model as M
from repro.serve import (
    Engine,
    EngineConfig,
    ServeConfig,
    Server,
    build_serve_report,
    frontend_extras,
    make_requests,
    run_static_waves,
)


def run_single_wave(cfg, params, args, mesh=None):
    """Legacy path: one batch, one wave (works for every cache family)."""
    srv = Server(
        cfg, params,
        ServeConfig(max_len=args.prompt_len + args.max_new + 8,
                    temperature=args.temperature),
        mesh=mesh,
    )
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    batch = frontend_extras(cfg, {"tokens": toks}, args.batch, args.prompt_len)
    t0 = time.time()
    out = srv.generate(batch, max_new_tokens=args.max_new)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s incl. compile)")
    print(out[:, :16])


def run_workload(cfg, params, args, mesh=None):
    """Multi-request workload through the selected engine(s)."""
    reqs = make_requests(
        cfg.vocab_size, args.num_requests,
        prompt_len=args.prompt_len, max_new=args.max_new,
        mean_interarrival=args.mean_interarrival, seed=args.seed,
    )
    max_len = args.prompt_len + args.max_new + 1
    useful = sum(r["max_new_tokens"] for r in reqs)

    if args.engine in ("static", "both"):
        srv = Server(cfg, params, ServeConfig(
            max_len=max_len, temperature=args.temperature, seed=args.seed,
        ), mesh=mesh)
        t0 = time.time()
        outs = run_static_waves(srv, reqs, args.max_seqs)
        dt = time.time() - t0
        print(f"[static-wave]  {len(outs)} requests, {useful} tokens in "
              f"{dt:.2f}s -> {useful / dt:.1f} tok/s (incl. compile)")

    if args.engine in ("continuous", "both"):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=args.max_seqs, max_len=max_len,
            page_size=args.page_size, num_pages=args.num_pages,
            temperature=args.temperature, seed=args.seed,
            chunked_prefill=not args.no_chunked_prefill,
            prefill_chunk=args.prefill_chunk,
            prefill_tokens_per_step=args.prefill_tokens_per_step,
            prefix_sharing=not args.no_prefix_sharing,
            backend=args.backend,
            debug_audit=args.debug_audit,
            obs=args.obs,
        ), mesh=mesh)
        for r in reqs:
            eng.submit(r["prompt"], r["max_new_tokens"],
                       rid=r["rid"], arrival_step=r["arrival_step"])
        t0 = time.time()
        done = eng.run()
        dt = time.time() - t0
        # one report, two renderings: the human table below prints straight
        # from this dict, and --json-report dumps the same dict to disk
        report = build_serve_report(eng, done, wall_s=dt, useful_tokens=useful)
        print_continuous_report(eng, report)
        if args.json_report:
            with open(args.json_report, "w", encoding="utf-8") as f:
                json.dump(report, f, indent=2)
            print(f"  json report -> {args.json_report}")
        if args.trace_out:
            trace = eng.export_trace(args.trace_out)
            print(f"  chrome trace -> {args.trace_out} "
                  f"({len(trace['traceEvents'])} events; open in "
                  f"ui.perfetto.dev or chrome://tracing)")


def print_continuous_report(eng, report):
    """Render the machine-readable serve report as the human table."""
    e, pool, px, wl = (report["engine"], report["pool"],
                       report["prefix_cache"], report["workload"])
    mode = (f"chunked prefill (chunk={e['chunk_size']} tok, "
            f"budget={e['prefill_tokens_per_step']} tok/step)"
            if e["chunked_prefill"] else "one-shot prefill")
    print(f"[continuous]   {wl['num_requests']} requests, "
          f"{wl['useful_tokens']} tokens in "
          f"{wl['wall_s']:.2f}s -> {wl['tok_s']:.1f} tok/s (incl. compile); "
          f"page={pool['page_size']} pool={pool['pages_total'] + 1} "
          f"cache={pool['cache_mb']:.2f} MB, {mode}")
    print("  rid arrive admit queue ttft_ms preempt cached  tok/s  n_tok")
    for r in report["requests"]:
        tok_s = float("inf") if r["decode_tok_s"] is None else r["decode_tok_s"]
        ttft_ms = float("nan") if r["ttft_ms"] is None else r["ttft_ms"]
        print(f"  {r['rid']:3d} {r['arrival_step']:6d} {r['admitted_step']:5d} "
              f"{r['queue_steps']:5d} {ttft_ms:7.1f} "
              f"{r['preemptions']:7d} {r['cached_prompt_tokens']:6d} "
              f"{tok_s:6.1f} {r['n_tokens']:6d}")
    print(f"  engine steps={e['steps']} decode_steps={e['decode_steps']} "
          f"prefill_tokens={e['prefill_tokens']} "
          f"prefill_chunks={e['prefill_chunks']}")
    if px["enabled"]:
        label = (px["mode"] if px["mode"] == "compute-skipping"
                 else "memory-dedup, recompute")
        print(f"  prefix cache [{label}]: {px['cached_prompt_tokens']}"
              f"/{px['prompt_tokens']} prompt tokens served from cache "
              f"({100.0 * px['hit_rate']:.1f}% hit rate), "
              f"{pool['pages_aliased_total']} page aliases, "
              f"{pool['cow_copies_total']} COW copies, "
              f"{pool['prefix_cache_pages']} pages resident")
    else:
        print("  prefix cache: off (family not shareable or "
              "--no-prefix-sharing)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=C.arch_ids())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="legacy single-wave batch size (--num-requests 0)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--num-requests", type=int, default=0,
                    help="> 0 switches to the multi-request workload path")
    ap.add_argument("--engine", choices=("static", "continuous", "both"),
                    default="continuous")
    ap.add_argument("--max-seqs", type=int, default=4,
                    help="concurrent batch slots (workload path)")
    ap.add_argument("--mean-interarrival", type=float, default=4.0,
                    help="mean request inter-arrival gap in decode steps")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size in tokens; 0 derives from cfg.block")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="physical page pool size; 0 sizes for max_seqs")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-admission chunk in tokens; 0 derives one "
                         "page (SSD-grid-aligned for SSM models)")
    ap.add_argument("--prefill-tokens-per-step", type=int, default=0,
                    help="prompt tokens admitted per engine step before the "
                         "decode batch steps (page-granular; the "
                         "latency/throughput knob).  0 derives 4 chunks")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="one-shot prefill per admission (the pre-chunking "
                         "behavior; still installed via donating jit)")
    ap.add_argument("--backend", default="reference",
                    choices=("reference", "pallas"),
                    help="paged-decode path for the continuous engine: the "
                         "jnp gather oracle or the fused paged-attention / "
                         "COW kernels (interpret mode on the CPU platform, "
                         "compiled elsewhere; families without paged decode fall "
                         "back to their reference path)")
    ap.add_argument("--mesh", default="",
                    help="DxM device mesh (e.g. 1x2): serve tensor-parallel "
                         "— resident sharded weights, head-sharded KV pools, "
                         "sharded jitted steps.  Needs D*M visible devices "
                         "(simulate on CPU with XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N); '' serves single-device")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the shared-prefix page cache (radix "
                         "index + refcounted aliasing + copy-on-write); "
                         "stateful families disable it automatically")
    ap.add_argument("--obs", action="store_true",
                    help="deep observability: audit-backed pool gauges every "
                         "step (spans, counters and the profiler step spans "
                         "are always recorded; this only adds the audit)")
    ap.add_argument("--json-report", default="",
                    help="write the latency/prefix-cache report (the table "
                         "above, machine-readable, plus the full metrics "
                         "registry snapshot) as JSON to this path")
    ap.add_argument("--trace-out", default="",
                    help="export request-lifecycle spans and engine-step "
                         "tracks as Chrome-trace JSON (open in "
                         "ui.perfetto.dev); validate with "
                         "`python -m repro.serve.obs PATH`")
    ap.add_argument("--debug-audit", action="store_true",
                    help="run the paged-KV refcount auditor after every "
                         "engine step (slow; catches page leaks / double "
                         "frees at the step that introduces them)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = C.get_config(args.arch, smoke=args.smoke,
                       dtype=jnp.float32 if args.smoke else jnp.bfloat16)
    if args.num_requests > 0 and args.engine != "static":
        # refuse BEFORE any pool (or even params) is allocated, with the
        # exact family list the adapter registry reports
        msg = A.unsupported_message(cfg, hint="rerun with --engine static")
        if msg is not None:
            raise SystemExit(msg)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(args.mesh)
        print(f"serving on mesh {args.mesh}: "
              f"{mesh.shape['data']} data x {mesh.shape['model']} model")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if args.num_requests > 0:
        run_workload(cfg, params, args, mesh=mesh)
    else:
        run_single_wave(cfg, params, args, mesh=mesh)


if __name__ == "__main__":
    main()
