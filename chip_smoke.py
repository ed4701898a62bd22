#!/usr/bin/env python3
"""Smoke run of the paged serving engine on a TPU, at minicpm-2b's widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # tensor-parallel 1x4 mesh vs one chip

One process drives what ``python -m repro.launch.serve --arch minicpm-2b
--num-requests 8 --backend pallas`` drives: ``C.get_config`` in bf16,
``M.init_params`` from ``--seed``, ``make_requests``, ``Engine`` and
``Engine.run()``, with the compiled Pallas paged-decode and COW kernels.

The default phase serves 8 requests (prompts of 128-1024 tokens, 32-64 new
tokens each) through 4 slots of 2048 tokens with 128-token pages, so the run
takes chunked prefill, page growth, admissions that wait for a free slot,
and one request whose whole prompt is a cached prefix ending mid-page: its
first decode write copies the shared page through the COW kernel.  It then
serves the same requests through a ``backend="reference"`` engine that
replays the pallas engine's tokens, and holds the logits behind every
generated token (prefill, every decode step, the steps after page growth)
to ``LOGIT_TOL``.

``--four-chips`` runs only the tensor-parallel path: the same requests
through ``Engine(mesh=make_serve_mesh("1x4"), backend="pallas")``, replaying
a single-device engine's tokens and compared with it row by row, plus the
per-device pool bytes and the decode and COW steps' memory per device.

The script exits non-zero, and prints no result line, unless JAX runs on a
TPU with compiled (not interpreted) kernels.  Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "minicpm-2b"
MAX_SEQS = 4
MAX_LEN = 2048
NUM_REQUESTS = 8
PROMPT_LEN = (128, 1024)
NEW_TOKENS = (32, 64)
# Logits are compared row by row as max|a - b| / max|b| over the
# vocabulary.  Weights and K/V pages are bf16 (8-bit mantissa, unit
# roundoff 2^-9 ~ 0.2%); both paths accumulate in f32 but round the
# attention output back to bf16 at different points, and such one-ulp
# differences pass through 40 residual layers of bf16 activations.  5% of
# the logit range admits that drift; a decode that reads one wrong page
# lands far outside it (see PERF.md, the planted-fault reading).
LOGIT_TOL = 0.05
# max|b| of a random-init logit row is a few units (unit-RMS final hidden
# state against 0.02-scale embeddings over 2304 dims); a row outside this
# range holds something else, e.g. the finfo.min of the vocabulary padding,
# and would make any error look small.
LOGIT_SCALE = (1e-2, 1e3)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def workload(cfg, seed: int):
    """make_requests' arrivals and prompts, cut to the smoke's lengths, plus
    one request that is a mid-page prefix of an earlier, longer prompt (it
    aliases 2 full pages and a partial third, and its first decode write
    must copy-on-write that third page)."""
    import numpy as np

    from repro.serve import make_requests

    page = cfg.block
    reqs = make_requests(
        cfg.vocab_size, NUM_REQUESTS, prompt_len=PROMPT_LEN[1],
        max_new=NEW_TOKENS[1], mean_interarrival=2.0, seed=seed,
    )
    rng = np.random.default_rng(seed)
    for r in reqs:
        r["prompt"] = r["prompt"][: rng.integers(PROMPT_LEN[0],
                                                 PROMPT_LEN[1] + 1)]
        r["max_new_tokens"] = int(rng.integers(NEW_TOKENS[0],
                                               NEW_TOKENS[1] + 1))
    src = next((r for r in reqs[:-1] if len(r["prompt"]) >= 3 * page), None)
    check(src is not None, "no prompt of 3 pages to share; pick another seed")
    last = reqs[-1]
    last["prompt"] = src["prompt"][: 2 * page + page // 3].copy()
    # arrive after the publisher's prefill has put its pages in the index
    last["arrival_step"] = max(last["arrival_step"], src["arrival_step"] + 8)
    return reqs


def serve(cfg, params, reqs, *, backend, mesh=None, seed=0, follow=None):
    """Serve ``reqs`` through one Engine.

    Returns the engine, the finished requests, the wall seconds and the
    logit row behind every generated token, as ``{"prefill": {...},
    "decode": {...}}`` keyed by ``(rid, k)`` for a request's k-th token.
    With ``follow`` (rid -> the tokens of an earlier run) the engine emits
    those tokens in place of its own greedy picks: each of its rows is then
    computed from the same history as the earlier run's row, and the two
    can be compared at every step."""
    import jax
    import numpy as np

    from repro.serve import Engine, EngineConfig

    eng = Engine(cfg, params, EngineConfig(
        max_seqs=MAX_SEQS, max_len=MAX_LEN, page_size=cfg.block,
        backend=backend, seed=seed,
    ), mesh=mesh)
    rows = {"prefill": {}, "decode": {}}
    sample_fn, decode_fn = eng._sample, eng._decode
    # the real vocabulary only: pad columns hold finfo.min in both runs
    vocab = cfg.vocab_size

    def sample(row, req):  # a prefill's final chunk samples through here
        k = len(req.out_tokens)
        rows["prefill"][req.rid, k] = np.asarray(row[:vocab], np.float32)
        tok = sample_fn(row, req)
        return tok if follow is None else int(follow[req.rid][k])

    def decode(*args):
        running = [(s, r.rid, r.n_generated) for s, r in eng.sched.decoding]
        greedy, logits, data = decode_fn(*args)
        host = np.asarray(logits[:, -1, :vocab], np.float32)
        for s, rid, k in running:
            rows["decode"][rid, k] = host[s]
        if follow is not None:
            forced = np.array(greedy)
            for s, rid, k in running:
                forced[s] = follow[rid][k]
            greedy = jax.device_put(forced, greedy.sharding)
        return greedy, logits, data

    # record (and replay) at every token; this syncs the host once per
    # step, which a smoke run can afford
    eng._sample, eng._decode = sample, decode
    for r in reqs:
        eng.submit(r["prompt"], r["max_new_tokens"], rid=r["rid"],
                   arrival_step=r["arrival_step"])
    t0 = time.perf_counter()
    done = eng.run()
    jax.block_until_ready(eng.kv.data)
    wall = time.perf_counter() - t0
    eng._sample, eng._decode = sample_fn, decode_fn
    return eng, done, wall, rows


def release(eng) -> None:
    """Drop an engine's device pool before the next engine allocates its
    own (two full pools and their step temps would crowd one chip)."""
    eng.kv.data = None
    eng._chunk_fn = eng._decode = None
    gc.collect()


def grown_rows(reqs, keys, page: int) -> list:
    """The decode rows whose step wrote to a page grown during decode (the
    pages a request is admitted with hold its prompt and its first decode
    write)."""
    plen = {r["rid"]: len(r["prompt"]) for r in reqs}
    return [(rid, k) for rid, k in keys
            if plen[rid] + k - 1 >= -(-(plen[rid] + 1) // page) * page]


def compare(name, got, want, keys=None) -> None:
    """Gate every row's max|got - want| / max|want| on LOGIT_TOL; print the
    top-1 agreement (the token agreement under one history), ungated."""
    import numpy as np

    check(set(got) == set(want), f"{name}: recorded rows differ "
                                 f"({sorted(set(got) ^ set(want))})")
    keys = sorted(want) if keys is None else sorted(keys)
    check(len(keys) > 0, f"{name}: no row to compare")
    worst, at, top1 = -1.0, None, 0
    for key in keys:
        a, b = got[key], want[key]
        check(np.isfinite(a).all() and np.isfinite(b).all(),
              f"{name}: non-finite logits at (request, token) {key}")
        scale = float(np.abs(b).max())
        check(LOGIT_SCALE[0] <= scale <= LOGIT_SCALE[1],
              f"{name}: row {key} has max|logit| {scale!r}, outside "
              f"{LOGIT_SCALE}: not a logit row")
        err = float(np.abs(a - b).max()) / scale
        if err > worst:
            worst, at = err, (key, scale)
        top1 += int(a.argmax() == b.argmax())
    print(f"{name} logits: max rel err {worst!r} over {len(keys)} rows "
          f"(tolerance {LOGIT_TOL}), at (request, token) {at[0]} whose "
          f"max|logit| is {at[1]!r}; token agreement (top-1, not gated: "
          f"random-init logits are near ties) {top1}/{len(keys)}",
          flush=True)
    check(worst <= LOGIT_TOL, f"{name} logits off by {worst!r} > {LOGIT_TOL}")


def compare_runs(got, want, reqs, page: int) -> None:
    """Row-by-row comparison of a replaying run with the run it replays."""
    compare("prefill", got["prefill"], want["prefill"])
    compare("decode", got["decode"], want["decode"])
    grown = grown_rows(reqs, want["decode"], page)
    check(len(grown) > 0, "no decode row read a grown page")
    compare("decode after page growth", got["decode"], want["decode"], grown)


def check_run(eng, done, reqs) -> None:
    """Every request finished in full and the page accounting balances."""
    check(len(done) == len(reqs), f"{len(done)}/{len(reqs)} requests done")
    for r in done:
        check(len(r.out_tokens) == r.max_new_tokens,
              f"request {r.rid}: {len(r.out_tokens)} of "
              f"{r.max_new_tokens} tokens")
    eng.kv.audit()  # raises on any refcount / page-table inconsistency


def memory_report(eng) -> None:
    """Compile the decode and COW steps ahead of time, print their memory
    analysis (per device under a mesh) and every device's peak memory, and
    hold each step's temp under a twentieth of the pool: the steps update
    the donated pool where it lies, in its stored page order, and keep no
    pool-sized buffer beside it."""
    import jax
    import jax.numpy as jnp

    B = eng.ec.max_seqs
    dec = eng._decode.lower(
        eng.params, eng.kv.data, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B,), jnp.int32), eng.kv.page_table(),
        jnp.zeros((B,), bool),
    ).compile()
    cow = eng.kv._cow_step().lower(
        eng.kv.data, jnp.int32(0), jnp.int32(0)).compile()
    pool = eng.kv.cache_bytes_per_device()
    for name, c in (("decode_step", dec), ("cow_copy", cow)):
        ma = c.memory_analysis()
        kernels = c.as_text().count("tpu_custom_call")
        print(f"{name} memory per device: argument "
              f"{ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} "
              f"B, alias {ma.alias_size_in_bytes} B (pool {pool} B per "
              f"device, temp/pool {ma.temp_size_in_bytes / pool:.3f}); "
              f"{kernels} tpu_custom_call", flush=True)
        check(kernels >= 1, f"{name} has no compiled Pallas kernel")
        check(ma.temp_size_in_bytes < pool / 20,
              f"{name} holds {ma.temp_size_in_bytes} B of temp beside a "
              f"{pool} B pool: the pool is copied, not updated in place")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"device {d.id} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')} of bytes_limit "
              f"{stats.get('bytes_limit')}", flush=True)


def one_chip(cfg, params, seed: int) -> None:
    import jax

    reqs = workload(cfg, seed)
    print(f"workload: {len(reqs)} requests, prompts "
          f"{[len(r['prompt']) for r in reqs]}, new tokens "
          f"{[r['max_new_tokens'] for r in reqs]}, arrivals "
          f"{[r['arrival_step'] for r in reqs]}", flush=True)

    compile_s = compile_clock()
    eng, done, wall, rows_p = serve(cfg, params, reqs, backend="pallas",
                                    seed=seed)
    check_run(eng, done, reqs)
    leaf = jax.tree.leaves(eng.kv.data)[0]
    pool = f"{tuple(leaf.shape)} {leaf.dtype}"
    del leaf  # an array kept here would stay on the chip past release()
    waited = sum(int(r.stats.queue_steps > 0) for r in done)
    tokens = sum(r.max_new_tokens for r in done)
    print(f"pallas engine: {eng.step_count} steps, {eng.decode_steps} "
          f"decode steps, {eng.prefill_chunks} prefill chunks, "
          f"{waited} requests waited for a slot, "
          f"{eng.kv.pages_aliased} pages aliased, {eng.kv.cow_copies} COW "
          f"copies; pool leaf {pool}, "
          f"{eng.kv.cache_bytes()} B in all", flush=True)
    print(f"pallas engine: compile {compile_s():.1f} s, wall {wall:.1f} s; "
          f"{tokens / wall:.1f} tok/s (smoke, incl. compile and a logit "
          f"sync per step — not a benchmark)", flush=True)
    check(eng.prefill_chunks > len(reqs), "no prompt took several chunks")
    check(waited >= 1, "no admission waited for a free slot")
    check(eng.kv.pages_aliased >= 2 and eng.kv.cow_copies >= 1,
          "the shared prefix was not aliased and copied on write")
    memory_report(eng)
    release(eng)

    compile_s = compile_clock()
    follow = {r.rid: r.out_tokens for r in done}
    ref, done_r, wall_r, rows_r = serve(cfg, params, reqs,
                                        backend="reference", seed=seed,
                                        follow=follow)
    check_run(ref, done_r, reqs)
    print(f"reference engine (replaying the pallas tokens): compile "
          f"{compile_s():.1f} s, wall {wall_r:.1f} s", flush=True)
    release(ref)
    compare_runs(rows_p, rows_r, reqs, cfg.block)


def four_chips(cfg, params, seed: int) -> None:
    import jax

    from repro.launch.mesh import make_serve_mesh

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    reqs = workload(cfg, seed)
    one, done_1, wall_1, rows_1 = serve(cfg, params, reqs, backend="pallas",
                                        seed=seed)
    check_run(one, done_1, reqs)
    print(f"single-device engine: wall {wall_1:.1f} s", flush=True)
    release(one)

    mesh = make_serve_mesh("1x4")
    follow = {r.rid: r.out_tokens for r in done_1}
    tp, done_4, wall_4, rows_4 = serve(cfg, params, reqs, backend="pallas",
                                       mesh=mesh, seed=seed, follow=follow)
    check_run(tp, done_4, reqs)
    total, per_dev = tp.kv.cache_bytes(), tp.kv.cache_bytes_per_device()
    print(f"1x4 mesh engine (replaying the single-device tokens): wall "
          f"{wall_4:.1f} s (smoke, incl. compile — not a benchmark); pool "
          f"{total} B in all, {per_dev} B per device, {tp.kv.cow_copies} "
          f"COW copies", flush=True)
    check(per_dev * 4 == total,
          f"per-device pool bytes {per_dev} != {total} / 4")
    memory_report(tp)
    release(tp)
    compare_runs(rows_4, rows_1, reqs, cfg.block)


_COMPILE_S: list = []  # backend compile durations, appended by _on_event


def _on_event(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S.append(duration)


def compile_clock():
    """Seconds JAX has spent in backend compiles since this call."""
    start = sum(_COMPILE_S)
    return lambda: sum(_COMPILE_S) - start


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4 tensor-parallel comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        import jax
        import jax.numpy as jnp

        import repro.configs as C
        from repro.launch.compile_cache import use_compile_cache
        from repro.models import attention as attn
        from repro.models import model as M
    except ImportError as e:
        fail(f"cannot import the program ({e}); run from the repo root")

    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    check(jax.default_backend() == "tpu",
          f"JAX runs on {jax.default_backend()!r}, not a TPU")
    check(not attn.kernels_interpreted(),
          "the paged kernels would run in interpret mode")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)

    cfg = C.get_config(ARCH, dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads x {cfg.d_head}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
          f"{sum(x.size for x in jax.tree.leaves(params))} bf16 params "
          f"initialised from seed {args.seed} in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    if args.four_chips:
        four_chips(cfg, params, args.seed)
    else:
        one_chip(cfg, params, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
