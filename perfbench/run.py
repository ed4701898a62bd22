#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 perfbench/run.py --workload minicpm-2b.chat --seed 7 \\
        --seconds 30 --trace 0

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file and its architecture's module
(``perfbench/families/<family>.py``, :mod:`model`),
``perfbench/traffic/<traffic>.json``, the limits of the
check in ``perfbench/limits/<cell>.json``, and, with ``--trace 1``, one
reader per per-layer metric in ``perfbench/metrics/<metric>.py``.  A metric
named ``<quantity>.<part>`` (one quantity split by the end-to-end metric it
moves) takes ``<quantity>.py`` where it has no file of its own, and an
end-to-end metric so named reports the loop's ``<quantity>``.

The run makes the weights from ``--seed`` on the device, builds
``repro.serve.Engine`` with the Pallas backend, warms up every program the
cell's traffic reaches (``setup_s`` ends there), serves the seed's open-loop
schedule for ``--seconds``, and then checks the served tokens against the
plain float32 reference (:mod:`check`).  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` reports its per-layer metrics, from the
same loop with a profiler sub-window in its middle.  The last line of
standard output is one JSON object; the numbers compared are the last lines
of standard error.

``--rehearse`` runs the same path at the configuration's ``rehearsal``
sizes on whatever JAX finds (CPU, interpreted kernels); without it a run
that finds no TPU, or fewer chips than the cell asks for, fails and prints
no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

T_IMPORT = time.perf_counter()
TRACE_S = 3.0  # the profiler sub-window of a --trace 1 run
DRAIN_S = 60.0  # how long the loop waits for the window's last first tokens
REHEARSAL_DRAIN_S = 5.0
# host spans read back from the trace: the loop's own (Recorder) and the
# engine's (repro.serve.obs), by which breakdown.idle_gaps names each gap
HOST_SPANS = ("bench.window", "engine.step", "decode_step", "prefill_chunk",
              "host_sample", "serve.step", "serve.schedule", "serve.prefill",
              "serve.decode", "serve.sample", "serve.flush", "serve.sync",
              "serve.bookkeeping")


def fail(msg: str, code: int = 3):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        stat = Path("/proc/self/stat").read_text()
        start = int(stat.rsplit(")", 1)[1].split()[19]) / os.sysconf("SC_CLK_TCK")
        up = float(Path("/proc/uptime").read_text().split()[0])
        return max(0.0, up - start)
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def use_compile_cache(jax) -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where the environment sets one, else ``.jax_cache/`` in the checkout (a
    fixed path: the path is part of the cache's key).  Every program is
    kept, however quick to compile, so that a second run compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def load_cell(name: str, rehearse: bool = False):
    """The benchmark, the cell, its configuration entry and its traffic mix
    (with the mix's ``rehearsal`` keys applied when asked: a smoke model
    reaches its steady state at once and serves many more requests)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r}; known: {sorted(cells)}", 2)
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    if rehearse:
        mix = {**mix, **mix["rehearsal"]}
    return bench, cell, cfg_entry, mix


def quantity(name: str) -> str:
    """``ttft_p50_ms.code`` -> ``ttft_p50_ms``."""
    return name.split(".", 1)[0]


def reports(m, cell) -> bool:
    return cell["name"] in m.get("workloads", [cell["name"]])


def metric_readers(bench, cell):
    """The per-layer metrics this cell reports, with their reader modules."""
    out = []
    for m in bench["per_layer"]:
        if not reports(m, cell):
            continue
        path = HERE / "metrics" / f"{m['name']}.py"
        if not path.exists():
            path = HERE / "metrics" / f"{quantity(m['name'])}.py"
        spec = importlib.util.spec_from_file_location(f"metric_{m['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m, mod))
    return out


def device_info(jax, n: int):
    devs = jax.devices()[:n]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n, "memory_peak_bytes": int(peak)}


class Recorder:
    """The traced run's own spans around the engine's calls, and what the
    per-layer readers need from the loop: decode contexts and prefill
    chunks inside the profiler window, pool use and the scheduler's
    counters over the measured window."""

    def __init__(self, eng, jax, seconds: float, logdir: str):
        self.eng, self.jax, self.seconds, self.logdir = eng, jax, seconds, logdir
        self.on = False
        self.decode_ctx = []  # per decode call in the profiler window
        self.chunks = []  # (offset, tokens, final) per prefill chunk in the window
        self.pages_used = []  # per step in the measured window
        self.snap0 = self.snap1 = None
        self.prof_start = None  # window-relative start of the profiler
        self._span = None
        A = jax.profiler.TraceAnnotation
        dec, chunk, samp, step = eng._decode, eng._chunk_fn, eng._sample, eng.step

        def decode(*a):
            if self.on:
                self.decode_ctx.append([r.next_pos + 1 for _, r in eng.sched.decoding])
            with A("decode_step"):
                return dec(*a)

        def prefill_chunk(*a):
            if self.on:
                for _, r in eng.sched.prefilling:
                    sp = r.timeline.spans[-1]
                    if sp.name == "prefill-chunk" and sp.open:
                        off, n = sp.attrs["off"], sp.attrs["tokens"]
                        self.chunks.append((off, n, off + n >= r.prefill_target))
            with A("prefill_chunk"):
                return chunk(*a)

        def sample(*a):
            with A("host_sample"):
                return samp(*a)

        def engine_step():
            with A("engine.step"):
                step()

        eng._decode, eng._chunk_fn, eng._sample = decode, prefill_chunk, sample
        eng.step = engine_step

    def before_step(self, t: float) -> None:
        eng = self.eng
        if 0.0 <= t < self.seconds:
            if self.snap0 is None:
                self.snap0 = eng.metrics()
            total = eng.kv.pool_stats()["pages_total"]
            self.pages_used.append((total - eng.kv.available_pages) / total)
        elif t >= self.seconds and self.snap1 is None:
            self.snap1 = eng.metrics()
        # from the window's middle, start at the first step that has a
        # prompt to prefill, so that the sub-window holds both programs
        start = max(0.0, self.seconds / 2 - TRACE_S / 2)
        pending = eng.sched.pending or eng.sched.queue or eng.sched.prefilling
        late = t >= max(start, self.seconds - TRACE_S)
        if self.prof_start is None and t >= start and (pending or late):
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no event per Python call
            # nothing in flight: every program run in the trace is one
            # that the recorder saw dispatched
            self.jax.block_until_ready(eng.kv.data)
            self.jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self._span = self.jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.prof_start = t
            self.on = True
        elif self.on and t >= self.prof_start + TRACE_S:
            self.stop()

    def stop(self) -> None:
        if not self.on:
            return
        self.on = False
        self.jax.block_until_ready(self.eng.kv.data)
        self._span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()


def per_layer(rec: Recorder, w, spec, peaks, readers, trace_mod, rehearse):
    """Reduce the trace and run each metric's reader."""
    import model as MB

    fam = MB.family(spec)
    ex = trace_mod.extract(trace_mod.find_xplane(rec.logdir), HOST_SPANS)
    if rehearse and not ex["devices"]:  # a CPU trace has no device plane
        readers = [(m, mod) for m, mod in readers if m["source"] != "device_trace"]
        ex["devices"] = {"/device:CPU:0": {trace_mod.OPS_LINE: []}}
    span = [h for h in ex["host"] if h[0] == "bench.window"]
    if not span:
        fail("the trace holds no bench.window span")
    lo, hi = span[0][1], span[0][1] + span[0][2]
    red = trace_mod.reduce(ex, lo, hi)
    print(f"perfbench: trace planes and lines {ex['lines_seen']}; programs "
          f"{trace_mod.top_modules(red)}", file=sys.stderr)
    if not rehearse:
        try:
            runs = trace_mod.step_programs(red, *fam.decode_kernel(spec))
        except ValueError as e:
            fail(f"the step programs are not what the readers read: {e}")
        seen = {"decode": len(rec.decode_ctx), "prefill": len(rec.chunks)}
        print(f"perfbench: step program runs traced {runs}, dispatched "
              f"{seen}", file=sys.stderr)
        # a run at each edge of the window may fall out by clock skew
        if any(abs(runs[k] - seen[k]) > 1 or (seen[k] > 0) != (runs[k] > 0)
               for k in seen):
            fail(f"the trace holds step program runs {runs} where the loop "
                 f"dispatched {seen}: the engine's calls were fused, renamed "
                 f"or moved, and the readers would misread them")
    ctx = SimpleNamespace(spec=spec, family=fam, peaks=peaks, window=w, red=red,
                          decode_ctx=rec.decode_ctx, chunks=rec.chunks,
                          pages_used=rec.pages_used, snap0=rec.snap0,
                          snap1=rec.snap1, trace=trace_mod)
    metrics = {}
    for m, mod in readers:
        v = mod.read(ctx)
        if v is None:
            fail(f"{m['name']} found nothing to read in a cell that lists it")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": trace_mod.top_ops(red),
                 "idle_gaps": trace_mod.idle_gaps(red)}
    if not rehearse and runs["other_kernels"]:
        breakdown["other_kernels"] = trace_mod.other_kernels(runs)
    return metrics, breakdown, trace_mod.busy_s(red), red["window_ns"] / 1e9


def warm_up(eng, vocab: int, n_slots: int) -> None:
    """Serve one request per slot, with final prefill chunks of every
    length bucket the engine can emit, so each program the traffic can
    reach (decode step, every chunk shape, the per-slot sampling ops) is
    compiled, or loaded from the cache, before the window."""
    import numpy as np

    from repro.serve import engine as E

    page = eng.kv.page_size
    sizes = E.chunk_shape_set(eng.cfg, eng.chunk_size)
    lens = [page + s for s in sizes]
    lens += [page + 1] * max(0, n_slots - len(lens))
    rng = np.random.default_rng(0)
    for i, n in enumerate(lens):
        eng.submit(rng.integers(0, vocab, n, dtype=np.int32), 3,
                   rid=10**9 + i)
    eng.run()


def build(spec, mix, seed: int, chips: int):
    """The engine the window drives, with its weights from ``seed`` and
    every program it will run compiled or loaded; and the sampler's law."""
    import jax

    import model as MB
    from repro.serve import Engine, EngineConfig

    srv = spec["serving"]
    cfg = MB.model_config(spec)
    mesh = shardings = None
    if srv.get("mesh"):
        from repro.distributed import sharding as SH
        from repro.launch.mesh import make_serve_mesh
        from repro.models import model as M

        mesh = make_serve_mesh(srv["mesh"])
        if mesh.devices.size != chips:
            fail(f"mesh {srv['mesh']} is not the cell's {chips} chips", 2)
        shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        shardings = SH.named(mesh, SH.param_pspecs(cfg, mesh, shapes, mode="serve"))
    params = MB.program_params(spec, cfg, seed, shardings)
    sampler = {"seed": MB.seed31(seed), "temperature": mix["temperature"]}
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=srv["max_seqs"], max_len=srv["max_len"],
        page_size=srv["page_size"], num_pages=srv["num_pages"],
        backend="pallas", temperature=mix["temperature"],
        eos_id=spec["eos_token_id"], seed=sampler["seed"]), mesh=mesh)
    del params
    warm_up(eng, MB.family(spec).vocab(spec)[0], srv["max_seqs"])
    jax.block_until_ready(eng.kv.data)
    return eng, sampler


def release(eng) -> None:
    """Drop the engine's device state (pool, weights, last tokens)."""
    eng.kv.data = None
    eng._last_tok = None
    eng.params = None
    gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU-sized smoke run of the same path (no metrics "
                         "of any device)")
    args = ap.parse_args(argv)

    bench, cell, cfg_entry, mix = load_cell(args.workload, args.rehearse)
    import model as MB

    spec = MB.load(ROOT / cfg_entry["file"], rehearse=args.rehearse)
    # the persistent compilation cache lives in the checkout, at a fixed path
    import jax

    if not args.rehearse:
        use_compile_cache(jax)
    chips = cell["chips"]
    if not args.rehearse:
        if jax.default_backend() != "tpu":
            fail(f"JAX finds no TPU (backend {jax.default_backend()!r})")
        if len(jax.devices()) < chips:
            fail(f"the cell needs {chips} chips, JAX finds {len(jax.devices())}")
    elif len(jax.devices()) < chips:
        fail(f"the rehearsal needs {chips} devices (XLA_FLAGS="
             f"--xla_force_host_platform_device_count={chips})")
    peaks = json.loads((HERE / "peaks.json").read_text())
    kind = jax.devices()[0].device_kind
    if not args.rehearse and kind not in peaks:
        fail(f"device kind {kind!r} has no entry in perfbench/peaks.json")
    peaks = peaks.get(kind)

    import check
    import loop
    import trace as trace_mod
    import traffic

    eng, sampler = build(spec, mix, args.seed, chips)
    plan = traffic.schedule(mix, args.seconds, args.seed,
                            MB.family(spec).vocab(spec)[0], eng.kv.max_len)
    readers = metric_readers(bench, cell) if args.trace else []
    rec = None
    logdir = None
    if args.trace:
        (ROOT / ".perfbench_traces").mkdir(exist_ok=True)
        logdir = tempfile.mkdtemp(prefix="trace-", dir=ROOT / ".perfbench_traces")
        rec = Recorder(eng, jax, args.seconds, logdir)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(d)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    setup_s = process_age_s()

    w = loop.run(eng, plan, args.seconds,
                 drain_s=REHEARSAL_DRAIN_S if args.rehearse else DRAIN_S,
                 before_step=rec.before_step if rec else None)
    if rec:
        if rec.prof_start is None:
            fail("no engine step came late enough in the window to trace")
        rec.stop()
        if rec.snap1 is None:  # the window's last step ended the loop
            rec.snap1 = eng.metrics()
    n_compiles = len(compiles)
    device = device_info(jax, chips)
    meas = w.measured()
    result = {"correct": False, "attempted": len(meas),
              "failed": len(w.missing())}
    if args.trace:
        try:
            metrics, breakdown, busy, window_s = per_layer(
                rec, w, spec, peaks, readers, trace_mod, args.rehearse)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)  # traces are large
        device.update(busy_s=busy, window_s=window_s)
    else:
        e2e = loop.end_to_end(w)
        metrics = {m["name"]: {"value": e2e[quantity(m["name"])], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if reports(m, cell) and e2e.get(quantity(m["name"])) is not None}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        print(f"perfbench: {e2e['_n_ttft']} requests due in the window, "
              f"{e2e['_missing']} without a first token, {e2e['_n_itl']} "
              f"token gaps, {e2e['_tokens']} tokens in the window; submissions "
              f"late by {e2e['_late_p50_ms']} ms (median), "
              f"{e2e['_late_max_ms']} ms (max)", file=sys.stderr)
        print("perfbench: end-to-end " + json.dumps(
            {k: v for k, v in e2e.items() if not k.startswith("_")}),
            file=sys.stderr)
    print(f"perfbench: {len(w.steps)} engine steps, {n_compiles} compiles in "
          f"the window, {eng.metrics()['counters']['preemptions_total']} "
          f"preemptions, setup {setup_s} s", file=sys.stderr, flush=True)

    release(eng)  # the program's state goes before the reference takes the chip
    t_check = time.perf_counter()
    res = check.compare(spec, args.seed, w, sampler,
                        check.limits(ROOT, cell["name"]), args.seed)
    print(f"perfbench: checked {res['tokens']} served tokens of "
          f"{res['requests']} requests in {time.perf_counter() - t_check} s",
          file=sys.stderr)
    result.update(correct=res["correct"], metrics=metrics, device=device)
    if args.trace:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = True
    result["compared"] = res["compared"]
    for name, c in res["compared"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
