#!/usr/bin/env python3
"""Readings that the check's limits are set from, many seeds in one process.

    python3 perfbench/calibrate.py --workload minicpm-2b.chat \\
        --seeds 1,2,3 --seconds 10 [--control fp8] [--fault state_unchanged]

For each seed: weights from the seed, the engine, a short window of the
cell's own traffic at its own load (as ``run.py`` serves it), then the
check on the same sample of finished requests, read twice: with the served
tokens (the program's reading) and, with ``--control``, with the tokens a
reference in that lower precision would put first (the control's reading).
With ``--fault``, the program runs with that fault planted under the timed
path (:mod:`faults`), and its reading is the fault's.
One JSON line per seed on standard output.  The benchmark's runs never run
this; ``PERF.md`` gives the readings and the limits set from them.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="", help="reference precision, e.g. fp8")
    ap.add_argument("--fault", default="", help="plant a fault, e.g. "
                    "state_unchanged (faults.FAULTS)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    bench, cell, cfg_entry, mix = run.load_cell(args.workload, args.rehearse)
    import model as MB

    spec = MB.load(run.ROOT / cfg_entry["file"], rehearse=args.rehearse)
    import jax

    if not args.rehearse:
        run.use_compile_cache(jax)
        if jax.default_backend() != "tpu":
            run.fail("JAX finds no TPU")
    import check
    import faults
    import loop
    import traffic

    if args.fault:
        faults.plant(setattr, args.fault)

    lim = check.limits(run.ROOT, cell["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        eng, sampler = run.build(spec, mix, seed, cell["chips"])
        plan = traffic.schedule(mix, args.seconds, seed,
                                MB.family(spec).vocab(spec)[0], eng.kv.max_len)
        w = loop.run(eng, plan, args.seconds, drain_s=run.DRAIN_S)
        peak = run.device_info(jax, cell["chips"])["memory_peak_bytes"]
        run.release(eng)
        del eng
        out = {"seed": seed, "peak_bytes": peak}
        for name, ctl in (("program", ""), ("control", args.control)):
            if name == "control" and not ctl:
                continue
            res = check.compare(spec, seed, w, sampler, lim, seed, control=ctl)
            key = (args.fault or name) if name == "program" else name
            out[key] = res["compared"]["logit_gap_max"]["value"]
            out["tokens"], out["requests"] = res["tokens"], res["requests"]
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
