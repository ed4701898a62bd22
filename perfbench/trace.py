"""From a profiler trace to device busy time, program and kernel times.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain structure: for each device plane its op line and its module line,
and the host's annotation spans (the engine's ``serve.*`` spans and the
loop's own ``TraceAnnotation``s), as ``[name, start_ns, duration_ns]``
lists on one clock.  :func:`reduce` works on that structure alone, so a
small recorded extract (``perfbench/testdata/trace_named.json.gz``) checks
it without a chip.

Names, as found in chip traces (TPU v5 lite, jax 0.9): the device plane
``/device:TPU:0`` has the lines ``XLA Ops`` (one event per HLO op, named by
the op's whole HLO text) and ``XLA Modules`` (one event per program run,
named ``jit_<function>(<id>)``).  The engine's step programs are
``jit__paged_step`` (decode) and ``jit_prefill_chunk`` (prefill chunk); a
Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` whose op is named after the
kernel (``%paged_attention_decode.13 = ...``).  The host's Python thread
is the line ``python3``.  :func:`step_programs` checks, before any reader
reads, that the step programs still look so: each decode-step run holds
the family's decode kernel once per layer and each prefill-chunk run
holds it not at all.  Other named kernels in either program are reported,
not refused: the readers match their own kernel by name.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
HOST_LINE = re.compile(r"^python")  # the Python thread ("python3" on the chip)


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def extract(path: str, host_names: Iterable[str] = ()) -> Dict:
    """Device op/module events and the host spans named in ``host_names``."""
    import jax

    keep = set(host_names)
    pd = jax.profiler.ProfileData.from_file(path)
    out: Dict = {"devices": {}, "host": [], "lines_seen": {}}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        out["lines_seen"][plane.name] = sorted(lines)
        if DEVICE_PLANE.match(plane.name):
            out["devices"][plane.name] = {
                name: [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in lines[name].events]
                for name in (OPS_LINE, MODULES_LINE) if name in lines
            }
        elif plane.name == HOST_PLANE:
            out["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                            for name, ln in lines.items() if HOST_LINE.match(name)
                            for e in ln.events if e.name in keep]
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(events, lo: int, hi: int):
    """Events cut to [lo, hi)."""
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b - a


def match(pattern: str, events) -> List[list]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def reduce(ex: Dict, lo: int, hi: int) -> Dict:
    """The traced window [lo, hi) (ns, host clock of the trace): per device
    busy ns (union of op intervals) and the clipped op and module events;
    the window's length; the longest idle gaps with the host span open at
    each gap's middle."""
    if not ex["devices"]:
        raise ValueError(f"no device plane in the trace; planes and lines: "
                         f"{ex['lines_seen']}")
    devices = {}
    for name, lines in sorted(ex["devices"].items()):
        if OPS_LINE not in lines:
            raise ValueError(f"{name} has no {OPS_LINE!r} line: "
                             f"{ex['lines_seen'].get(name)}")
        ops = list(clip(lines[OPS_LINE], lo, hi))
        # whole program runs only: a call cut by the window's edge is left out
        mods = [e for e in lines.get(MODULES_LINE, [])
                if e[1] >= lo and e[1] + e[2] <= hi]
        busy = union([(s, s + d) for _, s, d in ops])
        devices[name] = {"ops": ops, "modules": mods, "busy": busy,
                         "busy_ns": sum(e - s for s, e in busy)}
    return {"window_ns": hi - lo, "devices": devices, "host": ex["host"],
            "lo": lo, "hi": hi}


def busy_s(red: Dict) -> float:
    """Device-busy seconds, averaged over the devices traced."""
    devs = red["devices"].values()
    return sum(d["busy_ns"] for d in devs) / len(devs) / 1e9


def top_ops(red: Dict, n: int = 10) -> List[list]:
    """The device ops (by name, numbering stripped) that took most time,
    summed over devices and divided by their count."""
    tot: Dict[str, int] = {}
    for d in red["devices"].values():
        for name, _, dur in d["ops"]:
            key = re.sub(r"[.\d]+$", "", name)
            tot[key] = tot.get(key, 0) + dur
    k = len(red["devices"])
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def top_modules(red: Dict, n: int = 12) -> List[list]:
    """Programs (by name) that took most device time, with their calls."""
    tot: Dict[str, list] = {}
    for d in red["devices"].values():
        for name, _, dur in d["modules"]:
            t = tot.setdefault(name, [0, 0])
            t[0] += 1
            t[1] += dur
    return [[name, c, ns / 1e9] for name, (c, ns) in
            sorted(tot.items(), key=lambda x: -x[1][1])[:n]]


def idle_gaps(red: Dict, n: int = 10) -> List[list]:
    """The longest idle gaps of the first device, named by the innermost
    host span open at the gap's middle (``idle`` where none is)."""
    dev = red["devices"][sorted(red["devices"])[0]]
    edges = [red["lo"]] + [x for iv in dev["busy"] for x in iv] + [red["hi"]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        open_ = [h for h in red["host"] if h[1] <= mid < h[1] + h[2]]
        name = min(open_, key=lambda h: h[2])[0] if open_ else "idle"
        out.append([name, (e - s) / 1e9])
    return out


def module_calls(red: Dict, pattern: str) -> Optional[Tuple[int, float]]:
    """(calls, device seconds) of the programs whose name matches,
    averaged over devices; None where none ran in the window."""
    per = []
    for d in red["devices"].values():
        ev = match(pattern, d["modules"])
        if ev:
            per.append((len(ev), sum(e[2] for e in ev)))
    if not per:
        return None
    k = len(red["devices"])
    return sum(c for c, _ in per) // len(per), sum(t for _, t in per) / k / 1e9


def op_seconds(red: Dict, pattern: str, within: Optional[str] = None
               ) -> Optional[float]:
    """Device seconds of the ops whose name matches (and, with ``within``,
    that start inside a whole run of a program whose name matches it),
    averaged over devices."""
    tot = []
    for d in red["devices"].values():
        ops = match(pattern, d["ops"])
        if within is not None:
            mods = sorted((m[1], m[1] + m[2]) for m in match(within, d["modules"]))
            starts = [a for a, _ in mods]
            ops = [o for o in ops
                   if (i := bisect.bisect_right(starts, o[1]) - 1) >= 0
                   and o[1] < mods[i][1]]
        tot.append(sum(e[2] for e in ops))
    if not any(tot):
        return None
    return sum(tot) / len(tot) / 1e9


# the engine's step programs (see the module's docstring)
DECODE_PROGRAM = r"^jit__paged_step\("
PREFILL_PROGRAM = r"^jit_prefill_chunk\("
KERNEL_OP = r'custom_call_target="tpu_custom_call"'
OP_NAME = re.compile(r"^%?([\w-]+?)(\.\d+)?(\s|=|$)")


def op_name(text: str) -> str:
    """An op's name without its HLO numbering: ``paged_copy`` of
    ``%paged_copy.5 = bf16[...] custom-call(...)``."""
    m = OP_NAME.match(text)
    return m.group(1) if m else text


def kernel_op(name: str) -> str:
    """The pattern of the ops of the Pallas kernel named ``name``."""
    return rf"^%?{re.escape(name)}(\.\d+)?\s.*{KERNEL_OP}"


def step_programs(red: Dict, kernel: str, calls: int) -> Dict:
    """Whole runs of the decode-step and prefill-chunk programs in the
    window, per device, and the calls of other kernels inside them:
    ``{"decode": n, "prefill": n, "other_kernels": {name: calls},
    "other_kernel_s": {name: seconds}}``, the seconds averaged over devices.
    Raises where a decode run holds other than ``calls`` calls of the
    decode kernel ``kernel``, where a prefill run holds any, or where the
    devices disagree on the runs or calls."""
    ours = re.compile(kernel_op(kernel))
    seen, other_ns = [], {}
    for dev, d in sorted(red["devices"].items()):
        kernels = sorted((s, name, dur) for name, s, dur in match(KERNEL_OP, d["ops"]))
        starts = [s for s, _, _ in kernels]
        got = {"decode": 0, "prefill": 0, "other_kernels": {}}
        for part, pattern, want in (("decode", DECODE_PROGRAM, calls),
                                    ("prefill", PREFILL_PROGRAM, 0)):
            for m in match(pattern, d["modules"]):
                inside = kernels[bisect.bisect_left(starts, m[1]):
                                 bisect.bisect_left(starts, m[1] + m[2])]
                k = sum(1 for _, name, _ in inside if ours.search(name))
                if k != want:
                    raise ValueError(
                        f"{dev}: a run of {m[0]} holds {k} calls of the "
                        f"decode kernel {kernel!r}, not {want}: the readers "
                        f"would misread the step")
                other = got["other_kernels"]
                for _, name, dur in inside:
                    if not ours.search(name):
                        other[op_name(name)] = other.get(op_name(name), 0) + 1
                        other_ns[op_name(name)] = other_ns.get(op_name(name), 0) + dur
                got[part] += 1
        seen.append(got)
    if any(g != seen[0] for g in seen):
        raise ValueError(f"the devices ran different step programs: {seen}")
    return {**seen[0], "other_kernel_s": {
        name: ns / len(seen) / 1e9 for name, ns in other_ns.items()}}


def other_kernels(runs: Dict, n: int = 10) -> List[list]:
    """The result's ``breakdown.other_kernels``: the kernels other than the
    family's decode kernel that ran inside the step programs, with their
    device seconds, most first. No reader counts their work, so a share of
    the decode kernel's roofline leaves them out."""
    return [[name, s] for name, s in
            sorted(runs["other_kernel_s"].items(), key=lambda x: -x[1])[:n]]
