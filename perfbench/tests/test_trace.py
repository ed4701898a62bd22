"""The trace-to-metric reduction, on a small trace recorded on the chip.

``perfbench/testdata/trace_small.json.gz`` is 0.6 s of a TPU v5 lite trace
of ``minicpm-2b.chat`` as :func:`trace.extract` writes it: two runs of the
prefill-chunk program, two of the decode step, and the small programs of
host sampling between them.  Run by hand from the repo root:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_trace.py
"""
import gzip
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE)]

import model as MB  # noqa: E402
import trace as T  # noqa: E402

DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    ex = json.loads(gzip.open(HERE / "testdata" / "trace_small.json.gz").read())
    _, lo, dur = ex["host"][0]
    return ex, T.reduce(ex, lo, lo + dur)


def reader(name):
    spec = importlib.util.spec_from_file_location(name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ctx(red, **kw):
    spec = MB.load(HERE / "configs" / "minicpm-2b.json")
    peaks = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    return SimpleNamespace(red=red, trace=T, spec=spec, peaks=peaks, **kw)


def big_runs(ex):
    """The step programs' runs, by hand: the two ids seen in the slice."""
    mods = ex["devices"][DEV]["XLA Modules"]
    return [m for m in mods if m[0].startswith("jit__unknown(")]


def test_names_found(recorded):
    ex, red = recorded
    assert T.OPS_LINE in ex["lines_seen"][DEV] and T.MODULES_LINE in ex["lines_seen"][DEV]
    runs = big_runs(ex)
    assert len({m[0] for m in runs}) == 2  # the decode step and one chunk shape


def test_busy_is_the_union_of_ops(recorded):
    ex, red = recorded
    lo, hi = red["lo"], red["hi"]
    # a millisecond grid, by hand: busy where any op covers the point
    cover = [False] * ((hi - lo) // 1_000_000)
    for _, s, d in ex["devices"][DEV]["XLA Ops"]:
        for i in range(max(0, (s - lo) // 1_000_000), min(len(cover), (s + d - lo) // 1_000_000)):
            cover[i] = True
    by_grid = sum(cover) / 1e3
    assert abs(T.busy_s(red) - by_grid) < 0.01
    assert 0 < T.busy_s(red) < red["window_ns"] / 1e9
    idle = reader("device_idle_share").read(ctx(red))
    assert idle == pytest.approx(100 * (1 - T.busy_s(red) * 1e9 / red["window_ns"]))


def test_step_programs_are_told_apart(recorded):
    ex, red = recorded
    runs = big_runs(ex)
    kernel = [o for o in ex["devices"][DEV]["XLA Ops"] if "tpu_custom_call" in o[0]]
    dec = [m for m in runs if any(m[1] <= o[1] < m[1] + m[2] for o in kernel)]
    pre = [m for m in runs if m not in dec]
    assert len(dec) == 2 and len(pre) == 2
    want_dec = sum(m[2] for m in dec) / len(dec) / 1e6
    want_pre = sum(m[2] for m in pre) / len(pre) / 1e6
    assert reader("decode_step_ms").read(ctx(red)) == pytest.approx(want_dec)
    assert reader("prefill_chunk_ms").read(ctx(red)) == pytest.approx(want_pre)
    # the kernel runs once per layer in each decode step
    assert len(kernel) == 2 * 40


def test_roofline_and_mfu_from_counts(recorded):
    ex, red = recorded
    kernel = [o for o in ex["devices"][DEV]["XLA Ops"] if "tpu_custom_call" in o[0]]
    t_kernel = sum(o[2] for o in kernel) / 1e9
    decode_ctx = [[300] * 12, [301] * 12]  # 12 slots of 300 tokens, twice
    c = ctx(red, decode_ctx=decode_ctx, chunks=[(0, 128, False), (128, 100, True)])
    m = MB.dims(c.spec)
    kv = 2 * 40 * 36 * 64 * 2 * (300 * 12 + 301 * 12)  # K+V bytes, all layers
    qo = 2 * 40 * 36 * 64 * 2 * 24
    share = reader("paged_decode_roofline").read(c)
    assert share == pytest.approx(100 * (kv + qo) / 819e9 / t_kernel)
    assert 0 < share <= 100
    p = MB.param_count(c.spec)
    assert p["layers"] == 40 * (4 * 2304 * 2304 + 3 * 2304 * 5760)
    att = 4 * 40 * 36 * 64
    f = (24 * 2 * (p["layers"] + p["head"]) + att * (300 * 12 + 301 * 12)
         + 2 * p["layers"] * 228 + att * (128 * 129 // 2 + 100 * 128 + 100 * 101 // 2)
         + 2 * p["head"])
    assert reader("step_mfu").read(c) == pytest.approx(
        100 * f / (red["window_ns"] / 1e9 * 197e12))
    assert m["Vp"] == 122880


def test_breakdown_lists(recorded):
    _, red = recorded
    ops, gaps = T.top_ops(red), T.idle_gaps(red)
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(ops[i][1] >= ops[i + 1][1] for i in range(len(ops) - 1))
    assert sum(g for _, g in gaps) <= red["window_ns"] / 1e9 - T.busy_s(red) + 1e-9


def test_step_programs_are_checked(recorded):
    ex, red = recorded
    assert T.step_programs(red, 40) == {"decode": 2, "prefill": 2}
    with pytest.raises(ValueError, match="kernel calls"):
        T.step_programs(red, 32)  # a decode run is not one call per layer
    # a kernel planted in a prefill-chunk run: the readers would count it
    # as the paged-decode kernel, so the check refuses the trace
    pre = [m for m in big_runs(ex) if m[2] < 80e6][0]
    ops = ex["devices"][DEV]["XLA Ops"] + [
        ['custom-call custom_call_target="tpu_custom_call"', pre[1] + 1000, 10]]
    planted = {**ex, "devices": {DEV: {**ex["devices"][DEV], "XLA Ops": ops}}}
    with pytest.raises(ValueError, match="kernel calls"):
        T.step_programs(T.reduce(planted, red["lo"], red["hi"]), 40)
