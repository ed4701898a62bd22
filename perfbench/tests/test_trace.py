"""The trace-to-metric reduction, on a small trace recorded on the chip.

``perfbench/testdata/trace_named.json.gz`` is a slice of a TPU v5 lite
trace of ``minicpm-2b.chat`` as :func:`trace.extract` writes it, with the
engine's named programs and kernels: two runs of the prefill-chunk program
(``jit_prefill_chunk``), two of the decode step (``jit__paged_step``, 40
``paged_attention_decode`` calls each), the small programs of host
sampling between them, and the host's ``serve.*`` and recorder spans.
Run by hand from the repo root:

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
KERNEL = "paged_attention_decode"


@pytest.fixture(scope="module")
def recorded():
    ex = json.loads(gzip.open(HERE / "testdata" / "trace_named.json.gz").read())
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
    return SimpleNamespace(red=red, trace=T, spec=spec, family=MB.family(spec),
                           peaks=peaks, **kw)


def runs(ex, prefix):
    return [m for m in ex["devices"][DEV]["XLA Modules"] if m[0].startswith(prefix)]


def kernel_ops(ex):
    """The paged-decode kernel's ops, by hand."""
    return [o for o in ex["devices"][DEV]["XLA Ops"]
            if "tpu_custom_call" in o[0] and o[0].lstrip("%").startswith(KERNEL + ".")]


def plant(ex, op, at):
    """The extract with one more op, starting at ``at``."""
    ops = ex["devices"][DEV]["XLA Ops"] + [[op, at, 10]]
    return {**ex, "devices": {DEV: {**ex["devices"][DEV], "XLA Ops": ops}}}


def test_names_found(recorded):
    ex, red = recorded
    assert T.OPS_LINE in ex["lines_seen"][DEV] and T.MODULES_LINE in ex["lines_seen"][DEV]
    assert len(runs(ex, "jit__paged_step(")) == 2
    assert len(runs(ex, "jit_prefill_chunk(")) == 2
    assert {T.op_name(o[0]) for o in kernel_ops(ex)} == {KERNEL}
    assert {h[0] for h in ex["host"]} >= {"bench.window", "serve.step", "serve.sample"}


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
    dec, pre = runs(ex, "jit__paged_step("), runs(ex, "jit_prefill_chunk(")
    want_dec = sum(m[2] for m in dec) / len(dec) / 1e6
    want_pre = sum(m[2] for m in pre) / len(pre) / 1e6
    assert reader("decode_step_ms").read(ctx(red)) == pytest.approx(want_dec)
    assert reader("prefill_chunk_ms").read(ctx(red)) == pytest.approx(want_pre)
    # the kernel runs once per layer in each decode step, never in a chunk
    kernel = kernel_ops(ex)
    assert len(kernel) == 2 * 40
    assert all(any(m[1] <= o[1] < m[1] + m[2] for m in dec) for o in kernel)


def test_roofline_and_mfu_from_counts(recorded):
    ex, red = recorded
    t_kernel = sum(o[2] for o in kernel_ops(ex)) / 1e9
    decode_ctx = [[300] * 12, [301] * 12]  # 12 slots of 300 tokens, twice
    c = ctx(red, decode_ctx=decode_ctx, chunks=[(0, 128, False), (128, 100, True)])
    m = c.family.dims(c.spec)
    kv = 2 * 40 * 36 * 64 * 2 * (300 * 12 + 301 * 12)  # K+V bytes, all layers
    qo = 2 * 40 * 36 * 64 * 2 * 24
    share = reader("paged_decode_roofline").read(c)
    assert share == pytest.approx(100 * (kv + qo) / 819e9 / t_kernel)
    assert 0 < share <= 100
    p = c.family.param_count(c.spec)
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
    # every gap is named by a span, the engine's where one is innermost
    assert all(name != "idle" for name, _ in gaps)
    assert any(name.startswith("serve.") for name, _ in gaps)


def test_step_programs_are_checked(recorded):
    ex, red = recorded
    assert T.step_programs(red, KERNEL, 40) == {
        "decode": 2, "prefill": 2, "other_kernels": {}, "other_kernel_s": {}}
    with pytest.raises(ValueError, match="calls of the decode kernel"):
        T.step_programs(red, KERNEL, 32)  # a decode run is not one call per layer
    # the family's kernel planted in a prefill-chunk run: the readers would
    # count it as decode work, so the check refuses the trace
    pre = runs(ex, "jit_prefill_chunk(")[0]
    ours = f'%{KERNEL}.99 = bf16[12,36,64] custom-call(), custom_call_target="tpu_custom_call"'
    with pytest.raises(ValueError, match="calls of the decode kernel"):
        T.step_programs(T.reduce(plant(ex, ours, pre[1] + 1000), red["lo"], red["hi"]),
                        KERNEL, 40)


def test_foreign_kernel_is_reported(recorded):
    """Another family's or a later change's named kernel inside a step
    program is reported in the result's breakdown, not refused, and no
    reader counts it."""
    ex, red = recorded
    dec = runs(ex, "jit__paged_step(")[0]
    pre = runs(ex, "jit_prefill_chunk(")[0]
    gemm = '%moe_gmm.7 = bf16[8,128] custom-call(), custom_call_target="tpu_custom_call"'
    mla = ('%mla_paged_attention_decode.3 = bf16[12,128,512] custom-call(), '
           'custom_call_target="tpu_custom_call"')
    planted = plant(plant(plant(ex, gemm, dec[1] + 1000), mla, dec[1] + 2000),
                    gemm, pre[1] + 1000)
    red2 = T.reduce(planted, red["lo"], red["hi"])
    got = T.step_programs(red2, KERNEL, 40)
    assert got == {
        "decode": 2, "prefill": 2,
        "other_kernels": {"moe_gmm": 2, "mla_paged_attention_decode": 1},
        "other_kernel_s": {"moe_gmm": 20e-9, "mla_paged_attention_decode": 10e-9}}
    assert T.other_kernels(got) == [["moe_gmm", 20e-9],
                                    ["mla_paged_attention_decode", 10e-9]]
    assert T.other_kernels(T.step_programs(red, KERNEL, 40)) == []
    c, c2 = ctx(red, decode_ctx=[[300] * 12], chunks=[]), ctx(red2, decode_ctx=[[300] * 12], chunks=[])
    for name in ("paged_decode_roofline", "decode_step_ms", "prefill_chunk_ms"):
        assert reader(name).read(c2) == reader(name).read(c)
