"""The tensor-parallel cell ``starcoder2-7b.code-tp4``, rehearsed on the CPU.

The cell serves the whole StarCoder2-7B over a 1x4 mesh, one of its 4 KV
heads on each chip.  Its configuration's ``rehearsal`` keeps that geometry
at a CPU size (4 KV heads of 128, so 4 virtual devices hold one each, in
the pages' ``(hkv, page, dh)`` order), and this test runs the cell's whole
path on them in a child process (the device count must be set before JAX
starts): weights made sharded, the engine on the mesh, the code mix, and
the check against the plain reference.  The mix is offered at a rate a
loaded CPU keeps up with, so that every request due in the window gets its
first token.  By hand:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_tp4_cell.py

The ``collective_share`` reader is checked here too, on a small trace
written out by hand in the shape the chip's trace has.
"""
import gzip
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE)]

import trace as T  # noqa: E402

CELL = "starcoder2-7b.code-tp4"
RATE = 0.5  # req/s offered in the rehearsal (the mix's own is 4.0)

CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import run
real = run.load_cell

def slower(name, rehearse=False):
    bench, cell, cfg_entry, mix = real(name, rehearse)
    return bench, cell, cfg_entry, {**mix, "rate_per_s": float(sys.argv[2])}

run.load_cell = slower
real_build = run.build
seen = {}

def build(*a, **k):
    eng, sampler = real_build(*a, **k)
    pool = eng.kv.data["seg0"]["attn"]["k_pages"]
    seen["gauges"] = eng.metrics()["gauges"]
    seen["pool"] = list(pool.shape)
    seen["shard"] = list(pool.addressable_shards[0].data.shape)
    return eng, sampler

run.build = build
run.main(["--workload", sys.argv[3], "--seed", "2147490001", "--seconds", "4",
          "--trace", "0", "--rehearse"])
print(json.dumps(seen))
"""


def test_tp4_cell_rehearsal_is_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(HERE), str(RATE), CELL], env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result, seen = json.loads(lines[-2]), json.loads(lines[-1])
    assert result["correct"] is True, result
    assert result["compared"]["requests_without_first_token"]["value"] == 0
    assert result["device"]["count"] == 4
    # (L, pages, hkv, page, dh): each device holds one KV head's pages
    L, pages, hkv, page, dh = seen["pool"]
    assert (hkv, dh) == (4, 128) and seen["shard"] == [L, pages, 1, page, dh]
    g = seen["gauges"]
    assert g["mesh_devices"] == 4
    assert g["kv_pool_bytes_per_device"] == 4 * L * pages * page * dh  # K+V, bf16


def _reader():
    spec = importlib.util.spec_from_file_location(
        "collective_share", HERE / "metrics" / "collective_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(red):
    from types import SimpleNamespace

    return SimpleNamespace(red=red, trace=T)


def test_collective_share_reads_the_decode_programs_collectives():
    """Two devices, one decode-step run each of 1000 ns: the collectives
    inside it (a synchronous all-reduce, the two halves of an asynchronous
    all-gather) count, an op that only takes a collective's result and a
    collective of another program do not."""
    ops = [
        ["%all-reduce.17 = f32[16]{0:T(128)S(1)} all-reduce(f32[16]{0} "
         "%fusion.3), channel_id=9, replica_groups=[1,4]<=[4]", 100, 100],
        ["%broadcast_multiply_fusion.5 = f32[16]{0:T(128)S(1)} "
         "fusion(%all-reduce.17), kind=kLoop", 200, 50],
        ["%all-gather-start.2 = (bf16[16,1152]{1,0}, bf16[16,4608]{1,0}) "
         "all-gather-start(bf16[16,1152]{1,0} %x)", 300, 20],
        ["%all-gather-done.2 = bf16[16,4608]{1,0} all-gather-done("
         "(bf16[16,1152]{1,0}, bf16[16,4608]{1,0}) %all-gather-start.2)",
         500, 80],
        ["%all-reduce.3 = f32[] all-reduce(f32[] %y)", 2100, 400],
    ]
    mods = [["jit__paged_step(123)", 50, 1000],
            ["jit_prefill_chunk(456)", 2000, 1000]]
    ex = {"devices": {f"/device:TPU:{i}": {T.OPS_LINE: ops, T.MODULES_LINE: mods}
                      for i in range(2)}, "host": [], "lines_seen": {}}
    red = T.reduce(ex, 0, 4000)
    assert abs(_reader().read(_ctx(red)) - 20.0) < 1e-9  # 200 of 1000 ns


def test_collective_share_finds_nothing_on_one_chip():
    """The recorded one-chip trace (``trace_named.json.gz``) has no
    collective: the reader reads None, and a cell on one chip cannot list
    it."""
    ex = json.loads(gzip.open(HERE / "testdata" / "trace_named.json.gz").read())
    _, lo, dur = ex["host"][0]
    assert _reader().read(_ctx(T.reduce(ex, lo, lo + dur))) is None
