"""Rehearsal of the harness's four-chip path on 4 virtual CPU devices.

A cell with ``"chips": 4`` runs the engine over the (data, model) mesh its
configuration's ``serving.mesh`` names, with weights initialised already
sharded inside the one jitted call.  No cell asks for four chips yet; this
runs a one-chip cell's rehearsal as if it did, end to end, in a child
process (the device count must be set before JAX starts).  By hand:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_four_chips.py
"""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]

CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import run
real = run.load_cell

def four(name, rehearse=False):
    bench, cell, cfg_entry, mix = real(name, rehearse)
    return bench, {**cell, "chips": 4}, cfg_entry, mix

import model
real_load = model.load

def with_mesh(path, rehearse=False):
    spec = real_load(path, rehearse)
    spec["serving"] = {**spec["serving"], "mesh": "1x4"}
    return spec

run.load_cell, model.load = four, with_mesh
import jax
from jax.sharding import NamedSharding
seen = []
real_params = model.program_params

def params(*a, **k):
    p = real_params(*a, **k)
    seen.append(sorted({str(x.sharding.spec) for x in jax.tree.leaves(p)
                        if isinstance(x.sharding, NamedSharding)}))
    return p

model.program_params = params
run.main(["--workload", "minicpm-2b.chat", "--seed", "5", "--seconds", "3",
          "--trace", "0", "--rehearse"])
print(json.dumps({"param_specs": seen[0]}))
"""


def test_four_chip_cell_runs_sharded():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", CHILD, str(HERE)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result, specs = json.loads(lines[-2]), json.loads(lines[-1])["param_specs"]
    assert result["correct"] is True, result
    assert result["device"]["count"] == 4
    # weights came out of the init already split over the model axis
    assert any("model" in s for s in specs), specs
