"""Fixture tests for the compiled-artifact linter (repro.analysis.jaxcheck).

Per RPJ rule: a seeded-violation spec that must produce a finding, the
clean counterpart, and waiver suppression.  Plus budgets-file round-
tripping and the acceptance gate: the serving engine's own jitted-step
inventory is clean against the checked-in ``jaxcheck.budgets``.
"""
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.jaxcheck import (
    RULE_IDS,
    Budgets,
    Finding,
    format_budgets,
    load_budgets,
)
from repro.analysis.jaxcheck.harness import (
    ProbeSet,
    StepSpec,
    collective_stats,
    compile_step,
    gather_stats,
    measure,
    parse_aliased_params,
)
from repro.analysis.jaxcheck.inventory import InventoryConfig, serving_inventory
from repro.analysis.jaxcheck.rules import RULES, run_rules

REPO = Path(__file__).resolve().parent.parent


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _findings(spec, budgets=None, select=None):
    cs = compile_step(spec)
    budgets = budgets or Budgets()
    out = []
    for rid in select or RULE_IDS:
        out.extend(
            f for f in RULES[rid]([cs], None, budgets)
            if not budgets.waived(f.rule, f.step)
        )
    return out


# --------------------------------------------------------------------------
# RPJ101 — donation-effectiveness
# --------------------------------------------------------------------------

def _dropped_donation_spec():
    # arg 0 is donated but no output can reuse its buffer (shape/dtype
    # mismatch) -> XLA drops the donation, no alias entry.  NB a donated-
    # but-*unused* same-shape buffer still gets aliased; the drop needs the
    # buffer to be unusable.
    return StepSpec(
        name="drop", fn=lambda x, y: jnp.sum(y)[None].astype(jnp.int32),
        args=(_sds((64,)), _sds((64,))), donate_argnums=(0,),
    )


def test_rpj101_seeded_dropped_donation():
    found = _findings(_dropped_donation_spec(), select=["RPJ101"])
    assert [f.rule for f in found] == ["RPJ101"]
    assert "donation became a copy" in found[0].message


def test_rpj101_clean_effective_donation():
    spec = StepSpec(
        name="ok", fn=lambda x, y: x + y,
        args=(_sds((64,)), _sds((64,))), donate_argnums=(0,),
    )
    assert _findings(spec, select=["RPJ101"]) == []


def test_rpj101_waiver():
    budgets = Budgets(waivers={"drop": {"RPJ101"}})
    assert _findings(_dropped_donation_spec(), budgets,
                     select=["RPJ101"]) == []


# --------------------------------------------------------------------------
# RPJ102 — materialized gather
# --------------------------------------------------------------------------

def _gather_spec():
    # gathers 512 rows of 256 floats = 512 KiB output
    return StepSpec(
        name="big_gather",
        fn=lambda table, idx: jnp.take(table, idx, axis=0),
        args=(_sds((1024, 256)), _sds((512,), jnp.int32)),
    )


def test_rpj102_seeded_over_budget():
    budgets = Budgets(steps={"big_gather": {"max_gather_bytes": 1024}})
    found = _findings(_gather_spec(), budgets, select=["RPJ102"])
    assert [f.rule for f in found] == ["RPJ102"]
    assert "exceeds budget" in found[0].message


def test_rpj102_seeded_unbudgeted():
    found = _findings(_gather_spec(), select=["RPJ102"])
    assert [f.rule for f in found] == ["RPJ102"]
    assert "no max_gather_bytes budget" in found[0].message


def test_rpj102_clean_within_budget():
    budgets = Budgets(steps={"big_gather": {"max_gather_bytes": 512 * 1024}})
    assert _findings(_gather_spec(), budgets, select=["RPJ102"]) == []


def test_gather_stats_sees_nested_pjit_gather():
    # jnp.take hides its gather inside a nested pjit eqn
    cs = compile_step(_gather_spec())
    stats = gather_stats(cs.jaxpr)
    assert stats and max(s["output_bytes"] for s in stats) == 512 * 256 * 4


# --------------------------------------------------------------------------
# RPJ103 — dtype-promotion drift
# --------------------------------------------------------------------------

def test_rpj103_seeded_f64_upcast():
    with jax.enable_x64(True):
        spec = StepSpec(
            name="upcast", fn=lambda x: x.astype(jnp.float64) * 2.0,
            args=(_sds((16,), jnp.float32),),
        )
        found = _findings(spec, select=["RPJ103"])
    assert [f.rule for f in found] == ["RPJ103"]
    assert "float64" in found[0].message


def test_rpj103_clean_f32_converts():
    spec = StepSpec(
        name="ok", fn=lambda x: x.astype(jnp.float32) + 1.0,
        args=(_sds((16,), jnp.int32),),
    )
    assert _findings(spec, select=["RPJ103"]) == []


# --------------------------------------------------------------------------
# RPJ104 — retrace closure
# --------------------------------------------------------------------------

def test_rpj104_seeded_plan_escapes_closure():
    spec = StepSpec(
        name="escape", fn=lambda x: x * 2.0, args=(_sds((8,)),),
        signature_plan=(8, 3), signature_closure=(1, 2, 4, 8),
    )
    found = _findings(spec, select=["RPJ104"])
    assert [f.rule for f in found] == ["RPJ104"]
    assert "[3]" in found[0].message


def test_rpj104_seeded_probe_signature_leak():
    # the probe feeds two dtypes through one jit -> 2 cache entries, not 1
    spec = StepSpec(
        name="leak", fn=lambda x: x * 2, args=(_sds((8,)),),
        probe=ProbeSet(
            keys=(0, 1),
            make_args=lambda k: (
                jnp.zeros((8,), jnp.float32 if k == 0 else jnp.int32),
            ),
            expected_entries=1,
        ),
    )
    found = _findings(spec, select=["RPJ104"])
    assert [f.rule for f in found] == ["RPJ104"]
    assert "signature leak" in found[0].message


def test_rpj104_clean_probe():
    spec = StepSpec(
        name="ok", fn=lambda x: x * 2, args=(_sds((8,)),),
        signature_plan=(8,), signature_closure=(8,),
        probe=ProbeSet(
            keys=(0, 1),
            make_args=lambda k: (jnp.zeros((8,), jnp.float32),),
            expected_entries=1,
        ),
    )
    assert _findings(spec, select=["RPJ104"]) == []


# --------------------------------------------------------------------------
# RPJ105 — memory-budget regression
# --------------------------------------------------------------------------

def _mem_spec():
    return StepSpec(
        name="mem", fn=lambda x: jnp.dot(x, x.T).sum(),
        args=(_sds((64, 64)),),
    )


def test_rpj105_seeded_over_budget():
    budgets = Budgets(steps={"mem": {
        "temp_size_in_bytes": 0,
        "argument_size_in_bytes": 0,
        "output_size_in_bytes": 0,
    }})
    found = _findings(_mem_spec(), budgets, select=["RPJ105"])
    assert found and all(f.rule == "RPJ105" for f in found)
    assert any("exceeds budget" in f.message for f in found)


def test_rpj105_seeded_unbudgeted():
    found = _findings(_mem_spec(), select=["RPJ105"])
    assert found and all("no budget" in f.message for f in found)


def test_rpj105_clean_within_tolerance():
    cs = compile_step(_mem_spec())
    budgets = Budgets(steps={"mem": dict(cs.memory)})
    assert _findings(_mem_spec(), budgets, select=["RPJ105"]) == []


# --------------------------------------------------------------------------
# harness pieces
# --------------------------------------------------------------------------

def test_parse_aliased_params():
    hlo = textwrap.dedent("""
        HloModule jit_f, input_output_alias={ {0}: (1, {}, may-alias),
        {1}: (3, {}, may-alias) }, entry_computation_layout={...}
    """)
    assert parse_aliased_params(hlo) == {1, 3}
    assert parse_aliased_params("HloModule jit_f, entry={...}") == frozenset()


def test_measure_fields():
    rec = measure(compile_step(_gather_spec()))
    assert rec["max_gather_bytes"] == 512 * 256 * 4
    assert "temp_size_in_bytes" in rec


# --------------------------------------------------------------------------
# budgets file round-trip
# --------------------------------------------------------------------------

def test_budgets_round_trip(tmp_path):
    measured = {"decode_step": {"temp_size_in_bytes": 100,
                                "max_gather_bytes": 42}}
    waivers = {"decode_step": {"RPJ103"}, "global": {"RPJ102"}}
    text = format_budgets(measured, tolerance=0.25, allowed_widest="float32",
                          waivers=waivers)
    p = tmp_path / "jaxcheck.budgets"
    p.write_text(text, encoding="utf-8")
    b = load_budgets(p)
    assert b.steps == measured
    assert b.tolerance == 0.25
    assert b.waived("RPJ103", "decode_step")
    assert b.waived("RPJ102", "anything")  # global waiver
    assert not b.waived("RPJ101", "decode_step")
    assert b.allowed("decode_step", "temp_size_in_bytes", 125)
    assert not b.allowed("decode_step", "temp_size_in_bytes", 126)


def test_budgets_rejects_unknown_rule(tmp_path):
    p = tmp_path / "bad.budgets"
    p.write_text("[s]\nwaive = RPJ999\n", encoding="utf-8")
    with pytest.raises(ValueError, match="unknown rule"):
        load_budgets(p)


# --------------------------------------------------------------------------
# CLI exit codes
# --------------------------------------------------------------------------

def test_cli_exit_codes(tmp_path, capsys):
    import json

    from repro.analysis.jaxcheck.__main__ import main

    assert main(["--list-rules"]) == 0
    capsys.readouterr()

    # clean run against the checked-in budgets, with a JSON report
    report_path = tmp_path / "BENCH_jaxcheck.json"
    rc = main(["--budgets", str(REPO / "jaxcheck.budgets"),
               "--json-out", str(report_path)])
    capsys.readouterr()
    assert rc == 0
    report = json.loads(report_path.read_text(encoding="utf-8"))
    assert report["status"] == "clean" and report["findings"] == []
    assert report["n_steps"] == len(report["steps"]) > 0

    # seeded regression: zeroed budgets must fail with findings
    bad = tmp_path / "bad.budgets"
    bad.write_text(
        "[global]\ntolerance = 0.0\n\n[decode_step]\n"
        "temp_size_in_bytes = 1\nmax_gather_bytes = 1\n",
        encoding="utf-8",
    )
    rc = main(["--budgets", str(bad), "--select", "RPJ102", "RPJ105",
               "--json-out", str(report_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "RPJ102" in out and "RPJ105" in out
    report = json.loads(report_path.read_text(encoding="utf-8"))
    assert report["status"] == "findings"


# --------------------------------------------------------------------------
# acceptance gate: the engine's own inventory is clean
# --------------------------------------------------------------------------

def test_engine_inventory_is_clean():
    """The serving engine's compiled hot-path steps pass every RPJ rule
    against the checked-in budgets (re-baseline intentional changes with
    `python -m repro.analysis.jaxcheck --write-budgets`)."""
    budgets_file = REPO / "jaxcheck.budgets"
    assert budgets_file.exists(), "jaxcheck.budgets must be checked in"
    budgets = load_budgets(budgets_file)
    inv = serving_inventory()
    steps = [compile_step(spec) for spec in inv.specs]
    findings = run_rules(steps, inv, budgets)
    assert not findings, "\n".join(f.format() for f in findings)
    # and the inventory covers the steps the budgets file gates
    names = {cs.name for cs in steps}
    assert set(budgets.steps) <= names | {"global"}


# --------------------------------------------------------------------------
# RPJ106 — collective-traffic budget
# --------------------------------------------------------------------------

_SHARDED_HLO = """\
HloModule jit_step, is_scheduled=true

ENTRY main {
  %p0 = f32[2,48]{1,0} parameter(0)
  %all-gather = f32[2,96]{1,0} all-gather(f32[2,48]{1,0} %p0), dimensions={1}
  %all-reduce-start = f32[2,96]{1,0} all-reduce-start(f32[2,96]{1,0} %all-gather)
  %all-reduce-done = f32[2,96]{1,0} all-reduce-done(%all-reduce-start)
  %reduce-scatter = (f32[2,48]{1,0}, f32[4]{0}) reduce-scatter(%all-reduce-done)
  ROOT %out = f32[2,48]{1,0} get-tuple-element(%reduce-scatter), index=0
}
"""


def test_collective_stats_parses_hlo_once_per_async_pair():
    colls = collective_stats(_SHARDED_HLO)
    # the -done must not double-count its -start; the tuple shape sums
    assert [c["op"] for c in colls] == [
        "all-gather", "all-reduce", "reduce-scatter"
    ]
    assert [c["output_bytes"] for c in colls] == [
        2 * 96 * 4, 2 * 96 * 4, 2 * 48 * 4 + 4 * 4
    ]
    assert collective_stats("ENTRY main { ROOT %x = f32[4] add(...) }") == []


class _FakeArtifact:
    def __init__(self, hlo):
        self._hlo = hlo

    def hlo_text(self):
        return self._hlo


class _FakeCompiledStep:
    def __init__(self, name, hlo):
        self.name = name
        self.artifact = _FakeArtifact(hlo)


def test_rpj106_seeded_unbudgeted_and_over_budget():
    cs = _FakeCompiledStep("sharded_step", _SHARDED_HLO)
    found = RULES["RPJ106"]([cs], None, Budgets())
    assert found and "no collective_bytes budget" in found[0].message
    tight = Budgets(steps={"sharded_step": {"collective_bytes": 8}},
                    tolerance=0.0)
    found = RULES["RPJ106"]([cs], None, tight)
    assert found and "exceeds budget" in found[0].message


def test_rpj106_clean_within_budget_and_no_collectives():
    cs = _FakeCompiledStep("sharded_step", _SHARDED_HLO)
    total = sum(c["output_bytes"] for c in collective_stats(_SHARDED_HLO))
    ok = Budgets(steps={"sharded_step": {"collective_bytes": total}})
    assert RULES["RPJ106"]([cs], None, ok) == []
    # a single-device module (no collectives) passes with no budget at all
    clean = _FakeCompiledStep("local", "ENTRY main { ROOT %x = f32[4] neg() }")
    assert RULES["RPJ106"]([clean], None, Budgets()) == []


# --------------------------------------------------------------------------
# sharded inventory (needs simulated devices; CI `mesh` job runs this)
# --------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
def test_sharded_inventory_donation_and_collectives():
    """Acceptance gate: on a (1, 2) mesh the engine's compiled sharded
    steps keep every pool donation aliased (RPJ101 — donation survives
    sharding), carry real collectives for RPJ106 to budget, and the
    donated pool's alias bytes drop by the TP factor vs single-device."""
    inv = serving_inventory(InventoryConfig(mesh="1x2"))
    steps = [compile_step(spec) for spec in inv.specs]
    findings = run_rules(steps, inv, Budgets(), select=["RPJ101"])
    assert not findings, "\n".join(f.format() for f in findings)
    decode = next(cs for cs in steps if cs.name == "decode_step")
    assert collective_stats(decode.artifact.hlo_text()), (
        "sharded decode step should contain cross-device collectives"
    )
    single = serving_inventory()
    dec_spec = next(s for s in single.specs if s.name == "decode_step")
    alias_single = compile_step(dec_spec).memory["alias_size_in_bytes"]
    assert decode.memory["alias_size_in_bytes"] * 2 == alias_single
    # checked-in mesh budgets keep the sharded inventory clean end to end
    mesh_budgets = REPO / "jaxcheck_mesh.budgets"
    assert mesh_budgets.exists(), "jaxcheck_mesh.budgets must be checked in"
    findings = run_rules(steps, inv, load_budgets(mesh_budgets))
    assert not findings, "\n".join(f.format() for f in findings)
