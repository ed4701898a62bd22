"""Observability tests: metrics registry units, lifecycle-span correctness
under preemption/resume and slot re-fill, derived-stats consistency, and
the Chrome-trace export/validation contract.

The load-bearing guarantees:

* spans nest and close exactly — a drained engine leaves no open span, a
  mid-prefill preemption closes the victim's chunk/prefill spans (marked
  ``preempted``) and the resume opens fresh ones (no orphans);
* observability is free of observable effect — greedy outputs and the
  deterministic metrics (counters, step-unit histograms) are bit-identical
  between ``obs=True`` and ``obs=False`` engines;
* the profiler spans are always entered and nest inside ``serve.step``,
  ``host_syncs_total`` counts every device array brought to the host, and
  a first token is stamped only once it is on the host.
"""
import dataclasses
import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_serve_mesh
from repro.models import model as M
from repro.serve import (
    Engine,
    EngineConfig,
    MetricsRegistry,
    RequestTimeline,
    build_serve_report,
    validate_chrome_trace,
)
from repro.serve.obs import STEP_SPANS, Histogram, main as obs_main
from repro.serve.scheduler import Request, RequestStats


WALL_HISTOGRAMS = ("step_host_ms", "step_sync_wait_ms")


def _paged_cfg(**over):
    cfg = C.get_config("minicpm-2b", smoke=True, dtype=jnp.float32)
    return dataclasses.replace(cfg, **over)


@pytest.fixture(scope="module")
def cfg4():
    return _paged_cfg(block=4)


@pytest.fixture(scope="module")
def params4(cfg4):
    return M.init_params(cfg4, jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# Metrics registry units
# --------------------------------------------------------------------------

def test_registry_get_or_create_and_snapshot():
    r = MetricsRegistry()
    c = r.counter("reqs", "requests")
    c.inc()
    c.inc(4)
    assert r.counter("reqs") is c and c.value == 5
    g = r.gauge("depth")
    g.set(3)
    g.set(1)
    assert r.gauge("depth") is g and g.value == 1
    h = r.histogram("occ", edges=(1, 2, 4))
    for v in (0, 1, 2, 3, 9):
        h.observe(v)
    assert r.histogram("occ") is h
    # buckets are inclusive upper bounds + overflow: [<=1, <=2, <=4, >4]
    assert h.counts == [2, 1, 1, 1] and h.count == 5 and h.sum == 15
    snap = r.snapshot()
    assert snap["counters"] == {"reqs": 5}
    assert snap["gauges"] == {"depth": 1}
    assert snap["histograms"]["occ"]["edges"] == [1, 2, 4]
    json.dumps(snap)  # snapshot must be JSON-clean as-is


def test_histogram_unsorted_edges_sorted():
    h = Histogram("h", edges=(8, 1, 4))
    assert h.edges == (1, 4, 8)
    h.observe(5)
    assert h.counts == [0, 0, 1, 0]


# --------------------------------------------------------------------------
# Timeline / span units
# --------------------------------------------------------------------------

def test_timeline_span_discipline():
    tl = RequestTimeline()
    tl.begin("queued", 0, 0.0)
    with pytest.raises(AssertionError):
        tl.begin("queued", 1, 1.0)  # double-open is a bug, loudly
    s = tl.end("queued", 3, 3.0)
    assert s.steps == 3 and s.wall_s == 3.0 and not s.open
    tl.begin("prefill", 3, 3.0)
    tl.begin("prefill-chunk", 3, 3.0)
    closed = tl.close_all(5, 5.0, preempted=True)
    assert {c.name for c in closed} == {"prefill", "prefill-chunk"}
    assert all(c.attrs["preempted"] for c in closed)
    assert tl.open_spans == []
    with pytest.raises(KeyError):
        tl.end("prefill", 6, 6.0)  # closing a closed span is a bug too
    assert tl.mark("first_token", 7, 7.0)
    assert not tl.mark("first_token", 9, 9.0)  # milestones are first-only
    assert tl.marks["first_token"] == (7, 7.0)


def test_derived_stats_defaults_match_legacy():
    """A fresh Request's derived stats expose the pre-span defaults the
    drivers/benchmarks relied on (arrival 0, the rest -1 / 0.0)."""
    req = Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=2)
    s = req.stats
    assert isinstance(s, RequestStats)
    assert s.arrival_step == 0 and s.admitted_step == -1
    assert s.first_token_step == -1 and s.finish_step == -1
    assert s.t_arrival == 0.0 and s.t_finish == 0.0
    assert s.n_preemptions == 0 and s.cached_prompt_tokens == 0
    assert s.decode_tok_s(1) == float("inf")


def test_stats_single_source_for_step_and_wall():
    """The bugfix: step- and wall-TTFT must read the SAME milestones."""
    tl = RequestTimeline()
    tl.mark("arrival", 2, 10.0)
    tl.mark("admitted", 4, 10.5)
    tl.mark("first_token", 7, 11.0)
    tl.mark("finish", 9, 12.0)
    s = RequestStats(tl)
    assert s.queue_steps == 2 and s.ttft_steps == 5
    assert s.ttft_s == pytest.approx(1.0)
    assert s.decode_tok_s(3) == pytest.approx(2.0)


# --------------------------------------------------------------------------
# Engine integration: spans under preemption/resume + slot re-fill
# --------------------------------------------------------------------------

def _drive(eng):
    for _ in range(500):
        if not eng.sched.has_work():
            break
        eng.step()
    eng._flush_pending()
    assert not eng.sched.has_work()


def test_spans_close_exactly_under_mid_prefill_preemption(cfg4, params4):
    """The test_serve mid-prefill preemption workload, checked for span
    discipline: the victim's chunk/prefill spans close at preemption
    (no orphans), the resume opens fresh ones, and a drained engine leaves
    every span on every request closed."""
    rng = np.random.default_rng(11)
    short = rng.integers(0, cfg4.vocab_size, size=(8,)).astype(np.int32)
    long = rng.integers(0, cfg4.vocab_size, size=(16,)).astype(np.int32)
    eng = Engine(cfg4, params4, EngineConfig(
        max_seqs=2, max_len=24, page_size=4, num_pages=9,
        prefill_tokens_per_step=4,
    ))
    a = eng.submit(short, 8, rid=0)
    b = eng.submit(long, 8, rid=1)
    _drive(eng)
    assert b.stats.n_preemptions >= 1, "workload must exercise preemption"

    for req in (a, b):
        tl = req.timeline
        assert tl.open_spans == [], f"rid {req.rid} left spans open"
        assert all(not s.open and s.end_step >= s.begin_step for s in tl.spans)
        # milestones complete and ordered
        s = req.stats
        assert (s.arrival_step <= s.admitted_step <= s.first_token_step
                <= s.finish_step)
        # chunk spans nest inside a prefill span's interval
        prefills = [s for s in tl.spans if s.name == "prefill"]
        for ch in (s for s in tl.spans if s.name == "prefill-chunk"):
            assert any(p.begin_step <= ch.begin_step
                       and ch.end_step <= p.end_step for p in prefills), (
                f"rid {req.rid}: orphan prefill-chunk span {ch}"
            )

    # the victim's structure: each preemption closes one span generation
    # with preempted=True and re-opens "queued"; every re-admission opens a
    # fresh prefill; a preemption that lands after the first token closes
    # the decode span and the next prefill completion re-opens it
    tlb = b.timeline
    n_pre = tlb.n_preemptions
    queued = [s for s in tlb.spans if s.name == "queued"]
    prefills = [s for s in tlb.spans if s.name == "prefill"]
    decodes = [s for s in tlb.spans if s.name == "decode"]
    assert len(queued) == n_pre + 1
    assert len(prefills) == n_pre + 1
    assert sum(1 for s in tlb.spans if s.attrs.get("preempted")) >= n_pre
    assert len(decodes) == 1 + sum(
        1 for s in decodes if s.attrs.get("preempted")
    )
    assert not decodes[-1].attrs.get("preempted")  # the finishing one
    assert [n for n, *_ in tlb.instants] == ["preempt"] * n_pre
    # chunk spans from the aborted prefill closed AT the preemption, and
    # the resumed prefill re-ran its chunks from scratch
    total_chunk_tokens = sum(
        s.attrs["tokens"] for s in tlb.spans
        if s.name == "prefill-chunk" and not s.attrs.get("preempted")
    )
    assert total_chunk_tokens >= len(long)
    # registry counters saw the same story
    counters = eng.metrics()["counters"]
    assert counters["preemptions_total"] == n_pre
    assert counters["admissions_total"] == 2 + n_pre
    assert counters["finished_total"] == 2


def test_slot_refill_keeps_timelines_separate(cfg4, params4):
    """More requests than slots: re-filled slots must not bleed spans
    between the old and new occupant."""
    rng = np.random.default_rng(3)
    eng = Engine(cfg4, params4, EngineConfig(max_seqs=2, max_len=40))
    reqs = [
        eng.submit(rng.integers(0, cfg4.vocab_size, size=(8,)).astype(np.int32),
                   4 + i, rid=i, arrival_step=i)
        for i in range(5)
    ]
    _drive(eng)
    for req in reqs:
        tl = req.timeline
        assert tl.open_spans == []
        assert len([s for s in tl.spans if s.name == "decode"]) == 1
        assert req.stats.finish_step >= req.stats.first_token_step
        assert eng.obs.timelines[req.rid] is tl
    counters = eng.metrics()["counters"]
    assert counters["finished_total"] == 5
    assert counters["generated_tokens_total"] == sum(
        len(r.out_tokens) for r in reqs
    )
    h = eng.metrics()["histograms"]["generated_tokens"]
    assert h["count"] == 5


# --------------------------------------------------------------------------
# obs on/off: outputs and deterministic metrics bit-identical
# --------------------------------------------------------------------------

def test_obs_on_off_outputs_and_metrics_identical(cfg4, params4):
    """Deep observability must be a pure observer: greedy outputs and all
    deterministic metrics (counters; step-unit histograms) bit-identical
    to the gated-off engine on the same preemption-heavy workload."""
    def run(obs):
        rng = np.random.default_rng(11)
        short = rng.integers(0, cfg4.vocab_size, size=(8,)).astype(np.int32)
        long = rng.integers(0, cfg4.vocab_size, size=(16,)).astype(np.int32)
        eng = Engine(cfg4, params4, EngineConfig(
            max_seqs=2, max_len=24, page_size=4, num_pages=9,
            prefill_tokens_per_step=4, obs=obs,
        ))
        eng.submit(short, 8, rid=0)
        eng.submit(long, 8, rid=1)
        done = eng.run()
        outs = {r.rid: list(r.out_tokens) for r in done}
        return outs, eng.metrics()

    outs_off, m_off = run(False)
    outs_on, m_on = run(True)
    assert outs_on == outs_off
    assert m_on["counters"] == m_off["counters"]
    # the step-time histograms read the wall clock: one observation per
    # step either way, values free
    for name, h in m_off["histograms"].items():
        if name in WALL_HISTOGRAMS:
            assert m_on["histograms"][name]["count"] == h["count"], name
        else:
            assert m_on["histograms"][name] == h, name
    assert m_on["histograms"].keys() == m_off["histograms"].keys()
    # gauges too — except the two audit-backed ones only deep collection
    # fills (their staying 0 when gated off is exactly the gating contract)
    deep_only = {"pages_index_pinned", "pages_slot_held"}
    for name, v in m_off["gauges"].items():
        if name not in deep_only:
            assert m_on["gauges"][name] == v, name
    assert m_off["gauges"]["pages_index_pinned"] == 0
    # deep gauges carry the drained-engine audit: every non-free page is
    # prefix-index-pinned once nothing runs
    g = m_on["gauges"]
    assert g["pages_free"] + g["pages_index_pinned"] == g["pages_total"]


# --------------------------------------------------------------------------
# Chrome-trace export + validation
# --------------------------------------------------------------------------

def test_trace_export_valid_and_loadable(cfg4, params4, tmp_path):
    rng = np.random.default_rng(0)
    eng = Engine(cfg4, params4, EngineConfig(max_seqs=2, max_len=32))
    for i in range(3):
        eng.submit(rng.integers(0, cfg4.vocab_size, size=(8,)).astype(np.int32),
                   4, rid=i, arrival_step=i)
    eng.run()
    path = tmp_path / "trace.json"
    trace = eng.export_trace(str(path))
    assert validate_chrome_trace(trace) == []
    on_disk = json.loads(path.read_text())
    assert validate_chrome_trace(on_disk) == []
    # one engine-step X event per engine step, one track per request
    engine_x = [e for e in on_disk["traceEvents"]
                if e.get("ph") == "X" and e.get("cat") == "engine"]
    assert len(engine_x) == eng.step_count
    req_tids = {e["tid"] for e in on_disk["traceEvents"]
                if e.get("ph") == "X" and e.get("cat") == "request"}
    assert len(req_tids) == 3
    # the CLI validator agrees
    assert obs_main([str(path)]) == 0


def test_trace_validator_rejects_malformed(tmp_path, capsys):
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": []}) != []
    bad_x = {"traceEvents": [{"ph": "X", "name": "n", "ts": 0}]}
    assert any("missing" in p for p in validate_chrome_trace(bad_x))
    no_tracks = {"traceEvents": [{"ph": "M", "name": "process_name"}]}
    problems = validate_chrome_trace(no_tracks)
    assert any("engine-step track" in p for p in problems)
    assert any("request span track" in p for p in problems)
    # negative timestamps are nonsense in this exporter
    neg = {"traceEvents": [
        {"ph": "X", "name": "s", "cat": "engine", "ts": -1, "dur": 1,
         "pid": 1, "tid": 0},
        {"ph": "X", "name": "s", "cat": "request", "ts": 0, "dur": 1,
         "pid": 1, "tid": 1},
    ]}
    assert any("bad ts" in p for p in validate_chrome_trace(neg))
    # CLI: malformed file -> nonzero, problems printed
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"traceEvents": []}))
    assert obs_main([str(p)]) == 1
    assert "non-empty" in capsys.readouterr().out
    assert obs_main([str(tmp_path / "missing.json")]) == 1


def test_open_spans_export_flagged(cfg4, params4):
    """A live (undrained) engine's trace is still valid: open spans export
    with an explicit marker and a to-now duration."""
    rng = np.random.default_rng(0)
    eng = Engine(cfg4, params4, EngineConfig(max_seqs=1, max_len=32))
    eng.submit(rng.integers(0, cfg4.vocab_size, size=(8,)).astype(np.int32),
               8, rid=0)
    for _ in range(3):
        eng.step()
    trace = eng.obs.chrome_trace()
    assert validate_chrome_trace(trace) == []
    open_evs = [e for e in trace["traceEvents"]
                if e.get("args", {}).get("open")]
    assert open_evs and all(e["dur"] >= 0 for e in open_evs)
    _drive(eng)


# --------------------------------------------------------------------------
# JSON report
# --------------------------------------------------------------------------

def test_serve_report_json_clean_and_consistent(cfg4, params4):
    rng = np.random.default_rng(5)
    eng = Engine(cfg4, params4, EngineConfig(max_seqs=2, max_len=40))
    reqs = [
        eng.submit(rng.integers(0, cfg4.vocab_size, size=(8,)).astype(np.int32),
                   6, rid=i, arrival_step=2 * i)
        for i in range(4)
    ]
    done = eng.run()
    report = build_serve_report(eng, done, wall_s=1.5,
                                useful_tokens=sum(len(r.out_tokens)
                                                  for r in done))
    # standard-JSON round trip: no inf/nan anywhere
    parsed = json.loads(json.dumps(report, allow_nan=False))
    assert parsed["engine"]["steps"] == eng.step_count
    assert parsed["pool"]["pages_free"] == eng.kv.num_free_pages
    by_rid = {r["rid"]: r for r in parsed["requests"]}
    for req in reqs:
        row, s = by_rid[req.rid], req.stats
        assert row["ttft_steps"] == s.ttft_steps
        assert row["queue_steps"] == s.queue_steps
        assert row["ttft_ms"] == pytest.approx(s.ttft_s * 1e3)
        assert row["n_tokens"] == len(req.out_tokens)
    # a single-token request has inf decode_tok_s -> None in the report
    eng2 = Engine(cfg4, params4, EngineConfig(max_seqs=1, max_len=16))
    eng2.submit(rng.integers(0, cfg4.vocab_size, size=(4,)).astype(np.int32),
                1, rid=0)
    done2 = eng2.run()
    rep2 = build_serve_report(eng2, done2)
    assert rep2["requests"][0]["decode_tok_s"] is None
    json.dumps(rep2, allow_nan=False)


# --------------------------------------------------------------------------
# Profiler spans, the sync counter and the step's host time
# --------------------------------------------------------------------------

# four requests over two slots, one of them a single-token answer; each
# mode's tokens as the engine served them before the step spans existed
MODES = {
    "greedy": {},
    "greedy_eos": {"eos_id": 325},
    "sampled": {"temperature": 0.8},
    "sampled_eos": {"temperature": 0.8, "eos_id": 399},
}
SERVED = {
    "greedy": [[440], [416, 19, 111, 2, 153, 113],
               [426, 466, 325, 302, 304, 325, 27],
               [463, 55, 283, 162, 192, 237, 495, 470]],
    "greedy_eos": [[440], [416, 19, 111, 2, 153, 113], [426, 466, 325],
                   [463, 55, 283, 162, 192, 237, 495, 470]],
    "sampled": [[229], [411, 423, 399, 334, 81, 123],
                [311, 498, 74, 421, 250, 204, 97],
                [263, 76, 243, 5, 244, 300, 273, 492]],
    "sampled_eos": [[229], [411, 423, 399], [311, 498, 74, 421, 250, 204, 97],
                    [263, 76, 243, 5, 244, 300, 273, 492]],
}


@pytest.fixture(scope="module", params=list(MODES))
def served(request, cfg4, params4):
    mode = request.param
    rng = np.random.default_rng(7)
    eng = Engine(cfg4, params4, EngineConfig(
        max_seqs=2, max_len=40, seed=3, **MODES[mode]))
    for i, max_new in enumerate((1, 6, 7, 8)):
        eng.submit(rng.integers(0, cfg4.vocab_size, size=(6 + 3 * i,))
                   .astype(np.int32), max_new, rid=i, arrival_step=i)
    done = eng.run()
    return mode, eng, done


def test_served_tokens_unchanged(served):
    mode, _, done = served
    assert [list(r.out_tokens) for r in done] == SERVED[mode]


def test_host_syncs_total_exact(served):
    """One sync per first token, plus one per decode step: greedy or
    sampled, the step's tokens stay on the device and come over in one
    array."""
    _, eng, done = served
    c = eng.metrics()["counters"]
    assert c["preemptions_total"] == 0  # every admission samples one first token
    assert c["host_syncs_total"] == c["decode_steps_total"] + len(done)


def test_sampled_rows_total_counts_device_draws(served):
    """The device sampler draws every decoding slot of every step and every
    first token when the engine samples, and nothing under greedy."""
    mode, eng, done = served
    m = eng.metrics()
    rows = m["counters"]["sampled_rows_total"]
    if MODES[mode].get("temperature", 0) > 0:
        assert rows == m["histograms"]["decode_batch_occupancy"]["sum"] + len(done)
    else:
        assert rows == 0


def test_step_time_splits_into_host_and_sync_wait(served):
    _, eng, _ = served
    h = eng.metrics()["histograms"]
    host, wait = h["step_host_ms"], h["step_sync_wait_ms"]
    assert host["count"] == wait["count"] == eng.step_count
    wall_ms = sum(s.wall_s for s in eng.obs.step_spans) * 1e3
    assert host["sum"] + wait["sum"] == pytest.approx(wall_ms, rel=1e-9)
    assert host["sum"] > 0 and wait["sum"] > 0


@pytest.mark.parametrize("chunked", [True, False], ids=["chunked", "one_shot"])
def test_first_token_stamped_after_its_sync(cfg4, params4, chunked):
    """The first_token milestone (and so ttft_s) is taken once the sampled
    token is on the host, with the span order a max_new == 1 finish needs."""
    eng = Engine(cfg4, params4, EngineConfig(
        max_seqs=2, max_len=40, chunked_prefill=chunked))
    on_host = {}
    sample = eng._sample

    def timed(row, req):
        tok = sample(row, req)
        on_host.setdefault(req.rid, time.perf_counter())
        return tok

    eng._sample = timed
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, cfg4.vocab_size, size=(9,))
                       .astype(np.int32), n, rid=i, arrival_step=i)
            for i, n in enumerate((1, 4, 3))]
    eng.run()
    for r in reqs:
        assert r.stats.t_first_token >= on_host[r.rid]
        assert r.timeline.open_spans == []


@pytest.mark.parametrize("attr,module", [
    ("_decode", "jit__paged_step"), ("_chunk_fn", "jit_prefill_chunk"),
], ids=["decode", "chunk"])
@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh"])
def test_step_programs_lower_under_stable_names(cfg4, params4, attr, module,
                                                mesh):
    """A device trace finds the two step programs by these module names."""
    eng = Engine(cfg4, params4, EngineConfig(max_seqs=2, max_len=16),
                 mesh=make_serve_mesh("1x1") if mesh else None)
    jitted, shapes = getattr(eng, attr), []

    def spy(*args):
        shapes.append(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        return jitted(*args)

    setattr(eng, attr, spy)
    eng.submit(np.arange(6, dtype=np.int32), 2, rid=0)
    eng.run()
    assert jitted.lower(*shapes[0]).as_text().split()[:2] == ["module", f"@{module}"]


def test_profiler_capture_nests_spans_in_steps(cfg4, params4, tmp_path):
    """A CPU profiler capture, deep observability off: every engine span is
    on the host trace inside a numbered ``serve.step``, and every
    ``serve.sync`` inside the sampling or flush span that waits on it."""
    eng = Engine(cfg4, params4, EngineConfig(max_seqs=2, max_len=40,
                                             eos_id=1))
    rng = np.random.default_rng(2)
    for i in range(2):
        eng.submit(rng.integers(0, cfg4.vocab_size, size=(6,)).astype(np.int32),
                   3, rid=i)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("serve.")
    ]
    assert {name for name, *_ in events} == set(STEP_SPANS)
    steps = [(s, e) for name, s, e, _ in events if name == "serve.step"]
    assert sorted(st["step_num"] for name, *_, st in events
                  if name == "serve.step") == list(range(eng.step_count))

    def inside(s, e, outer):
        return any(a <= s and e <= b for a, b in outer)

    for name, s, e, _ in events:
        if name != "serve.step":
            assert inside(s, e, steps), name
    waits = [(s, e) for name, s, e, _ in events
             if name in ("serve.sample", "serve.flush")]
    syncs = [(s, e) for name, s, e, _ in events if name == "serve.sync"]
    assert syncs and all(inside(s, e, waits) for s, e in syncs)
    assert len(syncs) == eng.metrics()["counters"]["host_syncs_total"]
