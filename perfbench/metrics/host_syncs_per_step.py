"""host_syncs_per_step: engine steps (``serve/engine.py``) device->host syncs.

Device arrays the engine brought to the host (``host_syncs_total``, counted
by its ``serve.sync`` spans: a sampled token, or the pending greedy tokens
of a flush) per engine step (``engine_steps_total``), over the traced run's
measured window.  None where the window holds no step or the program counts
no syncs.  Moves, by cell: ``host_syncs_per_step.ttft_p50`` moves
``ttft_p50_ms`` (chat), ``host_syncs_per_step.itl_p95`` moves
``itl_p95_ms`` (code).
"""


def read(ctx):
    if ctx.snap0 is None or ctx.snap1 is None:
        return None
    c0, c1 = ctx.snap0["counters"], ctx.snap1["counters"]
    if "host_syncs_total" not in c1:
        return None
    steps = c1["engine_steps_total"] - c0["engine_steps_total"]
    if steps <= 0:
        return None
    return (c1["host_syncs_total"] - c0["host_syncs_total"]) / steps
