"""decode_step_ms: engine steps (``serve/engine.py``) decode program.

Device time per call of the decode-step program (``jit(_paged_step)``,
matched by name), from the profiler trace: the program's module events
inside the traced sub-window, summed and divided by their number, in ms.
Moves, by cell: ``decode_step_ms.ttft_p50`` moves ``ttft_p50_ms``
(chat), ``decode_step_ms.itl_p95`` moves ``itl_p95_ms`` (code).
"""


def read(ctx):
    got = ctx.trace.module_calls(ctx.red, ctx.trace.DECODE_PROGRAM)
    if got is None:
        return None
    calls, seconds = got
    return seconds / calls * 1e3
