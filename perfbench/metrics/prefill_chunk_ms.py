"""prefill_chunk_ms: engine steps (``serve/engine.py``) prefill-chunk program.

Device time per call of the chunked-prefill program (``jit(prefill_chunk)``,
matched by name, every chunk length pooled), from the profiler trace, in
ms.  Moves ``ttft_p50_ms``.
"""


def read(ctx):
    got = ctx.trace.module_calls(ctx.red, ctx.trace.PREFILL_PROGRAM)
    if got is None:
        return None
    calls, seconds = got
    return seconds / calls * 1e3
