"""prefill_chunk_ms: engine steps (``serve/engine.py``) prefill-chunk program.

Device time per call of the chunked-prefill program (``jit(prefill_chunk)``,
every chunk length pooled), from the profiler trace, in ms.  Moves
``ttft_p50_ms``.
"""
# both steps are named jit__unknown(<id>) in the trace (jitted partials);
# the decode step is the one that runs the paged-decode Pallas kernel
PATTERN = r"^jit_(_unknown|prefill_chunk)\b"
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    got = ctx.trace.module_calls(ctx.red, PATTERN, KERNEL, present=False)
    if got is None:
        return None
    calls, seconds = got
    return seconds / calls * 1e3
