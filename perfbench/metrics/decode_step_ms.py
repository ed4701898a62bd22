"""decode_step_ms: engine steps (``serve/engine.py``) decode program.

Device time per call of the decode-step program (``jit(_paged_step)``),
from the profiler trace: the program's module events inside the traced
sub-window, summed and divided by their number, in ms.  Moves, by
cell: ``decode_step_ms.ttft_p50`` moves ``ttft_p50_ms``
(chat), ``decode_step_ms.itl_p95`` moves ``itl_p95_ms`` (code).
"""
# both steps are named jit__unknown(<id>) in the trace (jitted partials);
# the decode step is the one that runs the paged-decode Pallas kernel
PATTERN = r"^jit_(_unknown|_paged_step)\b"
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    got = ctx.trace.module_calls(ctx.red, PATTERN, KERNEL, present=True)
    if got is None:
        return None
    calls, seconds = got
    return seconds / calls * 1e3
