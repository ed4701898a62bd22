"""collective_share: collectives (the tensor-parallel step's cross-chip ops).

The share of the decode-step program's device time (``jit__paged_step``
runs, matched by name) spent in collective ops, averaged over the devices
traced, in %.  A collective is an op of the trace's ``XLA Ops`` line named
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all`` or
``collective-permute``, or the ``-start`` / ``-done`` half of one's
asynchronous form.  The chip's trace (TPU v5 lite, four chips, jax 0.9)
names each op by its HLO text, numbered:
``%all-reduce.17 = f32[16]{0:T(128)S(1)} all-reduce(f32[16]{...} %convert_reduce_fusion.3), channel_id=1, ...``,
``%all-gather.39 = bf16[16,1,4608]{2,0,1:T(8,128)(2,1)S(1)} all-gather(...)``
and, in the prefill chunks, ``%collective-permute-done.5 = bf16[128,1152]{...}
collective-permute-done(...)``.  A fusion that takes a collective's result
(``fusion(... %all-gather.39 ...)``) is not one.  Only an op that starts
inside a whole run of the program counts, so the sampling and prefill
programs' collectives are left out.  Finds nothing, and reads None, where no
collective ran: a run on one chip.  Moves ``ttft_p50_ms``
(``collective_share.ttft_p50``), as the cell's other decode-side metrics
do.
"""

COLLECTIVE_OP = (r"(^%?|\s)(all-reduce|all-gather|reduce-scatter|all-to-all|"
                 r"collective-permute)(-start|-done)?[.\s(=]")


def read(ctx):
    coll = ctx.trace.op_seconds(ctx.red, COLLECTIVE_OP,
                                within=ctx.trace.DECODE_PROGRAM)
    prog = ctx.trace.module_calls(ctx.red, ctx.trace.DECODE_PROGRAM)
    if not coll or prog is None:
        return None
    return 100.0 * coll / prog[1]
