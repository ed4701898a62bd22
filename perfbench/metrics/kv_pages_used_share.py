"""kv_pages_used_share: KV manager (``serve/kvcache.py``) pool in use.

After each engine step of the traced run's window: pool pages that cannot
be handed out now (``pages_total - available_pages``: held by running
requests, shared prefix pages included) over ``pages_total``; the mean, in
%.  Pages the prefix index keeps for finished requests are reclaimable and
do not count.  Moves ``output_tok_per_s``.
"""


def read(ctx):
    if not ctx.pages_used:
        return None
    return 100.0 * sum(ctx.pages_used) / len(ctx.pages_used)
