"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the longest one and
every one that was preempted, is run through :mod:`reference` over its
prompt and the tokens it was served.  For each served token the check reads
how far the token's logit lies below the reference's best; under sampling
the engine's own Gumbel draw is added to both (see
``reference.gumbel_noise``), so the served token is then the reference's
first choice too, up to rounding.  The widest such gap over the sample is
held to the cell's limit (``perfbench/limits/<cell>.json``), whose readings
``PERF.md`` gives.  A request due in the window that got no first token
before the loop gave up on it (a minute past the window's close) is an
answer that never came, and fails the run: the limit on
``requests_without_first_token`` is 0.  The sample covers chunked prefill (each first token),
every decode step of those requests, the steps after page growth, and any
re-prefill after a preemption.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

SAMPLE_MAX = 24  # requests, at most


def sample(done: List, seed: int, tokens: int) -> List:
    """Finished requests to compare, up to ``tokens`` served tokens: the
    longest, every preempted one, then others in an order drawn from the
    seed."""
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.plan.prompt) + len(s.req.out_tokens))
    pre = [s for s in done if s.req.stats.n_preemptions and s is not longest]
    rest = [s for s in done if s is not longest and s not in pre]
    rng = np.random.default_rng(seed)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for s in [longest] + pre + rest:
        if len(out) >= SAMPLE_MAX or n >= tokens:
            break
        out.append(s)
        n += len(s.req.out_tokens)
    return out


def served_of(picked: List):
    """(sequences, served) as :meth:`reference.Reference.gaps` takes them:
    the prompt and every served token but the last."""
    seqs, served = [], []
    for s in picked:
        toks = np.asarray(s.req.out_tokens, np.int32)
        seqs.append(np.concatenate([s.plan.prompt, toks[:-1]]))
        served.append({"rid": s.plan.rid, "start": len(s.plan.prompt),
                       "tokens": toks})
    return seqs, served


def incomplete(picked: List, eos) -> int:
    """Requests that stopped before ``max_new`` without serving ``eos``."""
    return sum(1 for s in picked
               if len(s.req.out_tokens) != s.plan.max_new
               and not (eos is not None and s.req.out_tokens
                        and s.req.out_tokens[-1] == eos))


def limits(root: Path, cell: str) -> Dict:
    return json.loads((root / "perfbench" / "limits" / f"{cell}.json").read_text())


def compare(spec: Dict, seed: int, window, sampler: Dict, lim: Dict,
            rng_seed: int, control: str = "") -> Dict:
    """The numbers compared, each with its limit, and ``correct``.

    ``control``: a reference precision ("fp8") whose own first choices are
    judged in place of the served tokens (for the control's reading)."""
    import reference as R

    done = [s for s in window.served if s.done]
    picked = sample(done, rng_seed, lim["sample_tokens"])
    seqs, served = served_of(picked)
    ref = R.Reference(spec, seed)
    pick = R.Reference(spec, seed, quant=control) if control else None
    gaps = ref.gaps(seqs, served, sampler, pick=pick)
    gap = float(gaps.max()) if len(gaps) else float("inf")
    short = incomplete(picked, spec["eos_token_id"])
    compared = {
        "logit_gap_max": {"value": gap, "limit": lim["logit_gap_max"]["limit"]},
        "short_requests": {"value": short, "limit": 0},
        "requests_without_first_token": {"value": len(window.missing()),
                                         "limit": 0},
    }
    ok = bool(len(gaps)) and all(c["value"] <= c["limit"]
                                 for c in compared.values())
    return {"correct": ok, "compared": compared, "tokens": int(len(gaps)),
            "requests": len(picked)}
