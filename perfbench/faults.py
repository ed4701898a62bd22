"""Faults planted under the timed path, to see the check fail them.

The faults a one-chip serving cell can have: a decode step that returns
its state unchanged (the pool keeps no new K/V), half of the batch left out
(every odd slot gets its neighbour's answer), and a token altered where it
is produced (on the device for greedy decoding, on the host under
sampling).  :func:`plant` swaps them into ``repro.serve.engine`` before an
engine is built; ``tests/test_check.py`` plants them at rehearsal size and
``calibrate.py --fault`` at the cell's own size on the chip.
"""
from __future__ import annotations

FAULTS = ("state_unchanged", "half_batch", "token_altered")


def broken_decode(real_fn, fault: str):
    """A ``_decode_paged_fn`` whose steps carry ``fault``."""
    import jax
    import jax.numpy as jnp

    def make(cfg):
        real = real_fn(cfg)

        def step(params, caches, tokens, seq_pos, table, active):
            if fault == "state_unchanged":
                g, logits, _ = real(params, jax.tree.map(jnp.copy, caches),
                                    tokens, seq_pos, table, active)
                return g, logits, caches
            g, logits, caches = real(params, caches, tokens, seq_pos, table, active)
            if fault == "half_batch":
                logits = logits.at[1::2].set(logits[0::2])
                g = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
            if fault == "token_altered":
                g = (g + 1) % cfg.vocab_size
            return g, logits, caches

        return step

    return make


def plant(setattr_, fault: str) -> None:
    """Plant ``fault`` with ``setattr_(obj, name, value)`` (pytest's
    ``monkeypatch.setattr``, or plain ``setattr`` in a process of its own)."""
    from repro.serve import engine as E

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    setattr_(E, "_decode_paged_fn", broken_decode(E._decode_paged_fn, fault))
    if fault == "token_altered":  # sampled tokens are produced on the host
        real_sample = E.Engine._sample
        setattr_(E.Engine, "_sample", lambda self, row, req: (
            real_sample(self, row, req) + 1) % self.cfg.vocab_size)
