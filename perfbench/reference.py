"""Plain float32 reference of a configuration, and its float8 control.

The architecture's math (embedding, one layer of each kind, the head) is
its family module's (``perfbench/families/<family>.py``), written from the
published description and importing nothing of the program.  This module
keeps what every family shares: causal attention in blocks of query rows,
the matmul at each precision, the sampler's noise and the scoring.

Weights come from :mod:`model`'s generator, regenerated from the seed one
layer at a time, so the reference never holds more than one layer's
weights and the activations of the sequences it checks.  Every matmul runs
at ``precision="highest"``: on a TPU a float32 matmul is otherwise done in
bfloat16 passes.

``quant="fp8"`` computes every matmul on operands rounded to float8 e4m3
(per-tensor scale for weights, per-row for activations): the precision
below the bf16 that the configurations state, used as the check's control.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import model as MB

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512  # query rows per attention block (bounds the score tensor)
LOGIT_BLOCK = 256  # positions per head block (bounds the logit tensor)
BUCKET = 512  # sequences are padded to a multiple: few compiled shapes


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def matmul(a, w, quant):
    """a (..., k) @ w (k, n) in float32, or on float8-rounded operands."""
    if quant == "fp8":
        a, w = _q8(a, -1), _q8(w, None)
    return jnp.matmul(a, w, precision=HIGHEST)


def attention(q, k, v, quant, window=None):
    """Causal attention, within ``window`` positions where given; q (S, H,
    dh), k/v (S, Hkv, dh)."""
    S, H, dh = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    window = window or S + 1
    kpos = jnp.arange(S)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0 : q0 + Q_BLOCK]
        qpos = q0 + jnp.arange(qb.shape[0])
        if quant == "fp8":
            s = jnp.einsum("qhd,khd->hqk", _q8(qb, -1), _q8(k, -1), precision=HIGHEST)
        else:
            s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(dh)
        ok = (kpos[None] <= qpos[:, None]) & (qpos[:, None] - kpos[None] < window)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        if quant == "fp8":
            p, vv = _q8(p, -1), _q8(v, 0)
        else:
            vv = v
        outs.append(jnp.einsum("hqk,khd->qhd", p, vv, precision=HIGHEST))
    return jnp.concatenate(outs, 0)


def _gumbel(seed, rid, k, vp: int):
    """The engine's sampler draws token k of request rid as
    argmax(logits / T + Gumbel(fold_in(fold_in(PRNGKey(seed), rid), k)))
    over the padded vocabulary; this is that Gumbel draw."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), rid), k)
    return jax.random.gumbel(key, (vp,), jnp.float32)


def gumbel_noise(sampler: Dict, rid: int, ks, vp: int):
    """:func:`_gumbel` for the token indices ``ks``."""
    return jax.jit(jax.vmap(lambda k: _gumbel(sampler["seed"], rid, k, vp)))(
        jnp.asarray(ks, jnp.int32))


class Reference:
    """The reference over a set of sequences, for one configuration/seed."""

    def __init__(self, spec: Dict, seed: int, quant: str = "f32"):
        self.spec, self.seed, self.quant = spec, seed, quant
        fam = MB.family(spec)
        self.kinds = fam.layer_kinds(spec)
        self.V, self.Vp = fam.vocab(spec)
        spec_s = json.dumps(spec, sort_keys=True)  # hashable for the jit caches
        self._layer = {k: _layer_fn(spec_s, k, quant) for k in set(self.kinds)}
        self._weights = {k: _weights_fn(spec_s, seed, k) for k in set(self.kinds)}
        self._embed = _embed_fn(spec_s)
        self._head = _head_fn(spec_s, quant)
        self.g = _globals_fn(spec_s, seed)()

    def hidden(self, seqs: Sequence[np.ndarray]) -> List[jnp.ndarray]:
        """Final hidden states (before the final norm) of each sequence."""
        hs = []
        for s in seqs:
            n = -(-len(s) // BUCKET) * BUCKET  # causal: padding never leaks back
            toks = np.zeros(n, np.int32)
            toks[: len(s)] = s
            hs.append(self._embed(self.g, jnp.asarray(toks)))
        for l, kind in enumerate(self.kinds):
            W = self._weights[kind](l)
            hs = [self._layer[kind](W, h) for h in hs]
        return hs

    def logits(self, h, rows):
        """Head over rows of one sequence's hidden states, in blocks."""
        idx = np.full(-(-len(rows) // LOGIT_BLOCK) * LOGIT_BLOCK, rows[-1])
        idx[: len(rows)] = rows
        out = [self._head(self.g, h[idx[b : b + LOGIT_BLOCK]])
               for b in range(0, len(idx), LOGIT_BLOCK)]
        return jnp.concatenate(out)[: len(rows)]

    def gaps(self, seqs, served: Sequence[Dict], sampler: Dict, *, pick=None):
        """Per served token, how far the token's (perturbed) logit lies
        below the reference's best, in units of the standard deviation of
        the reference's logits at that position (so that one limit reads
        alike at every width and logit scale).

        ``served[i]``: ``{"rid", "start", "tokens"}``: the tokens served after
        position ``start - 1`` of sequence i, from the request's first.
        ``pick``: a second Reference whose own first choice is judged in
        place of the served token (the control reads the gap of what a lower
        precision would serve).  Every block of rows has one shape, so each
        program compiles once."""
        T = float(sampler["temperature"])
        hs = self.hidden(seqs)
        hs_pick = pick.hidden(seqs) if pick is not None else None
        score = _score_fn(self.V, self.Vp, T)
        out = []
        for i, (h, sv) in enumerate(zip(hs, served)):
            toks = np.asarray(sv["tokens"], np.int64)
            n = len(toks)
            rows = sv["start"] - 1 + np.arange(n)
            for b in range(0, n, LOGIT_BLOCK):
                r = rows[b : b + LOGIT_BLOCK]
                idx = np.full(LOGIT_BLOCK, r[-1])
                idx[: len(r)] = r
                ks = np.arange(b, b + LOGIT_BLOCK, dtype=np.int32)
                t = np.zeros(LOGIT_BLOCK, np.int32)
                t[: len(r)] = np.clip(toks[b : b + len(r)], 0, self.V - 1)
                zp = None
                if pick is not None:
                    zp = pick._head(pick.g, hs_pick[i][idx])
                g = score(self._head(self.g, h[idx]), zp, sampler["seed"],
                          sv["rid"], ks, t)
                g = np.asarray(g[: len(r)], np.float64)
                bad = (toks[b : b + len(r)] < 0) | (toks[b : b + len(r)] >= self.V)
                g[bad] = np.inf
                out.append(g)
        return np.concatenate(out) if out else np.zeros(0)


@functools.lru_cache(maxsize=None)
def _score_fn(V: int, vp: int, T: float):
    """Gaps of one block of rows: (logits, the pick's logits or None,
    sampler seed, request id, token indices, served tokens) -> gaps."""
    def score(z, zp, seed, rid, ks, toks):
        spread = z.std(-1)
        if T > 0:
            noise = T * jax.vmap(lambda k: _gumbel(seed, rid, k, vp))(ks)[:, :V]
            z = z + noise
            zp = None if zp is None else zp + noise
        if zp is not None:
            toks = jnp.argmax(zp, -1)
        val = jnp.take_along_axis(z, toks[:, None], 1)[:, 0]
        return (z.max(-1) - val) / spread

    return jax.jit(score)


@functools.lru_cache(maxsize=None)
def _layer_fn(spec_s: str, kind: str, quant: str):
    spec = json.loads(spec_s)
    fam = MB.family(spec)
    return jax.jit(lambda W, h: fam.layer(spec, kind, W, h, quant))


@functools.lru_cache(maxsize=None)
def _globals_fn(spec_s: str, seed: int):
    spec = json.loads(spec_s)
    return jax.jit(lambda: MB.global_weights(spec, seed))


@functools.lru_cache(maxsize=None)
def _weights_fn(spec_s: str, seed: int, kind: str):
    spec = json.loads(spec_s)
    return jax.jit(lambda l: MB.layer_weights(spec, seed, l, kind))


@functools.lru_cache(maxsize=None)
def _embed_fn(spec_s: str):
    spec = json.loads(spec_s)
    fam = MB.family(spec)
    return jax.jit(lambda g, toks: fam.embed(spec, g, toks))


@functools.lru_cache(maxsize=None)
def _head_fn(spec_s: str, quant: str):
    spec = json.loads(spec_s)
    fam = MB.family(spec)
    return jax.jit(lambda g, h: fam.head(spec, g, h, quant))
