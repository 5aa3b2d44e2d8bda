"""Synthetic text for the traffic mixes: Zipf–Markov token streams.

Each row starts from a Zipf-distributed token; at every later position it
either continues a fixed chain (prev·31 + 7 mod vocab, with probability
3/4) or resets to a fresh Zipf draw, as ``repro.data.tasks.lm_batch``
does.  Batch ``i`` of a run is a pure function of (seed, i), so the
program under test and the reference read the same rows, and every step
reads new ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CONTINUE_P, CHAIN_MULT, CHAIN_ADD = 0.75, 31, 7


def batch(seed: int, index, rows: int, seq_len: int, vocab: int):
    """{"tokens", "labels"}: int32 [rows, seq_len], labels the next token."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    k_zipf, k_cont = jax.random.split(key)
    u = jax.random.uniform(k_zipf, (rows, seq_len + 1), minval=1e-6)
    z = jnp.clip(jnp.exp(u * np.log(vocab)).astype(jnp.int32) - 1,
                 0, vocab - 1)
    cont = jax.random.bernoulli(k_cont, CONTINUE_P, (rows, seq_len + 1))

    def chain(prev, inputs):
        zt, ct = inputs
        nxt = jnp.where(ct, (prev * CHAIN_MULT + CHAIN_ADD) % vocab, zt)
        return nxt, nxt

    _, toks = jax.lax.scan(chain, z[:, 0], (z.T[1:], cont.T[1:]))
    toks = jnp.concatenate([z[:, :1], toks.T], axis=1)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def sampler(seed: int, rows: int, seq_len: int, vocab: int):
    """sample_fn(i) for the training loop (traced inside its scan)."""
    def sample_fn(i):
        return batch(seed, i, rows, seq_len, vocab)
    return sample_fn
