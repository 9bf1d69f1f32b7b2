"""The synthetic federated token corpus and cohort draw, from the seed.

A copy of the generator the program's data layer implements
(client-specific bigram habits over a Zipf(1.2) marginal, streams keyed by
``(seed, client, round)``; cohorts drawn without replacement from a stream
keyed by ``(seed, round)``), so that the reference sees the rows the
program should have fed without taking them from the program.
"""
from __future__ import annotations

import numpy as np

ZIPF_A = 1.2
HOT_TOKENS = 512


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence(entropy=seed, spawn_key=key))


def cohort_ids(seed: int, round_idx: int, population: int,
               clients: int) -> np.ndarray:
    """The ``clients`` ids drawn for round ``round_idx``."""
    return _rng(seed, round_idx).choice(population, size=clients,
                                        replace=False)


def client_tokens(seed: int, client: int, n: int, vocab: int,
                  salt: int) -> np.ndarray:
    """``n`` tokens of one client's stream for one round (``salt``)."""
    rng = _rng(seed, client, salt)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-ZIPF_A)
    base = rng.choice(vocab, size=n, p=p / p.sum())
    succ = rng.integers(0, vocab, size=HOT_TOKENS)
    prev = base[:-1]
    swap = (prev < HOT_TOKENS) & (rng.random(n - 1) < 0.5)
    nxt = np.where(swap, succ[np.minimum(prev, HOT_TOKENS - 1)], base[1:])
    return np.concatenate([base[:1], nxt]).astype(np.int32)


def client_batches(seed: int, client: int, steps: int, batch: int,
                   seq_len: int, vocab: int, salt: int) -> np.ndarray:
    """(steps, batch, seq_len + 1) token ids of one client for one round."""
    toks = client_tokens(seed, client, steps * batch * (seq_len + 1), vocab,
                         salt)
    return toks.reshape(steps, batch, seq_len + 1)


def round_batches(seed: int, round_idx: int, population: int, clients: int,
                  steps: int, batch: int, seq_len: int,
                  vocab: int) -> np.ndarray:
    """(clients, steps, batch, seq_len + 1): the cohort of ``round_idx``."""
    ids = cohort_ids(seed, round_idx, population, clients)
    return np.stack([client_batches(seed, int(c), steps, batch, seq_len,
                                    vocab, round_idx) for c in ids])


def eval_batch(seed: int, population: int, batch: int, seq_len: int,
               vocab: int) -> np.ndarray:
    """(batch, seq_len + 1): the held-out batch of client ``population + 1``."""
    return client_batches(seed, population + 1, 1, batch, seq_len, vocab,
                          0)[0]
