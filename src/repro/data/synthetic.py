"""Deterministic, seekable synthetic data pipeline.

Every batch is a pure function of (seed, step) via stateless PRNG folding —
the property that makes the whole fault-tolerance story work: any host can
regenerate any shard of any step after a restart, elastic rescale, or
straggler re-assignment, with no iterator state to checkpoint and no data
loss/replay.

Sequences are drawn from a fixed first-order Markov "teacher" (seeded
transition logits), so models measurably learn; fine-tuning benchmarks use a
second teacher seed as the "downstream task". A row of the teacher is drawn
from the PRNG when a token needs it, never stored as a (vocab, vocab) table:
at a 152k vocab that table would be 92 GB.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

CONCENTRATION = 1.5


def markov_rows(task_seed: int, tokens: jax.Array, vocab: int) -> jax.Array:
    """Teacher next-token logits for each of `tokens` -> (*tokens.shape,
    vocab); row t is a pure function of (task_seed, t)."""
    key = jax.random.PRNGKey(task_seed)
    row = lambda t: jax.random.normal(jax.random.fold_in(key, t), (vocab,))
    flat = jax.vmap(row)(tokens.reshape(-1)) * CONCENTRATION
    return flat.reshape(*tokens.shape, vocab)


def markov_table(vocab: int, task_seed: int):
    """The whole (vocab, vocab) teacher; for inspection at small vocab."""
    return markov_rows(task_seed, jnp.arange(vocab), vocab)


def sample_markov(key: jax.Array, task_seed: int, vocab: int, batch: int,
                  seq: int):
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (batch,), 0, vocab)

    def step(tok, k):
        nxt = jax.random.categorical(k, markov_rows(task_seed, tok, vocab))
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(key, seq - 1))
    return jnp.concatenate([first[None], rest], axis=0).T.astype(jnp.int32)


@dataclass
class SyntheticLM:
    vocab: int
    batch: int
    seq: int
    seed: int = 0
    task_seed: int = 1
    codebooks: int = 0

    def __post_init__(self):
        self._sample = jax.jit(
            lambda key: sample_markov(key, self.task_seed, self.vocab,
                                      self.batch, self.seq + 1))

    def batch_at(self, step: int, shard: int = 0, num_shards: int = 1) -> Dict:
        """Batch for global `step`; `shard`/`num_shards` carve the global
        batch deterministically for multi-host loading."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        toks = self._sample(key)                      # (B, seq+1)
        if num_shards > 1:
            per = self.batch // num_shards
            toks = toks[shard * per:(shard + 1) * per]
        tokens, labels = toks[:, :-1], toks[:, 1:]
        if self.codebooks:
            tokens = jnp.repeat(tokens[..., None], self.codebooks, axis=-1)
            labels = jnp.repeat(labels[..., None], self.codebooks, axis=-1)
        return {"tokens": tokens, "labels": labels}


@dataclass
class SyntheticClassification:
    """K-class Gaussian-blob classification (paper Appendix C.2 setting)."""
    num_classes: int = 8
    dim: int = 2
    noise: float = 0.4
    seed: int = 0

    def dataset(self, n_per_class: int = 64):
        rng = np.random.default_rng(self.seed)
        angles = np.linspace(0, 2 * np.pi, self.num_classes, endpoint=False)
        centers = np.stack([np.cos(angles), np.sin(angles)], -1) * 2.0
        if self.dim > 2:
            centers = np.concatenate(
                [centers, np.zeros((self.num_classes, self.dim - 2))], -1)
        xs, ys = [], []
        for c in range(self.num_classes):
            xs.append(centers[c] + rng.normal(size=(n_per_class, self.dim))
                      * self.noise)
            ys.append(np.full(n_per_class, c))
        x = np.concatenate(xs).astype(np.float32)
        y = np.concatenate(ys).astype(np.int32)
        perm = rng.permutation(len(y))
        return jnp.asarray(x[perm]), jnp.asarray(y[perm])
