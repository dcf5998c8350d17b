"""The federated task's inputs, made from the seed: a copy of the program's
synthetic Markov-chain language task (``repro/data/synthetic.py`` and
``repro/data/partition.py``) and of its client selection chain
(``repro.fl.simulation.select_round_clients``).

The reference needs the very token streams and client choices the program
draws from ``FLConfig.seed``, and imports nothing of the program, so the
generator lives here too.  The same numpy calls on the same seed give the
same arrays bit for bit.
"""

from __future__ import annotations

from typing import Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np


class Task:
    def __init__(self, vocab: int, n_clients: int, alpha, seed: int,
                 n_classes: int = 8, concentration: float = 6.0):
        rng = np.random.default_rng(seed)
        logits = rng.normal(size=(vocab, vocab))
        top = np.argpartition(-logits, 8, axis=1)[:, :8]
        boost = np.zeros_like(logits)
        np.put_along_axis(boost, top, concentration, axis=1)
        trans = np.exp(logits * 0.3 + boost)
        trans /= trans.sum(axis=1, keepdims=True)
        self.vocab, self.n_classes, self.trans = vocab, n_classes, trans
        self.class_of = rng.integers(0, n_classes, size=vocab)
        if alpha is None:
            self.priors = np.full((n_clients, n_classes), 1.0 / n_classes)
        else:
            p = rng.dirichlet([alpha] * n_classes, size=n_clients)
            self.priors = (p + 1e-6) / (p + 1e-6).sum(axis=1, keepdims=True)

    def stream(self, client: int, batch: int, seq: int, seed: int
               ) -> Iterator[Dict[str, np.ndarray]]:
        """Endless {tokens, labels} batches of one client (-1: the eval
        stream, drawn from the uniform class mixture)."""
        rng = np.random.default_rng(hash((seed, client)) % (2**31))
        prior = (np.ones(self.n_classes) / self.n_classes if client < 0
                 else self.priors[client])
        w = prior[self.class_of]
        trans_w = self.trans * w[None, :]
        trans_w /= trans_w.sum(axis=1, keepdims=True)
        cdf, p0 = np.cumsum(trans_w, axis=1), w / w.sum()
        del trans_w
        while True:
            x = np.empty((batch, seq + 1), np.int64)
            x[:, 0] = rng.choice(self.vocab, size=batch, p=p0)
            u = rng.random((batch, seq))
            for t in range(seq):
                x[:, t + 1] = (u[:, t:t + 1] < cdf[x[:, t]]).argmax(axis=1)
            yield {"tokens": np.asarray(x[:, :-1], np.int32),
                   "labels": np.asarray(x[:, 1:], np.int32)}


def selected_clients(seed: int, rnd: int, n_clients: int, n_sel: int):
    """The clients of round ``rnd``, sorted: a fold_in chain of the seed."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed + 0xC11E47), rnd)
    perm = jax.random.permutation(key, n_clients)
    return [int(c) for c in np.sort(np.asarray(perm[:n_sel]))]


def n_selected(participation: float, n_clients: int) -> int:
    return max(1, int(round(participation * n_clients)))


def eval_block(task: Task, batch: int, seq: int, seed: int, n: int):
    """The program's held-out block: the first ``n`` batches of the eval
    stream, stacked."""
    s = task.stream(-1, batch, seq, seed + 999)
    bs = [next(s) for _ in range(n)]
    return {k: jnp.stack([b[k] for b in bs]) for k in bs[0]}
