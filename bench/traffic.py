"""The benchmark's one traffic generator: a traffic file's parameters in,
one round's numpy batches out.

Tokens follow the Zipf-Markov stream of ``repro.data.synthetic.make_lm_tokens``
(a Zipf unigram marginal with a sparse second-order Markov overlay), rewritten
to make every row its own chain, so a whole pool is drawn in one vectorised
pass over positions instead of a Python loop over every token.  Rows of one
pool all differ.  With ``non_iid`` each node's rows pass through that node's
own permutation of the vocabulary, drawn from the seed, so the nodes' token
frequencies differ (the paper's heterogeneous-data premise).
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Traffic", "markov_rows"]


def markov_rows(rng: np.random.Generator, n_rows: int, length: int, vocab: int,
                zipf: float, order: int, branch: int, contexts: int) -> np.ndarray:
    """``n_rows`` independent Zipf-Markov chains of ``length`` tokens (int32)."""
    if order != 2:
        raise ValueError(f"the Markov overlay is second order, got order={order}")
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** (-zipf)
    probs /= probs.sum()
    a, b = rng.integers(1, 2**31 - 1, size=2)
    cand = rng.choice(vocab, size=(contexts, min(branch, vocab)), p=probs)
    choice = rng.integers(0, cand.shape[1], size=(n_rows, length))
    rows = np.empty((n_rows, length), np.int64)
    rows[:, :order] = rng.choice(vocab, size=(n_rows, order), p=probs)
    for t in range(order, length):
        h = (a * rows[:, t - 1] + b * rows[:, t - 2]) % contexts
        rows[:, t] = cand[h, choice[:, t]]
    return rows.astype(np.int32)


@dataclasses.dataclass
class Traffic:
    """A pool of ``pool_rounds`` rounds of batches, shaped as the round step
    takes them: ``(tau, nodes, node_batch, ...)`` per round."""

    tokens: np.ndarray                     # (P, tau, N, b, seq + 1) int32

    @classmethod
    def make(cls, spec: dict, model: dict, nodes: int, seed: int) -> "Traffic":
        tok = spec["tokens"]
        vocab = model["vocab_size"]
        seq = spec["seq_len"]
        shape = (spec["pool_rounds"], spec["tau"], nodes, spec["node_batch"])
        rng = np.random.default_rng([seed, 0x7E57])
        rows = markov_rows(rng, int(np.prod(shape)), seq + 1, vocab, tok["zipf"],
                           tok["order"], tok["branch"], tok["contexts"]).reshape(*shape, seq + 1)
        if tok["non_iid"]:
            perms = np.stack([rng.permutation(vocab) for _ in range(nodes)]).astype(np.int32)
            rows = perms[np.arange(nodes)[None, None, :, None, None], rows]
        return cls(rows)

    @property
    def rounds(self) -> int:
        return self.tokens.shape[0]

    def round(self, r: int) -> dict:
        """Round ``r``'s batches (the pool repeats past its end)."""
        rows = self.tokens[r % self.rounds]
        return {"tokens": rows[..., :-1], "targets": rows[..., 1:]}
