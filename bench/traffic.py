"""One general generator for every traffic file: a pool of training
batches made from the seed in set-up.

The token arithmetic is that of the program's `data/synthetic.py`
`SyntheticLM` (a Zipf unigram tail, a first-order Markov chain over the
frequent tokens and induction-style copies), kept here so that the
yardstick cannot change under a program change, and run once for the
whole pool: `SyntheticLM.batch` loops over every position in Python, too
slowly to run inside a timed window. Every seed gets the same sizes; the
seed only changes the tokens."""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1),
                                  sum(map(ord, stream))])


def make_pool(vocab: int, traffic: dict, seed: int) -> dict:
    """{"tokens", "labels"}: int32 arrays (pool_steps, replicas,
    sequences_per_replica, seq_len); labels are the next tokens."""
    p = traffic["synthetic"]
    n = (traffic["pool_steps"] * traffic["replicas"]
         * traffic["sequences_per_replica"])
    seq = traffic["seq_len"]
    m = min(p["n_states"], vocab)
    rng = rng_for(seed, "traffic")
    trans = rng.dirichlet(np.full(m, 0.3), size=m)
    trans_cum = np.cumsum(trans, axis=1)
    ranks = np.arange(1, vocab + 1)
    zipf = 1.0 / ranks ** p["zipf_exponent"]
    zipf_cum = np.cumsum(zipf / zipf.sum())

    toks = np.empty((n, seq + 1), np.int64)
    state = rng.integers(0, m, size=n)
    toks[:, 0] = state
    u = rng.random((n, seq))
    mix = rng.random((n, seq))
    zipf_draw = np.minimum(np.searchsorted(zipf_cum, rng.random((n, seq))),
                           vocab - 1)
    dist = p["copy_distance"]
    for t in range(1, seq + 1):
        # searchsorted(trans_cum[s], u) for every row at once
        nxt = np.minimum((trans_cum[state] < u[:, t - 1, None]).sum(1),
                         m - 1)
        nxt = np.where(mix[:, t - 1] < p["jump_prob"], zipf_draw[:, t - 1],
                       nxt)
        if t > dist:
            copy = mix[:, t - 1] > 1.0 - p["copy_prob"]
            nxt = np.where(copy, toks[:, t - dist], nxt)
        state = np.minimum(nxt, m - 1)
        toks[:, t] = nxt
    shape = (traffic["pool_steps"], traffic["replicas"],
             traffic["sequences_per_replica"], seq)
    return {"tokens": toks[:, :-1].astype(np.int32).reshape(shape),
            "labels": toks[:, 1:].astype(np.int32).reshape(shape)}
