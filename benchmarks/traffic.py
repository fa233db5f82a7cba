"""The one generator of traffic: it reads a mix's parameters from
`traffic/<name>.json` and draws the inputs from the seed.

A closed loop's requests come in cycles of the mix's `cycle`. Every cycle
holds the same set of sizes, one prompt length from each of `cycle` equal
strata of the mix's distribution and one reply length likewise; the seed
pairs them, orders them and draws the token values (and, through the
program, the weights). So every seed does the same work in another order,
and what a run reads varies with the order as far as the system lets it.
"""

from __future__ import annotations

import math

import numpy as np


def quantiles(dist: dict, n: int) -> list[int]:
    """n whole sizes at the mid-quantiles of a distribution's strata."""
    lo, hi = dist["min"], dist["max"]
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "uniform":
        return [int(round(lo + (hi - lo) * q)) for q in qs]
    if dist["dist"] == "log_uniform":
        return [int(round(math.exp(math.log(lo)
                                   + (math.log(hi) - math.log(lo)) * q)))
                for q in qs]
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def request_sizes(traffic: dict) -> tuple[list[int], list[int]]:
    """(prompt lengths, reply lengths) of one cycle of the mix."""
    return (quantiles(traffic["prompt_tokens"], traffic["cycle"]),
            quantiles(traffic["new_tokens"], traffic["cycle"]))


def requests(traffic: dict, vocab_size: int, seed: int):
    """An endless stream of (prompt token list, max_new_tokens): cycle
    after cycle of the mix's sizes, each cycle paired and ordered by the
    seed, with token values of the seed's. No two prompts share a prefix
    but by chance."""
    rng = np.random.default_rng(seed)
    prompts, replies = request_sizes(traffic)
    while True:
        for p, m in zip(rng.permutation(prompts), rng.permutation(replies)):
            yield rng.integers(0, vocab_size, int(p)).tolist(), int(m)


def train_batches(traffic: dict, vocab_size: int, seed: int, steps: int):
    """(x, y) for `steps` distinct batches of next-token prediction over
    seeded random tokens, in host memory, as FFModel.fit takes them."""
    rng = np.random.default_rng(seed)
    seq, batch = traffic["sequence_length"], traffic["global_batch"]
    rows = steps * batch
    toks = rng.integers(0, vocab_size, (rows, seq + 1)).astype(np.int32)
    x = {"tokens": toks[:, :-1],
         "positions": np.tile(np.arange(seq, dtype=np.int32), (rows, 1))}
    return x, toks[:, 1:, None]
