"""The general generator of closed-loop traffic, driven by a mix file.

A mix (``traffic/<name>.json``) gives the number of clients and, for the
prompt and the output, a lognormal length (``median``, ``sigma``) clipped
to ``[min, max]``.  The lengths are a pool of ``pool`` (prompt, output)
pairs at evenly spaced quantiles of the two distributions, paired by a
fixed permutation: the same set of sizes for every seed.  The seed draws
the order they are served in, stratified: each block of ``clients``
requests takes one pair from each of ``clients`` strata of the pool
sorted by total length, so any stretch of the stream carries about the
same work.  With ``pool == clients`` every block is the whole pool, the
clients' first requests included.  The seed also draws the prompt token
ids (and, in the harness, the weights).
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np


def derive(seed: int, tag: str) -> int:
    """A 32-bit integer derived from ``seed`` and ``tag``: seeds above
    2**32 stay distinct, and each use of the seed gets its own stream."""
    digest = hashlib.sha256(f"{int(seed)}/{tag}".encode()).digest()
    return int.from_bytes(digest[:4], "little")


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: tuple[int, ...]
    max_new: int


def _lengths(dist: dict, n: int) -> list[int]:
    nd = NormalDist()
    mu = math.log(dist["median"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(dist["max"],
                           max(dist["min"], round(math.exp(mu + dist["sigma"] * z))))))
    return out


def pool(mix: dict) -> list[tuple[int, int]]:
    """The mix's (prompt, output) pairs: the same for every seed."""
    n = int(mix["pool"])
    prompts = _lengths(mix["prompt"], n)
    outputs = _lengths(mix["output"], n)
    perm = np.random.default_rng(0).permutation(n)
    return [(prompts[i], outputs[int(perm[i])]) for i in range(n)]


def _order(pairs: list[tuple[int, int]], clients: int,
           rng: np.random.Generator) -> Iterator[tuple[int, int]]:
    by_size = sorted(pairs, key=lambda p: (p[0] + p[1], p))
    per = len(by_size) // clients
    strata = [by_size[s * per:(s + 1) * per] for s in range(clients)]
    while True:
        picks = [[st[int(i)] for i in rng.permutation(len(st))]
                 for st in strata]
        for b in range(per):
            block = [picks[s][b] for s in range(clients)]
            for j in rng.permutation(clients):
                yield block[int(j)]


def requests(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """The endless request stream of ``mix`` for ``seed``: the first
    ``clients`` requests open the clients' loops, each later one goes to
    whichever client finished."""
    clients = int(mix["clients"])
    if int(mix["pool"]) % clients:
        raise ValueError("pool must be a multiple of clients")
    order_rng = np.random.default_rng(derive(seed, "order"))
    tok_rng = np.random.default_rng(derive(seed, "tokens"))
    for p, o in _order(pool(mix), clients, order_rng):
        prompt = tok_rng.integers(2, vocab, size=p)
        yield Request(prompt=tuple(int(t) for t in prompt), max_new=int(o))
