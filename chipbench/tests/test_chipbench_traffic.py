"""The closed-loop traffic generator: deterministic per seed, lengths
inside their clips, the same sizes for every seed in an order the seed
draws."""

import json
from collections import Counter

import pytest

from chipbench.bench import HERE
from chipbench.traffic import derive, pool, requests

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def _take(mix, seed, n, vocab=1000):
    it = requests(mix, seed, vocab)
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    assert _take(mix, 2**31 + 17, 40) == _take(mix, 2**31 + 17, 40)
    a, b = _take(mix, 2**31 + 17, 40), _take(mix, 2**31 + 18, 40)
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clips(name):
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    for r in _take(mix, 5, 2 * mix["pool"]):
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert all(2 <= t < 1000 for t in r.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    """Every seed serves the same sizes: each pass over the pool, the
    clients' first requests included, holds the pool's sizes once; the
    seed draws their order."""
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    n = mix["pool"]
    want = Counter(pool(mix))
    orders = []
    for seed in (1, 2**31 + 5, 2**40 + 5):
        got = _take(mix, seed, 2 * n)
        for rnd in (got[:n], got[n:]):
            assert Counter((len(r.prompt), r.max_new) for r in rnd) == want
        orders.append([(len(r.prompt), r.max_new) for r in got])
    assert orders[0] != orders[1] != orders[2]


def test_blocks_take_one_request_from_each_stratum():
    mix = dict(json.loads((HERE / "traffic" / "chat.json").read_text()),
               pool=64)
    c = mix["clients"]
    by_size = sorted(pool(mix), key=lambda p: (p[0] + p[1], p))
    per = len(by_size) // c
    stratum = {}
    for s in range(c):
        for p in by_size[s * per:(s + 1) * per]:
            stratum.setdefault(p, set()).add(s)
    got = _take(mix, 9, 3 * c)[c:3 * c]   # the second and third blocks
    for block in (got[:c], got[c:]):
        seen = [stratum[(len(r.prompt), r.max_new)] for r in block]
        # a size that sits in one stratum only pins that stratum
        pinned = [next(iter(s)) for s in seen if len(s) == 1]
        assert len(pinned) == len(set(pinned))


@pytest.mark.parametrize("name", MIXES)
def test_pool_matches_the_published_medians(name):
    """The pool's median lengths are the mix's medians (inside the
    clips), and its sizes reach neither clip by more than a pool's
    quantile step."""
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    p = pool(mix)
    for i, key in enumerate(("prompt", "output")):
        lengths = sorted(x[i] for x in p)
        mid = (lengths[len(p) // 2 - 1] + lengths[len(p) // 2]) / 2
        assert abs(mid - mix[key]["median"]) <= 0.1 * mix[key]["median"]
    assert len(set(p)) == len(p)


def test_derive_separates_large_seeds():
    assert derive(7, "weights") != derive(2**33 + 7, "weights")
    assert derive(7, "weights") != derive(7, "order")
    assert 0 <= derive(2**40, "x") < 2**32
