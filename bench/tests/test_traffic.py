"""Every traffic mix is a function of its seed, and every seed gets the
same lengths in the same order."""
from collections import Counter

import numpy as np
import pytest

from tests_common import DATA
from traffic import BENCH, generate, load_traffic, lognormal_grid

MIXES = ([(p.stem, BENCH) for p in sorted((BENCH / "traffic").glob("*.json"))]
         + [(p.stem, DATA) for p in sorted((DATA / "traffic").glob("*.json"))])
BIG_SEED = 2 ** 40 + 12345


def _key(reqs):
    return [(r.max_new, r.prompt.tobytes()) for r in reqs]


@pytest.mark.parametrize("name,base", MIXES, ids=[m[0] for m in MIXES])
def test_same_seed_same_requests(name, base):
    mix = load_traffic(name, base)
    a = generate(mix, 1000, BIG_SEED)
    b = generate(mix, 1000, BIG_SEED)
    assert _key(a) == _key(b)
    c = generate(mix, 1000, BIG_SEED + 1)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("name,base", MIXES, ids=[m[0] for m in MIXES])
def test_seeds_share_sizes(name, base):
    mix = load_traffic(name, base)
    a = generate(mix, 1000, 1)
    b = generate(mix, 1000, 2 ** 35)
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    block = mix["block"]
    want_p = Counter(lognormal_grid(mix["prompt"], block).tolist())
    want_o = Counter(lognormal_grid(mix["output"], block).tolist())
    for lo in range(0, len(a) - block + 1, block):
        assert Counter(len(r.prompt) for r in a[lo:lo + block]) == want_p
        assert Counter(r.max_new for r in a[lo:lo + block]) == want_o
    for r in a:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 1000
