"""The benchmark's reference map against a brute-force dict."""

import numpy as np
import pytest

from reference import Reference


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_matches_dict(seed):
    rng = np.random.default_rng(seed)
    space = np.arange(0, 4000, 3, dtype=np.uint64) * np.uint64(2**50)  # some near 2^63+
    keys = np.sort(rng.choice(space, 300, replace=False))
    vals = rng.integers(0, 2**64, keys.size, dtype=np.uint64)
    ref = Reference(keys, vals)
    d = dict(zip(keys.tolist(), vals.tolist()))
    for _ in range(6):
        # a write batch with repeated keys: the last write wins
        k = rng.choice(space, 80)
        v = rng.integers(0, 2**64, k.size, dtype=np.uint64)
        ref.put(k, v)
        for a, b in zip(k.tolist(), v.tolist()):
            d[a] = b
        q = rng.choice(space, 200)
        got_v, got_f = ref.get(q)
        assert got_f.tolist() == [x in d for x in q.tolist()]
        assert got_v.tolist() == [d.get(x, 0) for x in q.tolist()]
        limit = int(rng.integers(1, 40))
        rk, rv, rc = ref.range(q, limit)
        ordered = sorted(d)
        for i, start in enumerate(q.tolist()):
            want = [x for x in ordered if x >= start][:limit]
            assert rc[i] == len(want)
            assert rk[i, : len(want)].tolist() == want
            assert rv[i, : len(want)].tolist() == [d[x] for x in want]
            assert not rk[i, len(want):].any() and not rv[i, len(want):].any()
