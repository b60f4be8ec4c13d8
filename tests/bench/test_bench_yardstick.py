"""The benchmark's own generator and reference."""
import itertools
import random

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repo on sys.path)

from bench import traffic, ycsb
from bench.reference import SortedSet


def test_reference_is_a_sorted_set():
    rnd = random.Random(5)
    ref, model = SortedSet([3, 1, 2]), {1, 2, 3}
    for _ in range(3000):
        kind = rnd.choice([ycsb.OP_FIND, ycsb.OP_INSERT, ycsb.OP_REMOVE])
        key = rnd.randint(0, 40)
        if kind == ycsb.OP_FIND:
            exp = key in model
        elif kind == ycsb.OP_INSERT:
            exp = key not in model
            model.add(key)
        else:
            exp = key in model
            model.discard(key)
        assert ref.apply(kind, key) == exp
    assert ref.keys() == sorted(model)


def _mix(theta):
    return {"loop": "closed", "clients": 8, "read_frac": 0.5,
            "theta": theta, "scrambled": theta > 0, "warmup_rounds": 1}


@pytest.mark.parametrize("theta", [0.0, 0.99])
def test_stream_is_a_function_of_the_seed(theta):
    seed = 2**31 + 123
    a = list(itertools.islice(traffic.op_stream(_mix(theta), 800, seed),
                              40000))
    b = list(itertools.islice(traffic.op_stream(_mix(theta), 800, seed),
                              40000))
    c = list(itertools.islice(traffic.op_stream(_mix(theta), 800, seed + 1),
                              40000))
    assert a == b and a != c
    # every seed does the same work: the same kinds, on other keys
    assert [k for k, _ in a] == [k for k, _ in c]
    kinds = np.array([k for k, _ in a])
    assert abs(np.mean(kinds == ycsb.OP_FIND) - 0.5) < 0.02
    assert abs(np.mean(kinds == ycsb.OP_INSERT) - 0.25) < 0.02
    keys = np.array([x for _, x in a])
    assert keys.min() >= 1 and keys.max() <= 800


def test_zipfian_hot_key_share_matches_ycsb():
    """Rank 1 of the bounded Zipfian carries 1/ζ(n, θ) of the draws."""
    n, theta = 65536, 0.99
    keys = ycsb.zipf_keys(np.random.default_rng(1), 200000, n, theta,
                          scrambled=True)
    share = np.bincount(keys).max() / len(keys)
    assert share == pytest.approx(1 / ycsb._zeta(n, theta), rel=0.05)


def test_load_keys_are_distinct():
    keys = ycsb.load_phase(np.random.default_rng([2**31 + 9, 1]), 500, 800)
    assert len(set(keys.tolist())) == 500
    assert keys.min() >= 1 and keys.max() <= 800
