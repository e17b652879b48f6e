"""The traffic generator: seeds, sizes and arrival statistics."""
import json
from pathlib import Path

import numpy as np

from harness import traffic as T

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
DATA = Path(__file__).resolve().parent / "data"


def load(name):
    """A cell's traffic file, or one kept for the tests (``code_sat``:
    Poisson arrivals, where ``chat_bursty``'s are gamma with CV 2)."""
    path = TRAFFIC / f"{name}.json"
    if not path.exists():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


def arrays(arr):
    return (np.array([a.t for a in arr]),
            np.array([len(a.prompt) for a in arr]),
            np.array([a.max_tokens for a in arr]))


def test_same_seed_same_schedule():
    tr = load("code_sat")
    a, b = T.schedule(tr, 2**31 + 7, 60.0, 49152), \
        T.schedule(tr, 2**31 + 7, 60.0, 49152)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.t == y.t and x.max_tokens == y.max_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_other_seed_same_schedule_other_prompts():
    tr = load("chat_bursty")
    a = T.schedule(tr, 11, 60.0, 32768)
    b = T.schedule(tr, 2**33 + 5, 60.0, 32768)
    assert len(a) == len(b) > 0
    assert [(x.t, x.max_tokens, len(x.prompt)) for x in a] == \
        [(x.t, x.max_tokens, len(x.prompt)) for x in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    # gaps and lengths come from the seed-independent pool
    pool_g, pool_p, pool_o = T.size_pool(tr, 60.0)
    t, p, o = arrays(a)
    assert set(np.round(np.diff(np.concatenate([[0], t])), 9)) \
        <= set(np.round(pool_g, 9))
    assert set(p) <= set(pool_p) and set(o) <= set(pool_o)
    assert t[-1] < 60.0 and np.all(np.diff(t) >= 0)


def test_lengths_and_cv_within_tolerance():
    for name, cv in (("code_sat", 1.0), ("chat_bursty", 2.0)):
        tr = load(name)
        g, p, o = T.size_pool(tr, 20000.0 / tr["arrivals"]["rate_rps"])
        assert abs(np.mean(g) * tr["arrivals"]["rate_rps"] - 1) < 0.05
        assert abs(np.std(g) / np.mean(g) - cv) < 0.1 * cv
        for lens, spec in ((p, tr["prompt_tokens"]),
                           (o, tr["output_tokens"])):
            assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
            assert abs(np.median(lens) / spec["median"] - 1) < 0.05


def test_every_request_fits_the_engine():
    for name in ("code_sat", "chat_bursty"):
        tr = load(name)
        longest = tr["prompt_tokens"]["max"] + tr["output_tokens"]["max"]
        assert longest <= tr["engine"]["max_seq"]


def test_every_block_holds_about_the_same_work():
    tr = load("code_sat")
    k = tr["strata"]
    for seed in (3, 2**31 + 99):
        _, p, o = arrays(T.schedule(tr, seed, 120.0, 49152))
        n = len(p) // k * k
        for lens, spec in ((p, tr["prompt_tokens"]), (o, tr["output_tokens"])):
            blocks = lens[:n].reshape(-1, k).sum(axis=1)
            # one value from each of k quantile bands per block: the block
            # sums lie within a band's width of each other
            assert blocks.std() / blocks.mean() < 0.1
    g = np.random.default_rng(0).lognormal(0, 1, 1000)
    s = T.stratified(g, 16, np.random.default_rng(1))
    assert sorted(s) == sorted(g)
