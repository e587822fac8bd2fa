"""The request generator and the serving statistics."""

import math

import numpy as np
import pytest

from harness import serve

MIX = {"prompt": {"median": 256, "sigma": 0.8, "min": 16, "max": 1024},
       "output": {"median": 128, "sigma": 0.6, "min": 16, "max": 256}}


def test_lognormal_strata():
    x = serve.lognormal_lengths(MIX["prompt"], 1001)
    assert x.min() >= 16 and x.max() <= 1024
    assert np.median(x) == 256
    assert np.all(np.diff(x) >= 0)


def test_every_seed_offers_the_same_work():
    a = serve.make_requests(MIX, 1, 200, 1000, rate=2.0)
    b = serve.make_requests(MIX, 2 ** 31 + 5, 200, 1000, rate=2.0)
    for key in (lambda r: r.prompt.size, lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    gaps = lambda rs: np.sort(np.diff([0.0] + [r.due for r in rs]))
    assert gaps(a) == pytest.approx(gaps(b), rel=1e-9)
    assert 0.5 * 200 / 2.0 < a[-1].due < 1.5 * 200 / 2.0


def test_open_loop_window_offers_the_same_count_for_every_seed():
    from harness import core
    mix = core.load_json(core.BENCH / "traffic" / "chat.json")
    n = core.kind_driver("open_loop").requests_for(mix, 51.0)
    assert n == round(mix["rate"] * 51.0)
    for seed in (1, 7, 2 ** 31 + 5):
        reqs = serve.make_requests(mix, seed, n, 1000, rate=mix["rate"])
        assert sum(r.due < 51.0 for r in reqs) == n


def test_p95_counts_missing_answers():
    assert serve.p95(list(range(101))) == pytest.approx(95.0)
    assert serve.p95([1.0] * 99 + [math.inf]) == pytest.approx(1.0)
    assert math.isinf(serve.p95([1.0] * 90 + [math.inf] * 10))
    assert math.isinf(serve.p95([]))
