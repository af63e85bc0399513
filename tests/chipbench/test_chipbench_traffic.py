"""The traffic generator: deterministic from the seed, inside its clipped
ranges, and the same multiset of sizes and gaps for every seed."""
import json

import numpy as np
from chipbench_tiny import ROOT

from chipbench import traffic


def _mix(name, **arrivals):
    m = json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json")
                   .read_text())
    if arrivals:
        m["arrivals"] = arrivals
    return m


def _sig(reqs):
    return [(r.arrival, r.max_new, r.prompt.tolist()) for r in reqs]


def test_backlog_is_deterministic_and_clipped():
    mix = _mix("batch-decode")
    a = traffic.make_requests(mix, 2**31 + 11, 151936, 51.0)
    b = traffic.make_requests(mix, 2**31 + 11, 151936, 51.0)
    assert _sig(a) == _sig(b)
    assert len(a) == mix["arrivals"]["requests"]
    assert all(r.arrival == 0.0 for r in a)
    p, o = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)
    assert all(o["min"] <= r.max_new <= o["max"] for r in a)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 151936 for r in a)


def test_backlog_blocks_hold_one_multiset_for_every_seed():
    mix = _mix("batch-decode")
    block = mix["arrivals"]["block"]
    x = traffic.make_requests(mix, 5, 1000, 51.0)
    y = traffic.make_requests(mix, 6, 1000, 51.0)
    assert _sig(x) != _sig(y)
    for i in range(0, len(x), block):
        for field in (lambda r: len(r.prompt), lambda r: r.max_new):
            assert sorted(map(field, x[i:i + block])) == \
                sorted(map(field, y[i:i + block]))


def test_poisson_arrivals_lie_in_the_window_at_the_rate():
    mix = _mix("batch-decode", kind="poisson", rate_per_s=2.0)
    a = traffic.make_requests(mix, 3, 1000, 51.0)
    b = traffic.make_requests(mix, 4, 1000, 51.0)
    arr = np.array([0.0] + [r.arrival for r in a])
    assert np.all(np.diff(arr) > 0) and arr[-1] < 51.0
    assert len(a) == 102
    assert sorted(np.diff(arr).round(9)) == sorted(
        np.diff([0.0] + [r.arrival for r in b]).round(9))
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    p = mix["prompt"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)


def test_lognormal_sizes_follow_the_median():
    s = traffic.lognormal_sizes(101, {"median": 96, "sigma": 0.5,
                                      "min": 32, "max": 256})
    assert s[50] == 96 and list(s) == sorted(s)


def test_drawn_normals_stay_finite_at_the_extremes():
    import jax.numpy as jnp

    from chipbench import weights

    u = weights._uniform(jnp.array([0, 2**32 - 1], jnp.uint32))
    assert 0.0 < float(u[0]) and float(u[1]) < 1.0
    z = np.asarray(weights.jax.scipy.special.ndtri(u))
    assert np.all(np.isfinite(z)) and abs(z).max() < 6.0
