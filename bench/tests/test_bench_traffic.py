"""Traffic generation: the same seed gives the same requests, another
seed other prompt tokens over the same sizes in the same order;
the first fill stands for a job in its steady state; the sources start
and stop the engine as they say."""
import json
import os
import sys

import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

from bench import traffic  # noqa: E402

SEED = 2**31 + 5          # seeds run past 32 signed bits


def _mix():
    with open(os.path.join(CHECKOUT, "bench", "traffic",
                           "decode_backlog.json")) as f:
        return json.load(f)


def _key(reqs):
    return [(r.max_new, r.first_fill, r.prompt.tobytes()) for r in reqs]


def test_same_seed_same_requests():
    tr = _mix()
    assert _key(traffic.generate(tr, SEED, 49152)) == \
        _key(traffic.generate(tr, SEED, 49152))


def test_other_seed_same_sizes_in_the_same_order_other_tokens():
    tr = _mix()
    a = traffic.generate(tr, SEED, 49152)
    b = traffic.generate(tr, SEED + 1, 49152)
    assert [(r.max_new, len(r.prompt), r.first_fill) for r in a] == \
        [(r.max_new, len(r.prompt), r.first_fill) for r in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert len(a) == tr["slots"] + tr["queued"]
    for r in a:
        assert len(r.prompt) + r.max_new <= tr["max_seq"]
        if not r.first_fill:
            assert tr["prompt"]["min"] <= len(r.prompt) <= \
                tr["prompt"]["max"]
            assert tr["output"]["min"] <= r.max_new <= tr["output"]["max"]


def test_every_queued_block_holds_the_same_sizes():
    tr = _mix()
    k, n = tr["block"], tr["slots"]
    a = traffic.generate(tr, SEED, 49152)[n:]
    b = traffic.generate(tr, SEED + 1, 49152)[n:]
    for i in range(0, len(a), k):
        assert sorted(r.max_new for r in a[i:i + k]) == \
            sorted(r.max_new for r in b[:k])
        assert sorted(len(r.prompt) for r in a[i:i + k]) == \
            sorted(len(r.prompt) for r in b[:k])
    # the blocks come in orders of their own, fixed apart from the seed
    assert [r.max_new for r in a[:k]] == [r.max_new for r in b[:k]]
    assert len({tuple(r.max_new for r in a[i:i + k])
                for i in range(0, len(a), k)}) > 1


def test_first_fill_is_part_way_through_its_outputs():
    tr = _mix()
    fill = traffic.first_fill(tr)
    assert len(fill) == tr["slots"]
    frac = sorted(f["done"] / f["budget"] for f in fill)
    n = len(fill)
    # progress spread evenly over (0, 1): one slot in each 1/n
    assert all(i / n <= x < (i + 1) / n for i, x in enumerate(frac))
    for f in fill:
        assert f["budget"] - f["done"] >= 1
        assert f["prompt"] + f["budget"] <= tr["max_seq"]
    # a slot is held by a long request more often than by a short one
    assert np.mean([f["budget"] for f in fill]) > \
        np.mean(traffic.quantiles(tr["output"], n))


def test_quantiles_follow_the_distribution():
    d = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
         "max": 10**6}
    q = traffic.quantiles(d, 1001)
    assert q[500] == 100                        # the median is the median
    assert list(q) == sorted(q)
    # 100 * exp(0.5 * z) at z = -+3.2905, the (0.5 / 1001)-quantiles
    assert (q[0], q[-1]) == (19, 518)
    # length-biased lognormal: median * exp(sigma^2)
    assert traffic.quantiles(d, 1, biased=True)[0] == \
        pytest.approx(100 * np.exp(0.25), rel=0.01)


def test_backlog_starts_at_the_first_tokens_and_stops_past_seconds():
    reqs = traffic.generate(_mix(), SEED, 1000)
    clock = {"t": 100.0, "first": None, "opened": 0}

    def opened():
        clock["opened"] += 1

    def stopping():
        # the wait for the work already sent: the close is read after it
        clock["t"] += 0.5
    src = traffic.Backlog(reqs, 5.0, lambda: clock["first"],
                          lambda: clock["t"], opened, stopping)
    assert src.due(0.0) == reqs                 # the whole queue at once
    assert src.due(0.1) == [] and src.start is None
    clock["first"] = 101.0
    assert src.due(0.2) == [] and src.start == 101.0
    clock["t"] = 105.9
    assert src.due(0.3) == [] and not src.exhausted
    clock["t"] = 106.0
    with pytest.raises(KeyboardInterrupt):
        src.due(0.4)
    assert src.stopped_at == 106.5 and clock["opened"] == 1


def test_script_feeds_a_group_per_boundary_then_stops():
    def req(n):
        return traffic.Request(-1, np.zeros(4, np.int32), n, False)
    groups = [[req(100), req(2)], [req(2)]]
    src = traffic.Script(groups)
    assert src.due(0.0) == groups[0]
    assert src.due(0.0) == groups[1]
    src.on_finish(0.0)
    assert src.due(0.0) == []                   # one short still running
    src.on_finish(0.0)
    with pytest.raises(KeyboardInterrupt):
        src.due(0.0)
