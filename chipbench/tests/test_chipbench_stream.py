"""The traffic generator: the same work for every seed, in another order."""

import json
from collections import Counter

from chipbench import stream
from chipbench.tests.helpers import HARNESS_DIR

MIX = json.loads((HARNESS_DIR / "traffic" / "mixedp.json").read_text())


def _ops(seed, n=800, traffic=MIX):
    s = stream.Stream(traffic, seed, pool=1024, insert_pool=1000)
    return [s.next() for _ in range(n)]


def test_same_seed_same_ops():
    a, b = _ops(7), _ops(7)
    assert [(o.kind, o.index, o.p) for o in a] == \
        [(o.kind, o.index, o.p) for o in b]


def test_every_seed_gets_the_same_p_mix():
    for seed in (1, 2, 2**31 + 3, 2**40 + 9):
        ops = _ops(seed)
        assert Counter(o.p for o in ops) == Counter(
            {p: 100 for p in MIX["p"]})
    assert [o.p for o in _ops(1)] != [o.p for o in _ops(2)]


def test_insert_share_is_exact_per_deck():
    mix = dict(MIX, insert_share=0.1)
    ops = _ops(5, n=1000, traffic=mix)
    assert sum(o.kind == stream.INSERT for o in ops) == 100


def test_arrivals_are_the_same_set_in_another_order():
    mix = dict(MIX, rate_qps=400.0, burst=12, arrivals="poisson")
    a = stream.arrival_offsets(mix, 10.0, 1)
    b = stream.arrival_offsets(mix, 10.0, 2)
    assert len(a) == len(b) and len(a) % 12 == 0
    assert abs(len(a) / 10.0 - 400.0) < 20.0
    assert (a[::12] != b[::12]).any()
    assert (a[12:] - a[:-12] >= 0).all()


def test_lanes_follow_the_cutoff():
    assert stream.lp_lanes(MIX["p"], 1.4) == [0.5, 1.5]
    assert stream.lp_lanes([1.0, 2.0], 1.4) == [1.0, 2.0]
