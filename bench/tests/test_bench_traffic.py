"""The traffic generator replays a seed bit for bit, and every seed gets
the same work in another order."""
import itertools
from collections import Counter

import numpy as np
import pytest

from lib.traffic import CLOSED_BLOCK, Traffic, TrafficError, stratified

OPEN = {"loop": "open", "rate_rps": 4.0, "queue_limit": 64,
        "prompt_lens": {"32": 0.1, "64": 0.15, "128": 0.25, "256": 0.25, "512": 0.15, "1024": 0.1},
        "gen_lens": {"16": 0.15, "32": 0.25, "64": 0.3, "128": 0.2, "256": 0.1}}
CLOSED = {"loop": "closed", "clients": 8, "queue_limit": 64,
          "prompt_lens": {"32": 0.3, "64": 0.3, "128": 0.25, "256": 0.15},
          "gen_lens": {"64": 0.4, "128": 0.4, "256": 0.2}}
BIG_SEED = 2**31 + 987654321


def _key(reqs):
    return [(r.id, r.prompt_len, r.gen_len, r.token_seed, r.due_s) for r in reqs]


def test_open_loop_replays_bit_for_bit():
    t = Traffic.from_dict(OPEN)
    a, b = t.open_requests(BIG_SEED, 30.0), t.open_requests(BIG_SEED, 30.0)
    assert _key(a) == _key(b)
    assert np.array_equal(a[3].prompt_tokens(151936), b[3].prompt_tokens(151936))
    assert _key(a) != _key(t.open_requests(BIG_SEED + 1, 30.0))


def test_closed_loop_replays_bit_for_bit():
    t = Traffic.from_dict(CLOSED)
    a = list(itertools.islice(t.closed_stream(BIG_SEED), 250))
    b = list(itertools.islice(t.closed_stream(BIG_SEED), 250))
    assert _key(a) == _key(b)
    assert [r.id for r in a] == list(range(250))


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_open_loop_work_is_the_same_on_every_seed(seed):
    t = Traffic.from_dict(OPEN)
    reqs = t.open_requests(seed, 30.0)
    ref = t.open_requests(1, 30.0)
    assert len(reqs) == 120
    assert Counter(r.prompt_len for r in reqs) == Counter(r.prompt_len for r in ref)
    assert Counter(r.gen_len for r in reqs) == Counter(r.gen_len for r in ref)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
    gaps = np.diff(due + [30.0])
    assert np.isclose(gaps.sum(), 30.0)


def test_closed_blocks_follow_the_mix_exactly():
    t = Traffic.from_dict(CLOSED)
    block = list(itertools.islice(t.closed_stream(3), CLOSED_BLOCK))
    assert Counter(r.prompt_len for r in block) == {32: 30, 64: 30, 128: 25, 256: 15}
    assert Counter(r.gen_len for r in block) == {64: 40, 128: 40, 256: 20}


def test_stratified_rounds_by_largest_remainder():
    rng = np.random.default_rng(0)
    out = stratified({1: 0.5, 2: 0.3, 3: 0.2}, 7, rng)
    assert len(out) == 7 and Counter(out.tolist()) == {1: 4, 2: 2, 3: 1}


def test_open_loop_gaps_are_one_multiset_at_the_rate():
    t = Traffic.from_dict(OPEN)
    gaps = [np.sort(np.diff([r.due_s for r in t.open_requests(s, 30.0)] + [30.0]))
            for s in (3, BIG_SEED)]
    assert np.allclose(gaps[0], gaps[1])
    assert 0.9 / 4.0 < gaps[0].mean() < 1.1 / 4.0


@pytest.mark.parametrize("bad", [dict(OPEN, rate_rps=0), dict(CLOSED, clients=0),
                                 dict(OPEN, loop="sideways"), dict(OPEN, extra=1),
                                 dict(CLOSED, bursts={"period_s": 1, "length_s": 1, "share": 1})])
def test_bad_traffic_files_are_refused(bad):
    with pytest.raises(TrafficError):
        Traffic.from_dict(bad)
