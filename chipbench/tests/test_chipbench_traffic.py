"""Seeded traffic: same seed, same inputs; other seeds, the same sizes
and arrivals in another order."""
from collections import Counter

import numpy as np
import pytest

from chipbench import harness
from chipbench.generators import closed_loop, lengths, open_loop

CHAT = harness._json(harness.PKG / "traffic" / "chat.json")
BATCH = harness._json(harness.PKG / "traffic" / "decode-batch.json")
BIG = 2**31 + 987_654_321          # seeds may pass 32 bits


def _chat(rate=2.0):
    return dict(CHAT, rate_per_s=rate)


def test_open_loop_same_seed_same_schedule():
    a = open_loop.plan(_chat(), BIG, 51, 50304)
    b = open_loop.plan(_chat(), BIG, 51, 50304)
    assert a == b
    assert len(a) == round(2.0 * 51)


def test_open_loop_other_seed_same_work_other_order():
    a = open_loop.plan(_chat(), BIG, 51, 50304)
    b = open_loop.plan(_chat(), BIG + 1, 51, 50304)
    assert [x[0] for x in a] != [x[0] for x in b]
    assert [len(x[1]) for x in a] != [len(x[1]) for x in b]
    assert Counter(len(x[1]) for x in a) == Counter(len(x[1]) for x in b)
    assert Counter(x[2] for x in a) == Counter(x[2] for x in b)
    gaps = lambda p: sorted(np.round(np.diff([x[0] for x in p]), 9))
    assert len(gaps(a)) == len(gaps(b))
    assert a[0][1] != b[0][1]          # token ids differ too


def test_open_loop_arrivals_span_the_window():
    sched = open_loop.plan(_chat(), 7, 51, 50304)
    times = [x[0] for x in sched]
    assert times[0] == 0.0 and times == sorted(times) and times[-1] < 51


@pytest.mark.parametrize("spec", [CHAT["prompt_len"], BATCH["prompt_len"]])
def test_drawn_prompt_lengths_lie_on_the_warmed_grid(spec):
    grid = set(lengths.support(spec))
    drawn = lengths.draw(spec, 500, np.random.default_rng(3))
    assert set(drawn) <= grid
    assert all(v % spec["grid"] == 0 for v in grid)


def test_lognormal_quantiles_follow_the_spec():
    spec = CHAT["output_len"]
    vals = lengths.draw(spec, 2001, np.random.default_rng(0))
    assert sorted(vals)[1000] == 250           # median
    assert min(vals) >= spec["min"] and max(vals) <= spec["max"]


def test_closed_loop_pool_same_seed_same_requests():
    a = closed_loop.Pool(BATCH, BIG, 50304, 16)
    b = closed_loop.Pool(BATCH, BIG, 50304, 16)
    ra = [a.next(0.0) for _ in range(40)]
    rb = [b.next(0.0) for _ in range(40)]
    assert [(r.prompt, r.max_new) for r in ra] == \
        [(r.prompt, r.max_new) for r in rb]
    c = closed_loop.Pool(BATCH, BIG + 1, 50304, 16)
    assert c.outs != a.outs and sorted(c.outs) == sorted(a.outs)
    assert sorted(c.plens) == sorted(a.plens)


def test_closed_loop_first_wave_is_under_way():
    pool = closed_loop.Pool(BATCH, 5, 50304, 16)
    first = pool.outs[:16]
    assert min(first) < BATCH["output_len"]["min"]
    assert all(o >= 1 for o in first)
