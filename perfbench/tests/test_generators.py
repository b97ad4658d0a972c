"""Generators: byte-identical for one seed, different for another, and the
same WORK for every seed (the same lengths and counts, in another order)."""

import numpy as np

from perfbench import common
from perfbench.generators import base, closed_loop, open_loop_lognormal, train_tokens

#: the committed mix, with the seed drawing the order inside every 3 arrivals
#: (the committed file's own group is 1: its seed draws jitter and ids only)
TRAFFIC = dict(common.read_json(common.os.path.join(common.HERE, "traffic", "chat-steady.json")),
               shuffle_group=3)
BIG = 3_000_000_019  # the driver's seeds pass 2**31


def _open(seed):
    return open_loop_lognormal.make(TRAFFIC, seed, 20.0, 32768).initial()


def test_open_loop_is_reproducible_and_seeded():
    assert base.digest(_open(BIG)) == base.digest(_open(BIG))
    assert base.digest(_open(BIG)) != base.digest(_open(BIG + 1))


def test_open_loop_offers_every_seed_the_same_work_in_an_order_the_seed_draws():
    a, b = _open(1), _open(BIG)
    group = TRAFFIC["shuffle_group"]
    pa, pb = [len(r.prompt) for r in a], [len(r.prompt) for r in b]
    oa, ob = [r.max_new_tokens for r in a], [r.max_new_tokens for r in b]
    assert pa != pb and oa != ob  # the seed draws the order ...
    for phase in ("ramp", "window", "drain"):
        ia = [i for i, r in enumerate(a) if r.phase == phase]
        assert ia == [i for i, r in enumerate(b) if r.phase == phase] and ia
        assert sorted(pa[i] for i in ia) == sorted(pb[i] for i in ia)  # ... never the work
        assert sorted(oa[i] for i in ia) == sorted(ob[i] for i in ia)
        # ... and moves no length further than its group of consecutive arrivals
        lo = ia[0]
        for seq_a, seq_b in ((pa, pb), (oa, ob)):
            unique = [i for i in ia if seq_a.count(seq_a[i]) == 1]
            assert unique and all(abs(seq_b.index(seq_a[i]) - i) < 2 * group for i in unique)
        assert all(lo <= i < lo + len(ia) for i in ia)
    moved = [abs(x.due_s - y.due_s) for x, y in zip(a, b)]
    assert 0 < max(moved) <= 2 * TRAFFIC["jitter_s"]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    window = [r for r in a if r.phase == "window"]
    assert len(window) == round(TRAFFIC["rate_rps"] * 20.0)
    lo, hi = TRAFFIC["ramp_s"], TRAFFIC["ramp_s"] + 20.0
    assert all(lo <= r.due_s < hi for r in window)
    spec = TRAFFIC["prompt_tokens"]
    assert all(spec["min"] <= len(r.prompt) <= spec["max"] for r in a)
    assert all(r.prompt.max() < 32768 and r.prompt.min() >= 0 for r in a)


def test_every_block_of_arrivals_holds_the_same_mix():
    vals = np.arange(70)
    dealt = base.deal(vals, 10, np.random.default_rng(0))
    assert sorted(dealt) == list(vals)
    for block in dealt.reshape(7, 10):  # one value of every tenth in each block of 10
        assert sorted(v // 7 for v in block) == list(range(10))
    assert len(base.deal(vals[:0], 10, np.random.default_rng(0))) == 0
    whole = base.shuffle_groups(12, 12, np.random.default_rng(3))
    assert sorted(whole) == list(range(12)) and list(whole) != list(range(12))
    assert list(base.shuffle_groups(12, 1, np.random.default_rng(3))) == list(range(12))


def test_a_group_of_one_leaves_the_seed_the_jitter_and_the_ids_only():
    one = dict(TRAFFIC, shuffle_group=1)
    a, b = (open_loop_lognormal.make(one, s, 20.0, 32768).initial() for s in (1, BIG))
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    moved = [abs(x.due_s - y.due_s) for x, y in zip(a, b)]
    assert 0 < max(moved) <= 2 * one["jitter_s"]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_length_sets_follow_the_stated_distribution():
    s = base.length_set({"dist": "lognormal", "median": 512, "sigma": 1.0, "min": 32, "max": 3072}, 1001)
    assert s[500] == 512 and s.min() == 32 and s.max() == 3072
    u = base.length_set({"dist": "uniform", "min": 10, "max": 20}, 11)
    assert u.min() >= 10 and u.max() <= 20 and len(set(u)) > 5


def test_closed_loop_sends_the_next_when_one_ends():
    traffic = {"clients": 3, "ramp_s": 1.0, "drain_s": 0.0,
               "prompt_tokens": {"dist": "uniform", "min": 8, "max": 16},
               "output_tokens": {"dist": "fixed", "value": 4}}
    load = closed_loop.make(traffic, 5, 2.0, 256)
    first = load.initial()
    assert sorted(r.client for r in first) == [0, 1, 2]
    nxt = load.on_complete(first[0], 1.5)
    assert len(nxt) == 1 and nxt[0].client == first[0].client
    assert nxt[0].due_s == 1.5 and nxt[0].phase == "window"
    again = closed_loop.make(traffic, 5, 2.0, 256)
    assert base.digest(again.initial()) == base.digest(closed_loop.make(traffic, 5, 2.0, 256).initial())
    assert base.digest(again.initial()) != base.digest(closed_loop.make(traffic, 6, 2.0, 256).initial())


def test_train_tokens_rows_all_differ():
    traffic = {"seq_len": 32, "global_batch": 4, "batches": 3}
    a = train_tokens.make(traffic, BIG, 1.0, 1000)
    b = train_tokens.make(traffic, BIG, 1.0, 1000)
    c = train_tokens.make(traffic, BIG + 1, 1.0, 1000)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    rows = {r.tobytes() for batch in a for r in batch}
    assert len(rows) == 12 and a[0].shape == (4, 32) and a[0].dtype == np.int32
