"""The serving cells' request generator: seeded, same work for every
seed, rate moves only the arrival times."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest  # noqa: E402

from chipbench import gen  # noqa: E402

MIX = {"rate": 5.0, "arrivals": "poisson", "shape_seed": 7,
       "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                  "min": 16, "max": 1024},
       "output": {"dist": "lognormal", "median": 192, "sigma": 0.6,
                  "min": 32, "max": 768}}
BIG_SEED = 2 ** 33 + 12345


@pytest.mark.parametrize("seed", [0, 1, BIG_SEED])
def test_same_seed_gives_byte_identical_requests(seed):
    a = gen.generate(MIX, seed, 20.0, 49152)
    b = gen.generate(MIX, seed, 20.0, 49152)
    assert a == b
    assert len(a) == gen.count_for(5.0, 20.0) == 100


def test_other_seed_draws_other_tokens_for_the_same_work():
    a = gen.generate(MIX, 1, 20.0, 49152)
    b = gen.generate(MIX, 2, 20.0, 49152)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert [(len(x.prompt), x.max_new_tokens, x.due_s) for x in a] == \
        [(len(x.prompt), x.max_new_tokens, x.due_s) for x in b]


def test_rate_changes_only_arrival_times():
    slow = gen.generate(MIX, 3, 20.0, 49152, rate=2.0)
    fast = gen.generate(MIX, 3, 8.0, 49152, rate=5.0)
    assert len(slow) == len(fast) == 40
    assert [(x.prompt, x.max_new_tokens) for x in slow] == \
        [(x.prompt, x.max_new_tokens) for x in fast]
    for s, f in zip(slow, fast):
        assert s.due_s == pytest.approx(f.due_s * 20.0 / 8.0)


def test_lengths_and_due_times_stay_in_bounds():
    items = gen.generate(MIX, 4, 30.0, 100)
    assert all(16 <= len(x.prompt) <= 1024 for x in items)
    assert all(32 <= x.max_new_tokens <= 768 for x in items)
    assert all(0 <= t < 100 for x in items for t in x.prompt)
    due = [x.due_s for x in items]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0


def test_replay_is_open_loop_and_counts_refused_submits():
    items = gen.generate(MIX, 5, 1.0, 100, rate=6.0)
    clock = [0.0]
    got = []

    def submit(req):
        if len(got) == 2:
            got.append(None)
            raise RuntimeError("shed")
        got.append(req)
        return req

    futs, late = gen.replay(submit, items, 0.0, lambda it: it.due_s,
                            clock=lambda: clock[0],
                            sleep=lambda d: clock.__setitem__(0, clock[0] + d))
    assert futs[2] is None and sum(f is None for f in futs) == 1
    assert len(futs) == len(late) == len(items)
    assert max(late) == pytest.approx(0.0, abs=1e-12)
