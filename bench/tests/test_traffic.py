"""The generator gives every seed the same work in another order."""

import json
import os

import numpy as np
import pytest

import traffic

MIXES = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


# shapes no committed cell uses yet, written as data the generator reads
SHARED = {
    "arrivals": {"kind": "poisson", "rate_per_s": 0.8},
    "shared_prefix": {"prompts": 8, "tokens": 2048, "zipf_s": 1.1},
    "prompt_tokens": {"median": 160, "sigma": 0.5, "min": 64, "max": 384},
    "output_tokens": {"median": 96, "sigma": 0.6, "min": 32, "max": 256},
}
BURSTY = {
    "arrivals": {"kind": "phases", "cycle": [
        {"seconds": 5, "rate_per_s": 1.6}, {"seconds": 5, "rate_per_s": 0}]},
    "prompt_tokens": {"median": 1020, "sigma": 0.8, "min": 32, "max": 3072},
    "output_tokens": {"median": 129, "sigma": 0.8, "min": 16, "max": 768},
}
MIXED = {
    "arrivals": {"kind": "poisson", "rate_per_s": 0.8},
    "classes": [
        {"share": 0.25,
         "prompt_tokens": {"median": 3000, "sigma": 0.1, "min": 2560,
                           "max": 3584},
         "output_tokens": {"median": 128, "sigma": 0.5, "min": 32,
                           "max": 256}},
        {"share": 0.75,
         "prompt_tokens": {"median": 128, "sigma": 0.5, "min": 16,
                           "max": 256},
         "output_tokens": {"median": 128, "sigma": 0.5, "min": 32,
                           "max": 256},
         "shared_prefix": {"prompts": 2, "tokens": 64, "zipf_s": 1.0}},
    ],
}
INLINE = {"shared": SHARED, "bursty": BURSTY, "mixed": MIXED}


def _mix(name):
    if name in INLINE:
        return INLINE[name]
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "longdoc-batch", "shared",
                                  "bursty", "mixed"])
def test_seeds_share_sizes_and_gaps(name):
    mix = _mix(name)
    a = traffic.generate(mix, 7, 45, 64000)
    b = traffic.generate(mix, 2 ** 40 + 3, 45, 64000)
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens,
                lambda p: p.prefix_id):
        assert sorted(map(key, a)) == sorted(map(key, b))
    gaps = [np.sort(np.diff([p.due_s for p in s])) for s in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert all(p.prompt.dtype == np.int32 and p.prompt.max() < 64000
               for p in a)


def test_lengths_stay_in_their_clips():
    mix = _mix("chat")
    plan = traffic.generate(mix, 1, 45, 64000)
    lens = [len(p.prompt) for p in plan]
    outs = [p.max_new_tokens for p in plan]
    assert min(lens) >= 32 and max(lens) <= 3072
    assert min(outs) >= 16 and max(outs) <= 768
    assert abs(np.median(lens) - 1020) < 64
    assert traffic.max_context(mix) == 3072 + 768


def test_poisson_count_and_backlog():
    chat = _mix("chat")
    plan = traffic.generate(chat, 3, 40, 100)
    assert len(plan) == round(chat["arrivals"]["rate_per_s"] * 40)
    assert plan[0].due_s == 0.0
    assert all(b.due_s >= a.due_s for a, b in zip(plan, plan[1:]))
    backlog = traffic.generate(_mix("longdoc-batch"), 3, 40, 100)
    assert all(p.due_s == 0.0 for p in backlog)


def test_shared_prefixes_follow_zipf():
    plan = traffic.generate(SHARED, 5, 45, 64000)
    counts = np.bincount([p.prefix_id for p in plan], minlength=8)
    assert counts.sum() == len(plan) and (np.diff(counts) <= 0).all()
    first = {}
    for p in plan:
        assert p.prefix_len == 2048
        head = p.prompt[:2048]
        assert np.array_equal(first.setdefault(p.prefix_id, head), head)
    assert traffic.zipf_counts(3, 1.0, 11).tolist() == [6, 3, 2]


def test_phases_make_bursts():
    """On for 5 s at 1.6 req/s, off for 5 s: 8 requests in each on phase,
    none in the off phases, the last on phase cut at the close."""
    plan = traffic.generate(BURSTY, 9, 47, 64000)
    due = np.array([p.due_s for p in plan])
    assert len(plan) == traffic.request_count(BURSTY, 47) == 5 * 8
    phase = (due // 5).astype(int)
    assert set(phase) == {0, 2, 4, 6, 8}
    assert all((phase == k).sum() == 8 for k in (0, 2, 4, 6, 8))
    assert (np.diff(due) >= 0).all() and due[0] == 0.0
    # a phase cut by the close holds the requests of its part only
    assert traffic.request_count(BURSTY, 42.5) == 4 * 8 + 4


def test_classes_keep_their_shares():
    plan = traffic.generate(MIXED, 4, 50, 64000)
    n = len(plan)
    long_ = [p for p in plan if len(p.prompt) >= 2560]
    short = [p for p in plan if len(p.prompt) < 2560]
    assert len(long_) == traffic.share_counts([0.25, 0.75], n)[0]
    assert all(p.prefix_id == -1 and p.prefix_len == 0 for p in long_)
    assert all(p.prefix_id in (0, 1) and p.prefix_len == 64
               and 80 <= len(p.prompt) <= 320 for p in short)
    assert traffic.max_context(MIXED) == 3584 + 256
    assert traffic.min_prompt(MIXED) == 64 + 16
