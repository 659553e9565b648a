"""Speculative decoding: parity-first proofs for the draft/verify/accept
machinery (serving/spec_decode.py + the engine's _spec_decode_step).

The load-bearing property is *greedy parity*: every token the spec path
commits is a target-model verify argmax, so generated sequences must be
bitwise-identical to plain greedy decode — speculation may only change
how many tokens commit per step.  The tests prove that on a Poisson
arrival trace (for a GQA model, and for an absorbed-MLA, routed-expert
one), then poke each edge of the accept/rollback state machine:
the pure accept rule (accept-all / reject-all / partial-accept), rewind
into COW-shared prefix-cache pages, draft-lane preemption under a
starved draft pool, and EOS/budget truncation mid-commit.
"""

import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.scheduler import Request
from repro.serving.spec_decode import accept_tokens

ARCH = "llama3.2-1b"
DRAFT_OTHER = "gemma-2b"      # different reduced weights: a draft that
                              # genuinely disagrees with the target


def _cfg():
    return get_arch(ARCH).reduced()


def _engine(cfg, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 40)
    kw.setdefault("block_size", 8)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("seed", 0)
    return ServingEngine(cfg, EngineConfig(**kw))


def _requests(n, prompt_len=12, gen=8, seed=0, arrivals=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    prompt=rng.integers(1, 500, prompt_len).astype(np.int32),
                    max_new_tokens=gen,
                    arrival_time=float(arrivals[i]) if arrivals is not None
                    else 0.0)
            for i in range(n)]


def _poisson_trace(n=6, rate=0.5, seed=3):
    """Poisson arrivals in virtual step time with mixed prompt/gen
    lengths — the same trace shape the serving benchmarks use."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    return [Request(rid=f"p{i}",
                    prompt=rng.integers(1, 500,
                                        int(rng.integers(6, 20))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(3, 10)),
                    arrival_time=float(arrivals[i]))
            for i in range(n)]


def _clone(reqs):
    return [Request(rid=r.rid, prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens,
                    arrival_time=r.arrival_time, eos_id=r.eos_id)
            for r in reqs]


# ---------------------------------------------------------------------------
# the accept rule, in isolation
# ---------------------------------------------------------------------------

def test_accept_all():
    # every draft matches the verify argmax -> all k accepted plus the
    # bonus token from the final row
    a, committed = accept_tokens([5, 7, 9], [5, 7, 9, 11])
    assert a == 3
    assert committed == [5, 7, 9, 11]


def test_reject_all():
    # first draft already disagrees -> nothing accepted, the corrected
    # token (what plain decode would have emitted) still commits
    a, committed = accept_tokens([5, 7, 9], [6, 7, 9, 11])
    assert a == 0
    assert committed == [6]


def test_partial_accept():
    # acceptance stops at the FIRST disagreement even if later drafts
    # happen to match again (they conditioned on a rejected token)
    a, committed = accept_tokens([5, 7, 9, 4], [5, 7, 8, 4, 2])
    assert a == 2
    assert committed == [5, 7, 8]


def test_accept_empty_draft():
    # a draft-preempted lane verifies only its pending token: C=1, the
    # argmax is exactly the plain-decode token
    a, committed = accept_tokens([], [42])
    assert a == 0
    assert committed == [42]


# ---------------------------------------------------------------------------
# engine-level greedy parity
# ---------------------------------------------------------------------------

def test_spec_parity_poisson_trace():
    """Bitwise-identical greedy tokens to plain decode on a Poisson
    trace, while committing > 1 token per step (self-speculation)."""
    cfg = _cfg()
    trace = _poisson_trace()
    plain = _engine(cfg).run(_clone(trace))
    eng = _engine(cfg, spec_draft="self", spec_k=3)
    spec = eng.run(_clone(trace))
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], spec[rid])
    s = eng.summary()
    assert s["spec_steps"] > 0
    assert s["spec_accepted_per_step"] > 1.0
    assert s["decode_steps"] < sum(len(t) for t in plain.values())


def test_spec_parity_latent_attention_experts():
    """The verifier is the chunk program with logits at every row for an
    absorbed-MLA, routed-expert model too: greedy tokens equal plain
    decode's, with drafts accepted."""
    cfg = get_arch("deepseek-v3-671b").reduced()
    trace = _poisson_trace()
    plain = _engine(cfg).run(_clone(trace))
    eng = _engine(cfg, spec_draft="self", spec_k=3)
    spec = eng.run(_clone(trace))
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], spec[rid])
    assert eng.summary()["spec_accepted_per_step"] > 1.0


def test_spec_parity_disagreeing_draft():
    """Parity must hold no matter how bad the draft is: a draft with
    different weights rejects most tokens, and every correction is the
    plain-decode token."""
    cfg = _cfg()
    reqs = _requests(3)
    plain = _engine(cfg).run(_clone(reqs))
    eng = _engine(cfg, spec_draft=DRAFT_OTHER, spec_k=3)
    spec = eng.run(_clone(reqs))
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], spec[rid])
    s = eng.summary()
    # engine-level reject/partial-accept actually exercised
    assert s["spec_drafted_tokens"] > s["spec_accepted_tokens"]
    assert s["spec_accept_rate"] < 1.0


def test_spec_budget_truncates_commit():
    """A commit batch larger than the remaining budget stops exactly at
    max_new_tokens (EOS/budget can land mid-commit)."""
    cfg = _cfg()
    reqs = _requests(2, gen=2)
    plain = _engine(cfg).run(_clone(reqs))
    spec = _engine(cfg, spec_draft="self", spec_k=4).run(_clone(reqs))
    for rid in plain:
        assert len(spec[rid]) == 2
        np.testing.assert_array_equal(plain[rid], spec[rid])


# ---------------------------------------------------------------------------
# rollback into shared (COW) pages
# ---------------------------------------------------------------------------

def test_spec_rollback_into_shared_cow_pages():
    """A cache-hit lane's verify rows start inside pages shared with the
    prefix cache (the minus-one resume offset).  The verify chunk writes
    there every step — including over rows a previous step rejected — so
    the engine must COW-fork before the write; the donor's cached pages
    must stay bit-identical, proven by the recipient decoding the same
    tokens as a cache-off run."""
    cfg = _cfg()
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 500, 16).astype(np.int32)  # 2 full pages

    def req(rid):
        return Request(rid=rid, prompt=prompt.copy(), max_new_tokens=8,
                       arrival_time=0.0)

    # cache-off baseline for the same prompt
    base = _engine(cfg).run([req("b")])["b"]

    eng = _engine(cfg, spec_draft="self", spec_k=3, prefix_cache=True,
                  sanitize=True)
    first = eng.run([req("a")])["a"]       # donor: populates the cache
    second = eng.run([req("c")])["c"]      # recipient: shared-page hit
    np.testing.assert_array_equal(base, first)
    np.testing.assert_array_equal(base, second)
    s = eng.summary()
    assert s["cache_hit_tokens"] > 0       # the hit actually happened
    assert eng.pool.cow_copies > 0         # and the verify write forked
    eng.pool.check()


def test_spec_draft_preemption():
    """A starved draft pool preempts draft lanes (pages free, the lane
    falls back to a plain C=1 verify) without losing parity or leaking
    draft pages."""
    cfg = _cfg()
    reqs = _requests(3)
    plain = _engine(cfg).run(_clone(reqs))
    eng = _engine(cfg, spec_draft="self", spec_k=3, spec_draft_blocks=4,
                  sanitize=True)
    spec = eng.run(_clone(reqs))
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], spec[rid])
    s = eng.summary()
    assert s["spec_draft_preempts"] > 0
    assert s["kv_draft_leaked_blocks"] == 0
    assert eng.spec.live_pages() == 0


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_spec_config_validation():
    cfg = _cfg()
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(cfg, EngineConfig(kv_layout="paged",
                                        spec_draft="self"))
    with pytest.raises(ValueError, match="greedy"):
        _engine(cfg, spec_draft="self", temperature=0.7)
    with pytest.raises(ValueError, match="spec_k"):
        _engine(cfg, spec_draft="self", spec_k=0)
    with pytest.raises(ValueError, match="family"):
        _engine(cfg, spec_draft="rwkv6-1.6b")
