"""A whole run of a tiny cell on the CPU, the chip check skipped: the
result line's keys, and ``correct`` against the reference.  Then the
timed path broken underneath, once per fault a served cell can have, and
``correct`` must come out false."""

import time

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}



def _run(cell, seed=3, seconds=3.0):
    import harness
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            CPU, log=lambda m: None)


def test_tiny_run_is_correct(tiny_cell):
    out = _run(tiny_cell)
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert list(out)[-1] == "compared"
    assert out["attempted"] > 5 and out["failed"] == 0
    for name in ("ttft_p90_ms", "itl_p95_ms", "out_tok_per_s", "setup_s"):
        assert out["metrics"][name]["value"] > 0
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"], gap
    assert set(out["compared"]) == {"max_logit_gap"}
    assert out["correct"] is True


def test_altered_token_is_caught(tiny_cell, monkeypatch):
    from repro.serving import engine as eng
    real = eng.sample_logits

    def off_by_one(key, logits, temperature=1.0, top_k=0):
        return (real(key, logits, temperature, top_k) + 1) % logits.shape[-1]

    monkeypatch.setattr(eng, "sample_logits", off_by_one)
    out = _run(tiny_cell)
    assert out["correct"] is False, out["compared"]


def test_unchanged_state_is_caught(tiny_cell, monkeypatch):
    """A decode step that returns the KV arena it was given: every later
    token attends without the keys of the tokens before it."""
    from repro.models import serving

    real = serving.paged_decode_step

    def forgetful(params, tokens, cfg, state, arena, *rest):
        logits, _ = real(params, tokens, cfg, state, arena, *rest)
        return logits, arena

    monkeypatch.setattr(serving, "paged_decode_step", forgetful)
    out = _run(tiny_cell)
    assert out["correct"] is False, out["compared"]


def test_half_the_batch_left_out_is_caught(tiny_cell, monkeypatch):
    """A chunked-prefill step that computes only the first half (rounded
    down) of its live lanes: the others get no KV rows and stale logits."""
    import jax.numpy as jnp
    from repro.models import serving

    real = serving.paged_prefill_step

    def half(params, tokens, cfg, arena, tables, kv_lens, chunk_lens):
        live = chunk_lens > 0
        rank = jnp.cumsum(live.astype(jnp.int32))
        keep = live & (rank <= live.sum() // 2)
        return real(params, tokens, cfg, arena, tables, kv_lens,
                    jnp.where(keep, chunk_lens, 0))

    monkeypatch.setattr(serving, "paged_prefill_step", half)
    out = _run(tiny_cell)
    assert out["correct"] is False, out["compared"]


def test_control_fails_the_limit(tiny_cell):
    """The float8 control, read at the served positions of a tiny run,
    lies beyond the limit that the program's own reading keeps to."""
    import gc

    import harness
    from control import control_gaps
    cfg, engine, plan = harness.setup(tiny_cell, 5, 3.0)
    run = harness.RunData(tiny_cell, cfg, {})
    harness.drive(engine, plan, 3.0, run)
    picked = harness.sample(run, 5)
    del engine
    gc.collect()
    model = tiny_cell.model
    program = harness.compare(model, cfg, 5, picked)["max_logit_gap"]
    control = control_gaps(model, cfg, 5, picked)["max_logit_gap"]
    limit = tiny_cell.limits["max_logit_gap"]
    assert program <= limit < control, (program, limit, control)


def test_shared_prefixes_are_cached_before_the_window(tiny_cell):
    """Set-up prefills each shared system prompt once, so the window's
    first request on each prompt already maps its pages from the cache."""
    import harness
    cell = tiny_cell
    cell.mix = dict(cell.mix, shared_prefix={"prompts": 2, "tokens": 32,
                                             "zipf_s": 1.0})
    cell.mix["prompt_tokens"] = {"median": 12, "sigma": 0.3, "min": 4,
                                 "max": 24}
    cfg, engine, plan = harness.setup(cell, 6, 2.0)
    assert engine.prefix_cache.num_entries >= 2 * 32 // 16
    run = harness.RunData(cell, cfg, {})
    harness.drive(engine, plan, 2.0, run)
    admitted = [r.req for r in run.recs if r.req.t_admit >= 0]
    assert admitted
    firsts = {}
    for r in run.recs:
        if r.req.t_admit >= 0:
            firsts.setdefault(r.planned.prefix_id, r.req)
    assert all(q.cached_prefix_tokens >= 16 for q in firsts.values())
