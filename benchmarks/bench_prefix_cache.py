"""Cross-request prefix caching on a shared-prefix Poisson trace.

Serves the same request set — a fraction of prompts open with one common
token run (the system-prompt workload prefix caching is for), arrivals
drawn from a Poisson process in virtual step time — through two
engines, cache off and cache on, and reports, per variant:

  * TTFT p50/p99 (virtual steps): a cache hit maps the shared prefix's
    KV pages at admission, so a recipient prefills only its suffix —
    first token lands after one cheap chunk batch instead of the full
    prompt's worth
  * prefill KV rows written into the paged arena: the tentpole claim —
    shared-prefix rows are written once by the first requester and
    refcounted into every later table, so write traffic scales with
    *distinct* tokens, not total tokens
  * prefix-cache telemetry: hit rate, reused pages, analytic prefill
    FLOPs avoided, COW copies, live shared pages
  * greedy parity: cache-on must emit exactly the cache-off tokens
    (page sharing is bitwise — same rows, same physical arena reads)

``--smoke`` is the CI gate: hits > 0, exact greedy parity cache-on vs
cache-off, KV-write reduction > 1.4x on the tiny trace, and a bounded
engine retrace count.
"""

import argparse

import numpy as np

ARCH = "llama3.2-1b"
BLOCK = 16


def _trace(cfg, rng, n, shared_frac, prefix_len, prompt_len, gen,
           mean_gap):
    """``n`` requests; the first ``round(n * shared_frac)`` open with one
    common ``prefix_len``-token run.  Request 0 (the donor) arrives at
    t=0 with a head start of ``2 * mean_gap`` virtual steps so its pages
    are cached before the Poisson tail of recipients lands; later gaps
    are exponential (Poisson arrivals in step time)."""
    from repro.serving import Request

    shared = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    n_shared = int(round(n * shared_frac))
    reqs, t = [], 0.0
    for i in range(n):
        p = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
        if i < n_shared:
            p[:prefix_len] = shared
        rid = f"{'shared' if i < n_shared else 'uniq'}-{i}"
        reqs.append(Request(rid, p, gen, arrival_time=t))
        t += 2 * mean_gap if i == 0 else float(rng.exponential(mean_gap))
    return reqs


def _serve(cfg, reqs, *, max_len, num_blocks, chunk, prefix_cache=False):
    from repro.serving import EngineConfig, ServingEngine
    engine = ServingEngine(cfg, EngineConfig(
        num_slots=4, max_len=max_len, block_size=BLOCK,
        num_blocks=num_blocks, temperature=0.0, kv_layout="paged",
        prefill_chunk=chunk, prefix_cache=prefix_cache))
    res = engine.run(reqs)
    if engine.prefix_cache is not None:
        engine.prefix_cache.clear()
    engine.pool.check()
    assert engine.pool.num_free == engine.pool.num_blocks
    return res, engine


def run(n: int = 16, shared_frac: float = 0.75, prefix_len: int = 64,
        prompt_len: int = 80, gen: int = 16, chunk: int = 16,
        mean_gap: float = 6.0):
    from benchmarks.common import emit
    from repro.configs.registry import get_arch

    cfg = get_arch(ARCH).reduced()
    max_len = prompt_len + gen + 1
    num_blocks = 4 * (-(-(max_len + 1) // BLOCK)) + 2 * (prefix_len // BLOCK)
    variants = [
        ("cache_off", dict()),
        ("cache_on", dict(prefix_cache=True)),
    ]
    rows, outputs, kv_rows = [], {}, {}
    for name, kw in variants:
        reqs = _trace(cfg, np.random.default_rng(0), n, shared_frac,
                      prefix_len, prompt_len, gen, mean_gap)
        res, eng = _serve(cfg, reqs, max_len=max_len,
                          num_blocks=num_blocks, chunk=chunk, **kw)
        outputs[name] = res
        s = eng.summary()
        kv_rows[name] = s["prefill_kv_write_rows"]
        rows += [
            {"name": f"bench_prefix_cache.{name}.ttft_p50_steps",
             "value": round(s["ttft_p50_s"], 3),
             "derived": "virtual step clock"},
            {"name": f"bench_prefix_cache.{name}.ttft_p99_steps",
             "value": round(s["ttft_p99_s"], 3)},
            {"name": f"bench_prefix_cache.{name}.prefill_kv_write_rows",
             "value": s["prefill_kv_write_rows"],
             "derived": "rows committed to the paged arena"},
            {"name": f"bench_prefix_cache.{name}.jit_compiles",
             "value": eng.dispatch_stats()["jit_compiles"]},
        ]
        if "prefix_cache_hit_rate" in s:
            rows += [
                {"name": f"bench_prefix_cache.{name}.hit_rate",
                 "value": round(s["prefix_cache_hit_rate"], 3),
                 "derived": "admissions matching a cached prefix"},
                {"name": f"bench_prefix_cache.{name}.reused_pages",
                 "value": s["prefix_cache_reused_pages"]},
                {"name": f"bench_prefix_cache.{name}.cache_hit_tokens",
                 "value": s["cache_hit_tokens"],
                 "derived": "prompt tokens served from cached pages"},
                {"name": f"bench_prefix_cache.{name}.prefill_flops_saved",
                 "value": float(f"{s['prefill_flops_saved']:.3e}"),
                 "derived": "analytic per-token GEMM cost avoided"},
                {"name": f"bench_prefix_cache.{name}.kv_cow_copies",
                 "value": s["kv_cow_copies"]},
            ]

    # -- cross-variant claims -------------------------------------------------
    reduction = kv_rows["cache_off"] / max(kv_rows["cache_on"], 1)
    assert reduction >= 2.0, \
        f"prefill KV-write reduction {reduction:.2f}x < 2x " \
        f"({kv_rows['cache_off']} vs {kv_rows['cache_on']} rows)"
    for rid, toks in outputs["cache_off"].items():
        np.testing.assert_array_equal(outputs["cache_on"][rid], toks)
    rows += [
        {"name": "bench_prefix_cache.prefill_kv_write_reduction_x",
         "value": round(reduction, 3),
         "derived": "cache_off rows / cache_on rows (claim: >= 2x)"},
        {"name": "bench_prefix_cache.greedy_parity", "value": 1,
         "derived": "cache_on tokens == cache_off tokens, exactly"},
    ]
    return emit(rows, "bench_prefix_cache",
                config={"n": n, "shared_frac": shared_frac,
                        "prefix_len": prefix_len, "prompt_len": prompt_len,
                        "gen": gen, "chunk": chunk, "mean_gap": mean_gap,
                        "arch": ARCH})


def smoke():
    """CI gate: cache hits happen, greedy tokens are exactly the
    cache-off tokens, KV writes drop, retraces stay bounded."""
    from repro.configs.registry import get_arch

    cfg = get_arch(ARCH).reduced()
    n, prefix_len, prompt_len, gen = 6, 16, 24, 4
    max_len = prompt_len + gen + 1
    kw = dict(max_len=max_len, num_blocks=14, chunk=8)
    reqs = _trace(cfg, np.random.default_rng(0), n, 2 / 3, prefix_len,
                  prompt_len, gen, 4.0)
    res_off, _ = _serve(cfg, reqs, **kw)
    reqs = _trace(cfg, np.random.default_rng(0), n, 2 / 3, prefix_len,
                  prompt_len, gen, 4.0)
    res_on, eng = _serve(cfg, reqs, prefix_cache=True, **kw)
    for rid in res_off:
        np.testing.assert_array_equal(res_on[rid], res_off[rid])
    s = eng.summary()
    assert s["prefix_cache_hits"] > 0, s
    assert s["cache_hit_tokens"] > 0, s
    off_rows = n * prompt_len
    reduction = off_rows / max(s["prefill_kv_write_rows"], 1)
    assert reduction > 1.4, \
        f"reduction {reduction:.2f}x ({s['prefill_kv_write_rows']} rows)"
    compiles = eng.dispatch_stats()["jit_compiles"]
    assert 2 <= compiles <= 16, f"jit_compiles={compiles}"
    print(f"prefix-cache smoke OK (greedy parity, "
          f"{s['prefix_cache_hits']} hits, "
          f"{s['cache_hit_tokens']} cached tokens, "
          f"{reduction:.2f}x fewer KV writes, {compiles} jit compiles)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--shared-frac", type=float, default=0.75)
    ap.add_argument("--prefix-len", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=80)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI parity gate (no sweep)")
    a = ap.parse_args()
    if a.smoke:
        smoke()
        return
    print("name,value,derived")
    run(n=a.n, shared_frac=a.shared_frac, prefix_len=a.prefix_len,
        prompt_len=a.prompt_len, gen=a.gen, chunk=a.chunk)


if __name__ == "__main__":
    main()
