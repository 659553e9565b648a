"""Serving launcher — thin CLI over the continuous-batching ServingEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --requests 8 --prompt-len 32 --gen 32

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke

``serve_waves`` is kept as the wave-based compatibility path (a whole batch
prefills together and decodes until the longest member finishes): it is the
reference the engine's greedy outputs are tested against, and the baseline
``benchmarks/bench_serving.py`` compares continuous batching to.

``sample_logits`` now lives in ``repro.serving.engine``; the re-export here
keeps existing imports working.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import sample_logits  # noqa: F401  (compat re-export)


def serve_waves(arch: str = "llama3.2-1b", preset: str = "reduced",
                batch: int = 4, prompt_len: int = 32, gen: int = 32,
                waves: int = 2, temperature: float = 0.8, top_k: int = 40,
                seed: int = 0, override_cfg=None, log: bool = True):
    """Wave-based batched serving (compatibility / baseline path)."""
    from repro.configs.registry import get_arch
    from repro.models.api import build_model

    cfg = override_cfg if override_cfg is not None else get_arch(arch)
    if preset == "reduced":
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    max_len = prompt_len + gen + 1

    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed + 1)
    stats = {"prefill_tokens": 0, "prefill_s": 0.0,
             "decode_tokens": 0, "decode_s": 0.0}
    outputs = []

    for w in range(waves):
        prompts = rng.integers(0, cfg.vocab_size,
                               (batch, prompt_len)).astype(np.int32)
        batch_in = {"tokens": jnp.asarray(prompts)}
        if cfg.family == "vlm":
            batch_in["patch_embeds"] = jnp.zeros(
                (batch, cfg.frontend.num_tokens, cfg.frontend.feature_dim),
                jnp.dtype(cfg.compute_dtype))
        src_len = 0
        if cfg.family == "encdec":
            src_len = prompt_len
            batch_in["src_features"] = jnp.asarray(
                rng.standard_normal((batch, src_len,
                                     cfg.frontend.feature_dim)),
                jnp.dtype(cfg.compute_dtype))

        cache = model.init_cache(batch, max_len
                                 + (cfg.frontend.num_tokens
                                    if cfg.family == "vlm" else 0),
                                 src_len=src_len)
        t0 = time.time()
        logits, cache = jax.block_until_ready(
            prefill(params, batch_in, cache))
        stats["prefill_s"] += time.time() - t0
        stats["prefill_tokens"] += batch * prompt_len

        key, k = jax.random.split(key)
        tok = sample_logits(k, logits, temperature, top_k)[:, None]
        generated = [np.asarray(tok)]
        t0 = time.time()
        for _ in range(gen - 1):
            logits, cache = decode(params, tok, cache)
            key, k = jax.random.split(key)
            tok = sample_logits(k, logits, temperature, top_k)[:, None]
            generated.append(np.asarray(tok))
        jax.block_until_ready(tok)
        stats["decode_s"] += time.time() - t0
        stats["decode_tokens"] += batch * (gen - 1)
        outputs.append(np.concatenate(generated, axis=1))
        if log:
            print(f"  wave {w}: generated {outputs[-1].shape} tokens")

    if log:
        print(f"serve: prefill {stats['prefill_tokens']/max(stats['prefill_s'],1e-9):,.0f} tok/s, "
              f"decode {stats['decode_tokens']/max(stats['decode_s'],1e-9):,.0f} tok/s")
    return outputs, stats


def serve_continuous(arch: str = "llama3.2-1b", preset: str = "reduced",
                     num_requests: int = 8, num_slots: int = 4,
                     prompt_len: int = 32, gen: int = 32,
                     temperature: float = 0.8, top_k: int = 40,
                     seed: int = 0, execute: str = "auto",
                     dispatcher: str = "oracle",
                     adaptnet_ckpt: str = None, kv_layout: str = "auto",
                     prefill_chunk: int = None, prefix_cache: bool = False,
                     shared_prefix_decode: bool = False,
                     defrag_threshold: float = None,
                     shared_prefix_len: int = 0, trace_out: str = None,
                     sanitize: bool = False, chaos=None,
                     deadline_s: float = None, snapshot_dir: str = None,
                     snapshot_every: int = 0, spec_draft: str = None,
                     spec_k: int = 4,
                     override_cfg=None, log: bool = True):
    """Serve a request set through the continuous-batching engine.

    ``execute`` selects the GEMM backend every model site runs through
    the SARA dispatch layer with: "pallas" (RSA kernel), "xla", or
    "auto" (compiled Pallas on TPU, XLA elsewhere).  ``dispatcher``
    selects the recommendation source: "oracle" (analytic search) or
    "adaptnet" (trained ADAPTNET-TPU loaded from ``adaptnet_ckpt`` —
    the self-adaptive path, with oracle fallback out of trained range).
    ``kv_layout`` selects the decode KV storage: "paged" (physical page
    arena + paged flash-decode kernel), "dense" (stacked per-slot caches),
    or "auto" (paged for attention families on TPU; dense elsewhere and
    for recurrent-state families).  ``prefill_chunk`` (with the paged
    layout, dense/moe families) streams each prompt into KV pages that
    many tokens per engine step — chunked paged prefill — instead of one
    padded-bucket call per request.  ``trace_out`` enables full span
    recording (``EngineConfig.trace``) and writes a Chrome/Perfetto
    trace-event JSON (plus a ``.jsonl`` event stream) to that path after
    the run — load it at https://ui.perfetto.dev or chrome://tracing.
    ``sanitize`` runs the KV-arena sanitizer (``EngineConfig.sanitize``):
    freed pages are NaN-poisoned, decode block tables are
    generation-checked, the pool invariants run every step, and leaks
    are audited at drain — use-after-free raises instead of corrupting
    output.  ``prefix_cache`` (requires ``prefill_chunk``) turns on the
    cross-request prefix cache: prompts that open with an
    already-served token run map those KV pages refcounted/copy-on-write
    instead of recomputing them; ``shared_prefix_decode`` additionally
    batches decode attention over the common physical prefix (cascade).
    ``chaos`` (a :class:`repro.serving.faults.ChaosConfig`) turns on the
    seed-driven fault-injection harness — injected pool OOMs / poisoned
    pages / stalls / forced preemptions are contained by the engine's
    step error boundary instead of crashing the run.  ``deadline_s``
    attaches a per-request deadline (virtual steps under the default
    step clock): queued requests past it expire, and admission sheds
    requests the rolling-TTFT estimate says cannot make it.
    ``snapshot_dir`` / ``snapshot_every`` enable crash-safe periodic
    engine snapshots (``ServingEngine.snapshot``/``restore``).
    ``spec_draft`` turns on speculative decoding (requires
    ``prefill_chunk`` and greedy sampling, ``temperature=0``): "self"
    for self-speculation or a registry arch name for a separate draft
    model; the draft proposes up to ``spec_k`` tokens per lane per step
    and one target verify pass commits the longest agreeing prefix plus
    a corrected token — outputs stay bitwise-identical to plain greedy
    decode.
    """
    from repro.configs.registry import get_arch
    from repro.serving import EngineConfig, Request, ServingEngine

    cfg = override_cfg if override_cfg is not None else get_arch(arch)
    if preset == "reduced":
        cfg = cfg.reduced()
    rng = np.random.default_rng(seed)
    engine = ServingEngine(cfg, EngineConfig(
        num_slots=num_slots, max_len=prompt_len + gen + 1,
        temperature=temperature, top_k=top_k, seed=seed,
        src_len=prompt_len if cfg.family == "encdec" else 0,
        execute=execute, dispatcher_mode=dispatcher,
        adaptnet_dir=adaptnet_ckpt, kv_layout=kv_layout,
        prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
        shared_prefix_decode=shared_prefix_decode,
        defrag_threshold=defrag_threshold, trace=trace_out is not None,
        sanitize=sanitize, chaos=chaos, snapshot_dir=snapshot_dir,
        snapshot_every=snapshot_every, spec_draft=spec_draft,
        spec_k=spec_k))
    # ``shared_prefix_len`` > 0 makes every prompt open with the same token
    # run (a system-prompt-style workload) so the cross-request prefix cache
    # has something to hit; the tail stays per-request random.
    shared = (rng.integers(0, cfg.vocab_size,
                           min(shared_prefix_len, prompt_len)).astype(np.int32)
              if shared_prefix_len > 0 else None)
    reqs = []
    for i in range(num_requests):
        p = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
        if shared is not None:
            p[:len(shared)] = shared
        extras = None
        if cfg.family == "encdec":
            extras = {"src_features": rng.standard_normal(
                (1, prompt_len, cfg.frontend.feature_dim)).astype(np.float32)}
        reqs.append(Request(rid=f"req-{i}", prompt=p, max_new_tokens=gen,
                            extras=extras, deadline_s=deadline_s))
    t0 = time.time()
    outputs = engine.run(reqs)
    if log:
        total = sum(len(v) for v in outputs.values())
        print(f"served {len(reqs)} requests / {total} tokens "
              f"in {time.time() - t0:.2f}s on {num_slots} slots "
              f"(kv_layout={engine.kv_layout})")
        print(engine.metrics.report(engine.dispatcher.cache_info(),
                                    engine.dispatch_stats()))
        print("  executed gemm plan (last step):")
        for site, desc in engine.gemm_plan.items():
            print(f"    {site:<24} {desc}")
    if trace_out is not None:
        jsonl = engine.export_trace(trace_out)
        if log:
            print(f"  trace: {trace_out} (+ {jsonl}) — "
                  f"{len(engine.obs)} events, open in ui.perfetto.dev")
    return outputs, engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--preset", default="reduced")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--execute", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="GEMM backend for the dispatch layer")
    ap.add_argument("--dispatcher", default="oracle",
                    choices=["oracle", "adaptnet"],
                    help="recommendation source for every GEMM site")
    ap.add_argument("--adaptnet-ckpt", default=None,
                    help="trained ADAPTNET-TPU dir (launch.train_adaptnet)")
    ap.add_argument("--kv-layout", default="auto",
                    choices=["auto", "paged", "dense"],
                    help="decode KV storage: paged arena or dense slots")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help=">0: chunked paged prefill — stream each prompt "
                         "into KV pages this many tokens per step "
                         "(requires --kv-layout paged, dense/moe families)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix cache: refcounted "
                         "copy-on-write KV pages shared across prompts "
                         "with a common token prefix (requires "
                         "--prefill-chunk and the paged layout)")
    ap.add_argument("--shared-prefix-decode", action="store_true",
                    help="with --prefix-cache: cascade decode attention — "
                         "one pass over the common physical prefix pages "
                         "+ per-lane unique suffixes, merged by softmax "
                         "state (reassociates the softmax; opt-in)")
    ap.add_argument("--defrag-threshold", type=float, default=None,
                    help="auto-defragment the KV pool from the engine "
                         "step loop when fragmentation exceeds this "
                         "fraction (0..1)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help=">0: every request's prompt opens with the same "
                         "token run of this length (system-prompt-style "
                         "workload for exercising --prefix-cache)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing and write a Chrome/Perfetto "
                         "trace-event JSON here after the run")
    ap.add_argument("--waves", type=int, default=0,
                    help=">0: run the legacy wave-based path instead")
    ap.add_argument("--sanitize", action="store_true",
                    help="KV-arena sanitizer: poison freed pages, "
                         "generation-check decode tables, per-step pool "
                         "invariants, leak audit at drain")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="seed-driven fault injection (pool OOM, poisoned "
                         "pages, stalls, forced preemption); faults are "
                         "contained by the step error boundary, not fatal")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline (virtual steps under the "
                         "default step clock): queued requests past it "
                         "expire, hopeless admissions are shed")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="crash-safe engine snapshots go here "
                         "(ServingEngine.snapshot/restore)")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                    help=">0: auto-snapshot every N engine steps "
                         "(requires --snapshot-dir)")
    ap.add_argument("--spec-draft", default=None, metavar="DRAFT",
                    help="speculative decoding: 'self' or a registry "
                         "arch name for the draft model (requires "
                         "--prefill-chunk and --temperature 0; outputs "
                         "stay bitwise-identical to plain greedy decode)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per lane per spec step "
                         "(verified by one K+1-row target pass)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI smoke: tiny trace, assert completion")
    a = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if a.smoke and a.chaos is not None:
        # Chaos smoke: the same greedy workload served twice — fault-free,
        # then with every injector armed at boosted probabilities.  The
        # chaotic run must terminate every request, contain at least one
        # injected fault inside the step boundary, and leave every
        # non-faulted request's tokens identical to the fault-free run.
        from repro.serving import ChaosConfig
        common = dict(
            arch=a.arch, preset=a.preset, num_requests=4, num_slots=2,
            prompt_len=12, gen=6, temperature=0.0, execute=a.execute,
            dispatcher=a.dispatcher, adaptnet_ckpt=a.adaptnet_ckpt,
            kv_layout="paged", prefill_chunk=a.prefill_chunk or 8,
            sanitize=True, log=False)
        base, _ = serve_continuous(**common)
        chaos = ChaosConfig(seed=a.chaos, pool_oom_p=0.15, poison_p=0.15,
                            stall_p=0.1, preempt_p=0.1)
        outputs, engine = serve_continuous(
            **common, chaos=chaos, deadline_s=a.deadline,
            snapshot_dir=a.snapshot_dir, snapshot_every=a.snapshot_every,
            trace_out=a.trace_out)
        s = engine.summary()
        assert s["faults_injected"] >= 1, s
        assert s["faults_contained"] >= 1, s
        outcomes = {r.rid: r.outcome for r in engine.requests.values()}
        assert all(outcomes.values()), outcomes   # every request terminal
        done = [rid for rid, o in outcomes.items() if o == "done"]
        for rid in done:
            assert np.array_equal(outputs[rid], base[rid]), \
                (rid, outputs[rid], base[rid])
        assert s["kv_leaked_tables"] == 0 and s["kv_leaked_refs"] == 0, s
        assert engine.pool.num_free == engine.pool.num_blocks
        print(f"chaos smoke OK (seed={a.chaos}: "
              f"{int(s['faults_injected'])} injected, "
              f"{int(s['faults_contained'])} contained, outcomes="
              f"{sorted(outcomes.values())}, greedy parity for "
              f"{len(done)} survivors)")
        return
    if a.smoke and a.prefix_cache:
        # Prefix-cache smoke: a shared-prefix workload served twice —
        # cache off, then cache on (+ optional cascade) — must agree
        # token-for-token under greedy sampling while the cached run
        # actually reuses pages.
        common = dict(
            arch=a.arch, preset=a.preset, num_requests=4, num_slots=2,
            prompt_len=24, gen=6, temperature=0.0, execute=a.execute,
            dispatcher=a.dispatcher, adaptnet_ckpt=a.adaptnet_ckpt,
            kv_layout="paged", prefill_chunk=a.prefill_chunk or 8,
            shared_prefix_len=16, defrag_threshold=a.defrag_threshold,
            sanitize=a.sanitize, log=False)
        base, _ = serve_continuous(**common)
        outputs, engine = serve_continuous(
            **common, prefix_cache=True,
            shared_prefix_decode=a.shared_prefix_decode,
            trace_out=a.trace_out)
        assert all(len(v) == 6 for v in outputs.values()), outputs
        assert set(outputs) == set(base)
        for rid in base:
            assert np.array_equal(outputs[rid], base[rid]), \
                (rid, outputs[rid], base[rid])
        stats = engine.prefix_cache.stats()
        assert stats["prefix_cache_hits"] > 0, stats
        assert stats["prefix_cache_reused_pages"] > 0, stats
        assert engine.metrics.cache_hit_tokens > 0
        engine.prefix_cache.clear()
        engine.pool.check()
        assert engine.pool.num_free == engine.pool.num_blocks
        print(f"prefix-cache smoke OK (hit_rate="
              f"{stats['prefix_cache_hit_rate']:.2f}, reused_pages="
              f"{stats['prefix_cache_reused_pages']}, greedy parity)")
        return
    if a.smoke and a.spec_draft:
        # Spec-decode smoke: the same greedy workload served twice —
        # plain, then speculatively — must agree token-for-token (every
        # committed token is a target verify argmax) while the spec run
        # actually accepts draft tokens and commits more than one token
        # per verify step.
        common = dict(
            arch=a.arch, preset=a.preset, num_requests=4, num_slots=2,
            prompt_len=12, gen=6, temperature=0.0, execute=a.execute,
            dispatcher=a.dispatcher, adaptnet_ckpt=a.adaptnet_ckpt,
            kv_layout="paged", prefill_chunk=a.prefill_chunk or 8,
            sanitize=a.sanitize, log=False)
        base, _ = serve_continuous(**common)
        outputs, engine = serve_continuous(
            **common, spec_draft=a.spec_draft, spec_k=a.spec_k,
            trace_out=a.trace_out)
        assert all(len(v) == 6 for v in outputs.values()), outputs
        assert set(outputs) == set(base)
        for rid in base:
            assert np.array_equal(outputs[rid], base[rid]), \
                (rid, outputs[rid], base[rid])
        s = engine.summary()
        assert s["spec_steps"] > 0, s
        assert s["spec_accepted_tokens"] >= 1, s
        if a.spec_draft == "self":
            assert s["spec_accepted_per_step"] > 1.0, s
        assert engine.spec.live_pages() == 0
        engine.pool.check()
        assert engine.pool.num_free == engine.pool.num_blocks
        print(f"spec-decode smoke OK (draft={a.spec_draft}, k={a.spec_k}: "
              f"greedy parity, {int(s['spec_accepted_tokens'])} accepted "
              f"draft tokens, "
              f"{s['spec_accepted_per_step']:.2f} committed/step over "
              f"{int(s['spec_steps'])} verify steps)")
        return
    if a.smoke:
        outputs, engine = serve_continuous(
            arch=a.arch, preset=a.preset, num_requests=3, num_slots=2,
            prompt_len=12, gen=6, temperature=0.0, execute=a.execute,
            dispatcher=a.dispatcher,
            adaptnet_ckpt=a.adaptnet_ckpt, kv_layout=a.kv_layout,
            trace_out=a.trace_out, sanitize=a.sanitize)
        assert all(len(v) == 6 for v in outputs.values()), outputs
        engine.pool.check()
        assert engine.pool.num_free == engine.pool.num_blocks
        if a.sanitize:
            s = engine.summary()
            assert s["kv_sanitize_checks"] > 0, s
            assert s["kv_poison_hits"] == 0 and \
                s["kv_generation_faults"] == 0, s
            assert s["kv_leaked_tables"] == 0 and s["kv_leaked_refs"] == 0
            print(f"sanitizer clean ({int(s['kv_sanitize_checks'])} checks, "
                  f"{int(s['kv_poison_fills'])} pages poisoned on free)")
        # the plan must be registry-backed: sites that actually traced
        assert engine.gemm_plan and "unembed" in engine.gemm_plan, \
            engine.gemm_plan
        assert engine.registry.scopes(), "no dispatch scopes traced"
        if a.dispatcher == "adaptnet":
            # the learned model (not the oracle) must have driven dispatch
            assert engine.dispatcher.mode == "adaptnet"
            src = engine.dispatcher.source_info()
            assert src["adaptnet"] > 0 or src["oracle_fallback"] > 0, src
            print(f"serving smoke OK (adaptnet: {src})")
            return
        print("serving smoke OK")
        return
    if a.waves > 0:
        serve_waves(arch=a.arch, preset=a.preset, batch=a.slots,
                    prompt_len=a.prompt_len, gen=a.gen, waves=a.waves,
                    temperature=a.temperature, top_k=a.top_k)
        return
    chaos = None
    if a.chaos is not None:
        from repro.serving import ChaosConfig
        chaos = ChaosConfig(seed=a.chaos, pool_oom_p=0.05,
                            poison_p=0.05 if a.sanitize else 0.0,
                            stall_p=0.05, preempt_p=0.05)
    serve_continuous(arch=a.arch, preset=a.preset, num_requests=a.requests,
                     num_slots=a.slots, prompt_len=a.prompt_len, gen=a.gen,
                     temperature=a.temperature, top_k=a.top_k,
                     execute=a.execute, dispatcher=a.dispatcher,
                     adaptnet_ckpt=a.adaptnet_ckpt, kv_layout=a.kv_layout,
                     prefill_chunk=a.prefill_chunk or None,
                     prefix_cache=a.prefix_cache,
                     shared_prefix_decode=a.shared_prefix_decode,
                     defrag_threshold=a.defrag_threshold,
                     shared_prefix_len=a.shared_prefix_len,
                     trace_out=a.trace_out, sanitize=a.sanitize,
                     chaos=chaos, deadline_s=a.deadline,
                     snapshot_dir=a.snapshot_dir,
                     snapshot_every=a.snapshot_every,
                     spec_draft=a.spec_draft, spec_k=a.spec_k)


if __name__ == "__main__":
    main()
