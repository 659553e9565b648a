"""Bring-up smoke for a TPU: llama3.2-1b served at full width through the
compiled Pallas path.  The quickest proof that the system still starts and
computes the right thing on the chip; its wall times are not a benchmark.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded training on a 2x2 mesh only

One chip runs three phases at llama3.2-1b's published widths (16 layers,
d 2048, 32 query / 8 KV heads of 64, ff 8192, vocab 128256, bf16) from
random weights seeded by ``--seed``:

1. paged attention: the compiled decode and chunked-prefill kernels against
   their XLA twins (``kernels/ref.py``) on a ragged page arena;
2. RSA GEMM: ``dispatch.gemm`` at the oracle's tile for the decode QKV, MLP
   and unembed shapes against a plain dot with f32 accumulation;
3. serving: 8 requests (prompts of 128-512 tokens, 32 greedy tokens each)
   on 4 slots through ``ServingEngine`` with the paged KV arena, chunked
   prefill and the default ``execute="auto"`` oracle dispatch.  It asserts
   what ran (paged layout, every GEMM site on "pallas", every request done,
   a leak-free pool) and compares each request's first-token logits with a
   float32 reference forward (``models/transformer.py``).

``--four-chips`` runs only the sharded training step: 5 steps of
``launch.train.build_trainer`` on a (data=2, model=2) mesh against the same
seed and batches on a one-device mesh of the first chip.

Every phase that fails raises, and the process exits non-zero.  Without a
TPU it exits non-zero before any phase and never falls back to the CPU.
The last line of stdout, printed only on success, is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama3.2-1b"
SLOTS = 4
REQUESTS = 8
PROMPT_MIN, PROMPT_MAX = 128, 512
GEN = 32
CHUNK = 256                       # prefill_chunk: two chunks for the longest
MAX_LEN = PROMPT_MAX + GEN + 1
GEMM_SITES = ("attn_qkv", "mlp_up", "mlp_down", "lm_head")

# Bounds, each on max|x - ref| / max|ref|; a wrong page, mask, head, tile
# index or residency shows as an error of order 1:
# - paged kernels: the kernel rounds softmax probabilities to bf16 before
#   the PV matmul and emits bf16, the twin is f32 throughout and rounds
#   once; each rounding is 2^-9 relative.
KERNEL_TOL = 2e-2
# - RSA GEMM: f32 accumulation (OS in VMEM, WS/IS as f32 partials summed
#   after the kernel) rounded once to the bf16 output (2^-9).
GEMM_TOL = 2e-2
# - first-token logits of the bf16 engine against the f32 reference: bf16
#   weights and activations (2^-9 per rounding) through 16 layers, about
#   ten roundings each.  A token may differ from the reference argmax only
#   where the reference's top-2 margin is within this bound.
LOGIT_TOL = 3e-2

# four-chip training: depth cut so the one-device comparison (f32 params,
# grads and Adam state, 16 bytes a parameter) fits one chip's 16 GB
TRAIN_LAYERS = 4                  # of 16
TRAIN_STEPS = 5
TRAIN_BATCH, TRAIN_SEQ = 8, 128
TRAIN_TOL = 1e-3                  # relative, per step's loss


def rel_err(x, ref) -> float:
    x, ref = np.asarray(x, np.float32), np.asarray(ref, np.float32)
    if not (np.isfinite(x).all() and np.isfinite(ref).all()):
        return float("inf")
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def check(label: str, err: float, bound: float) -> None:
    print(f"{label}: max_err={err:.3e} bound={bound:.0e}")
    if not err <= bound:
        raise RuntimeError(f"{label}: error {err:.3e} exceeds {bound:.0e}")


def require_tpu(count: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r} "
                 "devices; refusing to fall back")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds spent in XLA compilation (persistent-cache reads included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration


# ---------------------------------------------------------------------------
# phase 1: paged attention kernels vs their XLA twins
# ---------------------------------------------------------------------------

def kernel_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    bs = 16
    width = -(-MAX_LEN // bs)
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    nb = SLOTS * width + 1
    arena = (nb, bs, cfg.num_kv_heads, cfg.head_dim)
    k = jax.random.normal(keys[0], arena, jnp.bfloat16)
    v = jax.random.normal(keys[1], arena, jnp.bfloat16)
    # every lane owns its own scattered pages
    tables = jnp.asarray(rng.permutation(nb - 1)[:SLOTS * width]
                         .reshape(SLOTS, width).astype(np.int32))
    # decode: one token, a page boundary, one past it, a full table
    lengths = jnp.asarray([1, bs, bs + 1, MAX_LEN - 1], jnp.int32)
    q = jax.random.normal(keys[2], (SLOTS, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    out = {impl: ops.paged_attention(q, k, v, tables, lengths, impl=impl,
                                     interpret=False)
           for impl in ("pallas", "xla")}
    check("paged decode (pallas vs xla)", rel_err(out["pallas"], out["xla"]),
          KERNEL_TOL)

    # chunked prefill: a fresh prompt, one row after a page, a ragged
    # mid-stream chunk, a full chunk ending at the last position
    starts = np.array([0, bs, 100, MAX_LEN - 1 - CHUNK], np.int32)
    chunks = np.array([CHUNK, 1, 77, CHUNK], np.int32)
    q = jax.random.normal(keys[3], (SLOTS, CHUNK, cfg.num_heads,
                                    cfg.head_dim), jnp.bfloat16)
    args = (q, k, v, tables, jnp.asarray(starts),
            jnp.asarray(starts + chunks))
    out = {impl: np.asarray(ops.paged_prefill_attention(
        *args, impl=impl, interpret=False), np.float32)
        for impl in ("pallas", "xla")}
    live = np.arange(CHUNK)[None, :] < chunks[:, None]   # rows past a chunk
    check("paged prefill (pallas vs xla)",                # are garbage
          rel_err(out["pallas"][live], out["xla"][live]), KERNEL_TOL)


# ---------------------------------------------------------------------------
# phase 2: RSA GEMM at the oracle's tiles vs a plain dot
# ---------------------------------------------------------------------------

def gemm_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro import dispatch
    from repro.core.hw import DATAFLOW_NAMES
    from repro.serving.engine import gemm_sites

    reg = dispatch.SiteRegistry()
    key = jax.random.PRNGKey(seed + 1)
    for site, m, k, n in gemm_sites(cfg, SLOTS):
        if site not in GEMM_SITES:
            continue
        key, ka, kb = jax.random.split(key, 3)
        a = jax.random.normal(ka, (m, k), jnp.bfloat16)
        b = jax.random.normal(kb, (k, n), jnp.bfloat16)
        with dispatch.use(execute="pallas", registry=reg), reg.scope("smoke"):
            got = jax.jit(lambda x, w, s=site: dispatch.gemm(x, w, site=s))(
                a, b)
        ref = jnp.dot(a, b, preferred_element_type=jnp.float32)
        r = reg.sites("smoke")[site]
        check(f"rsa_gemm {site} {m}x{k}x{n} bm={r.block_m} bn={r.block_n} "
              f"bk={r.block_k} {DATAFLOW_NAMES[r.mode]} (vs jnp.dot f32)",
              rel_err(got, ref), GEMM_TOL)


# ---------------------------------------------------------------------------
# phase 3: serve at full width, then check against the f32 reference
# ---------------------------------------------------------------------------

def capture_first_token_logits(engine) -> dict:
    """Record, per request, the logits its first token is sampled from: the
    row of the chunk-prefill step that lands the request's last prompt
    chunk.  Wraps the engine's jitted step; changes nothing it computes."""
    step = engine._chunk_prefill
    captured = {}

    def spy(params, toks, leaves, tables, kv, chunk):
        logits, leaves = step(params, toks, leaves, tables, kv, chunk)
        n = np.asarray(chunk)
        for slot, req in engine.sched.active.items():
            if req.prefilling and n[slot] and \
                    req.prefill_pos + n[slot] >= req.context_len:
                captured[req.rid] = np.asarray(logits[slot], np.float32)
        return logits, leaves

    engine._chunk_prefill = spy
    return captured


def serve_phase(cfg, seed: int):
    import jax
    from repro.serving import EngineConfig, Request, ServingEngine

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, REQUESTS)
    lens[:2] = PROMPT_MIN, PROMPT_MAX
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    engine = ServingEngine(cfg, EngineConfig(
        num_slots=SLOTS, max_len=MAX_LEN, temperature=0.0, seed=seed,
        kv_layout="paged", prefill_chunk=CHUNK))
    first = capture_first_token_logits(engine)
    reqs = [Request(rid=f"req-{i}", prompt=p, max_new_tokens=GEN)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    outputs = engine.run(reqs)
    jax.block_until_ready(engine.arena.leaves)
    wall = time.perf_counter() - t0

    backends = {scope: engine.registry.backends(scope)
                for scope in engine.registry.scopes()}
    print(f"engine: kv_layout={engine.kv_layout} prefill_chunk="
          f"{engine.prefill_chunk} gemm backends per scope={backends}")
    if engine.kv_layout != "paged":
        raise RuntimeError(f"kv_layout resolved to {engine.kv_layout!r}")
    if not backends or any(set(b) != {"pallas"} for b in backends.values()):
        raise RuntimeError(f"GEMM sites not all on pallas: {backends}")
    outcomes = {r.rid: r.outcome for r in reqs}
    if any(o != "done" for o in outcomes.values()) or \
            any(len(outputs[r.rid]) != GEN for r in reqs):
        raise RuntimeError(f"requests incomplete: {outcomes}, lengths "
                           f"{[len(outputs[r.rid]) for r in reqs]}")
    engine.pool.check()
    if engine.pool.num_free != engine.pool.num_blocks:
        raise RuntimeError(f"pool leaked: {engine.pool.num_free} of "
                           f"{engine.pool.num_blocks} pages free")
    if set(first) != {r.rid for r in reqs}:
        raise RuntimeError(f"first-token logits captured for {sorted(first)}")
    tokens = sum(len(v) for v in outputs.values())
    print(f"served {len(reqs)} requests, prompts {int(lens.min())}-"
          f"{int(lens.max())} tokens ({int(lens.sum())} in all), "
          f"{tokens} tokens generated, {engine.summary()['jit_compiles']:.0f}"
          f" step compiles; wall {wall:.2f} s (smoke, not a benchmark)")
    return engine, reqs, prompts, outputs, first


def reference_logits(cfg, params, prompts) -> np.ndarray:
    """Last-position logits of each prompt from the plain float32 forward:
    XLA dots at highest precision, no kernels, cache or batching tricks."""
    import jax
    import jax.numpy as jnp
    from repro import dispatch
    from repro.models import transformer

    cfg32 = cfg.replace(param_dtype="float32", compute_dtype="float32")
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    # forward() predicts from tokens[:, :-1]; causal, so padding at the end
    # leaves every earlier position unchanged
    toks = np.zeros((len(prompts), PROMPT_MAX + 1), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    last = np.array([len(p) - 1 for p in prompts], np.int32)

    def fwd(p, toks, last):
        hidden, _, _ = transformer.forward(p, {"tokens": toks}, cfg32)
        h = hidden[jnp.arange(hidden.shape[0]), last]
        return jnp.dot(h, transformer._unembed_weight(p, cfg32))

    with dispatch.use(execute="xla"), jax.default_matmul_precision("highest"):
        out = jax.jit(fwd)(p32, jnp.asarray(toks), jnp.asarray(last))
    return np.asarray(out, np.float32)


def reference_phase(cfg, engine, reqs, prompts, outputs, first) -> None:
    ref = reference_logits(cfg, engine.params, prompts)
    worst, flips = 0.0, []
    for i, r in enumerate(reqs):
        got, want = first[r.rid], ref[i]
        err = rel_err(got, want)
        worst = max(worst, err)
        top2 = np.sort(want)[-2:]
        margin = float((top2[1] - top2[0]) / np.max(np.abs(want)))
        tok, ref_tok = int(outputs[r.rid][0]), int(np.argmax(want))
        print(f"  {r.rid} prompt {len(prompts[i])}: logits err {err:.3e}, "
              f"token {tok} vs reference {ref_tok} (top-2 margin "
              f"{margin:.3e})")
        if tok != ref_tok and margin > LOGIT_TOL:
            flips.append(r.rid)
    check("first-token logits (engine bf16 vs f32 reference)", worst,
          LOGIT_TOL)
    if flips:
        raise RuntimeError(f"first tokens differ from the reference argmax "
                           f"beyond the tolerance margin: {flips}")


def one_chip(cfg, seed: int) -> None:
    import jax

    clock = CompileClock()
    kernel_phase(cfg, seed)
    gemm_phase(cfg, seed)
    engine, reqs, prompts, outputs, first = serve_phase(cfg, seed)
    print(f"compile: {clock.seconds:.1f} s through serving")
    reference_phase(cfg, engine, reqs, prompts, outputs, first)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# four chips: the sharded train step against one chip
# ---------------------------------------------------------------------------

def four_chips(cfg, seed: int) -> None:
    import jax
    from repro.launch.train import build_trainer

    cfg = cfg.replace(num_layers=TRAIN_LAYERS)
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, cfg.vocab_size, (TRAIN_BATCH, TRAIN_SEQ + 1))
               .astype(np.int32) for _ in range(TRAIN_STEPS)]
    losses = {}
    # Pallas kernels cannot be partitioned across devices, so the mesh runs
    # its GEMMs through XLA ("auto" resolves so); both runs pin that backend
    # so that the mesh is the only difference
    for axes in ((2, 2), (1, 1)):
        t0 = time.perf_counter()
        params, opt_state, step, b_sh = build_trainer(
            cfg, *axes, global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ,
            seed=seed, execute="xla")
        run = []
        for b in batches:
            params, opt_state, metrics = step(
                params, opt_state, jax.device_put({"tokens": b}, b_sh))
            run.append(float(metrics["loss"]))
        del params, opt_state
        losses[axes] = run
        print(f"mesh data={axes[0]} model={axes[1]}: losses "
              f"{[f'{x:.6f}' for x in run]} ({time.perf_counter() - t0:.1f}"
              " s with compile)")
    a, b = np.array(losses[(2, 2)]), np.array(losses[(1, 1)])
    check("train loss, 2x2 mesh vs one chip (relative)",
          float(np.max(np.abs(a - b) / np.abs(b))), TRAIN_TOL)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, prompts and test data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training comparison on a "
                         "2x2 mesh (needs 4 chips)")
    a = ap.parse_args()
    device = require_tpu(4 if a.four_chips else 1)
    from repro.configs.registry import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {device['kind']} x{device['count']}; compile cache "
          f"{enable_compile_cache()}")
    (four_chips if a.four_chips else one_chip)(get_arch(ARCH), a.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
