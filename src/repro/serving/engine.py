"""ServingEngine: continuous-batching inference over the repro model stack.

Execution model
---------------
* ``num_slots`` fixed decode lanes.  Each slot owns a batch=1 cache pytree
  (``models/serving.py``); the engine stacks them on a leading slot axis and
  decodes every step with one ``jit(vmap(decode_step))`` — per-slot scalar
  positions/lengths become per-lane under vmap, so heterogeneous sequence
  lengths coexist in one batched step with no model changes.
* Prefill has two paths.  The *bucketed* default runs per admitted request
  at a small set of padded bucket shapes (one XLA compilation per bucket):
  the prompt is right-padded and the true ``length`` is passed as a traced
  scalar, which ``serving.prefill`` uses to pick the real last-token
  logits and correct the cache lengths.  SSM/hybrid families use
  exact-length prefill (their recurrent state integrates every input
  token).  Under ``EngineConfig.prefill_chunk`` (paged layout, dense/moe
  families) prefill is instead *chunked and paged*: every step runs ONE
  ragged batch over all mid-prefill lanes, each contributing up to
  ``prefill_chunk`` of its remaining context (per-row lengths — one batch
  carries heterogeneous prompts; rows past a lane's length land in the
  write-discard page exactly like stalled decode lanes).  Chunk KV rows
  are written straight into the lane's pool pages — no dense scratch
  cache, no bucket-granularity copy — so prefill KV traffic scales with
  real prompt tokens, a long prompt streams over several steps instead of
  monopolizing one, and a short prompt admitted alongside gets its first
  token after one cheap chunk batch (TTFT is stamped per chunk
  completion).
* Every GEMM the model runs goes through the SARA dispatch layer
  (``repro.dispatch``): each prefill/decode entry point traces under a
  named registry scope with this engine's dispatcher active, so the tile
  configuration every site *executes* with (RSA Pallas blocks + residency
  mode under ``execute="pallas"``/on-TPU ``"auto"``; XLA otherwise) is
  recorded per trace.  ``gemm_plan`` is read back from that registry —
  the executed plan, not an advisory estimate — and ``plan_changes``
  counts real reconfigurations (steps whose executed plan differs from
  the previous step's).  ``SaraDispatcher.cache_info()`` feeds the
  recommendation-cache hit rate into the metrics.
* ``EngineConfig.dispatcher_mode`` selects the recommendation source:
  ``"oracle"`` (exhaustive analytic search) or ``"adaptnet"`` (a trained
  ADAPTNET-TPU loaded from ``adaptnet_dir`` — the paper's self-adaptive
  runtime path; shapes outside its trained range fall back to the
  oracle, and per-source site counts land in ``dispatch_stats()``).
* KV layout (``EngineConfig.kv_layout``): under ``"paged"`` (what
  ``"auto"`` picks for attention families on TPU) each layer's K/V rows
  live in a physical page arena ``(layers, num_blocks + 1, block_size,
  ...)`` bound to the ``KVBlockPool``; decode runs ONE batched ``paged_decode_step`` over all
  lanes that reads K/V through per-slot block tables
  (``kernels/paged_attn.py``), so per-step KV traffic is
  ``sum_lane ceil(kv_len / block_size)`` pages — it scales with live
  tokens, not ``num_slots * max_len``.  The table width shipped to the
  kernel each step is the max live page count rounded up to a power of
  two (one compilation per width bucket).  Bucketed prefill runs at
  padded bucket shapes into a scratch dense cache whose first pages are
  then scattered into the arena at bucket granularity; chunked prefill
  (``prefill_chunk``) skips the scratch cache entirely and writes chunk
  rows straight into pages.  Slot KV
  snapshot/restore disappears: stalled lanes simply don't commit (their
  new-token KV is routed to the arena's trailing write-discard page) and
  preemption frees pages without copying anything.  ``"dense"`` keeps the
  original stacked per-slot caches + ``jit(vmap(decode_step))`` and is
  what recurrent-state families (ssm, hybrid) always use; encdec pages
  its self-attention KV while its cross K/V stays dense per slot.
* The ``KVBlockPool`` meters admission over *text* tokens under the dense
  layout (the vlm frontend adds a constant per-slot overhead outside the
  budget); under the paged layout the vlm frontend's rows live in pool
  pages too, so reservations include them.  ``reserve="full"`` can never
  stall; ``reserve="incremental"`` packs denser: a lane whose block-table
  extension fails stalls (skips committing) until blocks free up, and if
  every lane stalls the newest request is preempted
  (recompute-on-readmit: it re-enters the queue and re-prefills
  prompt+generated at its next admission).

The clock is either ``"wall"`` (live serving) or ``"steps"`` (virtual time
in engine-step units — deterministic, used by tests and trace benchmarks).
"""

from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import dispatch
from repro.configs.base import ArchConfig
from repro.core.sara import SaraDispatcher
from repro.dispatch import SiteRegistry
from repro.models.serving import PAGED_FAMILIES
from repro.obs import (JitWatch, RequestTracker, StepTimeline, TraceRecorder,
                       write_chrome_trace, write_jsonl)
from repro.serving.faults import (OUTCOME_COUNTERS, ChaosConfig,
                                  FaultInjector, fault_rids)
from repro.serving.kv_pool import (KVArena, KVBlockPool, PoolError,
                                   SanitizerError)
from repro.serving.metrics import ServingMetrics
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import ContinuousScheduler, Request
from repro.serving.spec_decode import (SpecDecoder, accept_tokens,
                                       resolve_draft)


def sample_logits(key, logits: jnp.ndarray, temperature: float = 1.0,
                  top_k: int = 0) -> jnp.ndarray:
    """logits: (B, V) -> (B,) int32.  temperature<=0 is greedy argmax;
    top_k>0 masks everything below the k-th logit before sampling."""
    if temperature <= 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        thresh = vals[:, -1:]
        logits = jnp.where(logits < thresh, -1e30, logits)
    return jax.random.categorical(key, logits, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# GEMM-site enumeration (analytic estimate — benchmarks/capacity planning).
# The engine itself no longer consults this: its gemm_plan is read back
# from the dispatch registry, i.e. from the sites that actually traced.
# ---------------------------------------------------------------------------

def gemm_sites(cfg: ArchConfig, m_tokens: int) -> List[Tuple[str, int, int, int]]:
    """The (M, K, N) of each distinct GEMM the model runs on ``m_tokens``
    rows this step (MoE expert GEMMs use the expected routed-row count)."""
    m = max(int(m_tokens), 1)
    d = cfg.d_model
    sites: List[Tuple[str, int, int, int]] = []
    if cfg.attention_type == "gqa":
        sites += [("attn_qkv", m, d, cfg.q_dim + 2 * cfg.kv_dim),
                  ("attn_out", m, cfg.q_dim, d)]
    elif cfg.attention_type == "mla":
        a = cfg.mla
        q_in = a.q_lora_rank or cfg.num_heads * (a.qk_nope_head_dim
                                                 + a.qk_rope_head_dim)
        sites += [("mla_down", m, d,
                   q_in + a.kv_lora_rank + a.qk_rope_head_dim),
                  ("mla_out", m, cfg.num_heads * a.v_head_dim, d)]
    if cfg.moe is not None:
        sites += [("moe_expert",
                   max(m * cfg.moe.experts_per_token, 1), d,
                   2 * cfg.moe.d_ff_expert),
                  ("moe_router", m, d, cfg.moe.num_experts)]
    else:
        sites += [("mlp_up", m, d, 2 * cfg.d_ff),
                  ("mlp_down", m, cfg.d_ff, d)]
    if cfg.ssm is not None:
        sites += [("ssm_proj", m, d, 2 * cfg.ssm.expand * d)]
    sites += [("lm_head", m, d, cfg.vocab_size)]
    return sites


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@dataclass
class EngineConfig:
    """Serving-engine knobs (model-independent; the architecture comes from
    the ``ArchConfig`` the engine is built with).

    The defaults serve small CPU traces; production settings raise
    ``num_slots`` / ``max_len`` / ``num_blocks`` and leave the backend-aware
    ``"auto"`` selectors alone so the same config runs compiled Pallas +
    paged KV on TPU and XLA + dense KV elsewhere.  See ``docs/SERVING.md``
    for the request lifecycle each field participates in.
    """

    num_slots: int = 4
    max_len: int = 96                 # per-slot token capacity (prompt+gen+1)
    block_size: int = 16              # KV pool page size (tokens)
    num_blocks: Optional[int] = None  # KV budget; None = full slot capacity
    buckets: Optional[Sequence[int]] = None   # prefill shapes; None = pow2
    max_prefills_per_step: int = 1    # admissions per engine step
    reserve: str = "full"             # "full" | "incremental"
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    eos_id: Optional[int] = None
    clock: str = "steps"              # "steps" | "wall"
    src_len: int = 0                  # encdec: shared encoder length
    execute: str = "auto"             # GEMM backend: "pallas"|"xla"|"auto"
    dispatcher_mode: str = "oracle"   # recommendation source: "oracle"|"adaptnet"
    adaptnet_dir: Optional[str] = None  # trained ADAPTNET-TPU checkpoint dir
    # KV storage: "paged" = physical page arena + paged flash-decode kernel
    # (attention families); "dense" = stacked per-slot caches + vmapped
    # decode (always used by ssm/hybrid).  "auto" is backend-aware like
    # execute="auto": paged on TPU (where page-granular HBM traffic is the
    # win), dense elsewhere — at CPU-test capacities the paged path's
    # fixed per-step overheads outweigh the rows it skips.
    kv_layout: str = "auto"           # "auto" | "paged" | "dense"
    # Chunked paged prefill: stream each prompt into the arena
    # ``prefill_chunk`` tokens per engine step instead of one padded-bucket
    # call per request.  One ragged batch carries every mid-prefill lane
    # (per-row lengths; short prompts finish in one chunk while long ones
    # keep streaming), KV rows land directly in pages (no dense scratch
    # cache, no bucket-granularity copy), and a long prompt no longer
    # monopolizes a step.  Requires the paged layout and a
    # CHUNKED_PREFILL_FAMILIES family (dense/moe); None keeps the padded
    # bucketed prefill.
    prefill_chunk: Optional[int] = None
    # Cross-request prefix caching (serving/prefix_cache.py): admission
    # matches each prompt's longest cached page prefix, maps those pages
    # into the new request's table (refcounted, copy-on-write on first
    # write) and prefills only the suffix.  Requires prefill_chunk — cache
    # hits admit mid-prompt, and only the chunked path can resume a
    # prefill from a per-lane offset.
    prefix_cache: bool = False
    # Speculative decoding (serving/spec_decode.py): draft ``spec_k``
    # tokens per lane per step with a draft model, verify all of them
    # (plus the pending token) with ONE target pass through the ragged
    # chunked-prefill kernel, and commit the longest matching prefix +
    # one corrected token.  Every committed token is the target verify
    # argmax, so output is bitwise-identical to plain greedy decode.
    # ``spec_draft`` names a registry arch for the draft model, or
    # "self" for self-speculation (shares the target's params — the
    # acceptance-rate upper bound, what the benchmark uses to isolate
    # engine overheads).  Requires prefill_chunk (the verifier IS the
    # chunk program, with logits at every row) and greedy decoding
    # (temperature <= 0).
    spec_draft: Optional[str] = None
    spec_k: int = 4
    # Draft-arena page budget (None = same as the target pool).  Draft
    # KV lives under the same pool economics; a lane whose draft
    # reservation fails is draft-preempted for the step (plain C=1
    # verify, counted in spec_draft_preempts) — a small budget is the
    # test lever for that path.
    spec_draft_blocks: Optional[int] = None
    # Auto-defrag: compact the pool after any step that leaves
    # fragmentation() above this threshold (None = manual defrag() only).
    defrag_threshold: Optional[float] = None
    # Observability (repro.obs): counters/gauges are ALWAYS on (a dict
    # update per event); ``trace=True`` additionally records span/instant
    # events — request lifecycle, step phases, dispatch/compile/arena —
    # into a ring buffer of ``trace_capacity`` events, exportable via
    # ``export_trace()`` (serve.py --trace-out).
    trace: bool = False
    trace_capacity: int = 65536
    # KV-arena sanitizer (serving/kv_pool.py): poison freed pages with
    # NaN, stamp every page with a generation counter (bumped on each
    # re-allocation) and validate decode block tables against the stamps
    # captured at table-build time, run the pool invariant check every
    # step, and audit refcount/pin leaks when ``run()`` drains.  Traps
    # use-after-free through stale tables as :class:`SanitizerError`
    # instead of silent garbage logits.  Debug/test mode — poisoning
    # rewrites one arena page per freed block.
    sanitize: bool = False
    # Chaos harness (serving/faults.py): deterministic seed-driven fault
    # injection — simulated pool OOMs, poisoned pages (requires
    # ``sanitize``), forced lane stalls, forced mid-prefill preemptions.
    # None / all-zero probabilities = no injection.
    chaos: Optional[ChaosConfig] = None
    # Livelock guard: preempt/readmit cycles a request may consume before
    # the engine fails it (outcome "failed") instead of requeueing again.
    preempt_budget: int = 3
    # Step error boundary: an UNattributable PoolError/SanitizerError
    # (no ``rids`` — cannot be pinned on one request) is retried this
    # many times with exponential backoff (``retry_backoff_s`` doubling
    # per attempt, slept only under the wall clock) before surfacing.
    max_step_retries: int = 2
    retry_backoff_s: float = 0.05
    # Crash safety: when set, ``snapshot()`` / auto-snapshots (every
    # ``snapshot_every`` steps, 0 = manual only) write a restorable
    # engine checkpoint through checkpoint/manager; a fresh engine with
    # the same configs resumes mid-trace via ``restore()``.
    snapshot_dir: Optional[str] = None
    snapshot_every: int = 0


class _PagedFeed(NamedTuple):
    """One paged decode's inputs, built on the host before the call:
    ``args`` after ``params`` (tokens, state, arena, tables, lengths,
    write mask), and what the step's accounting and the sanitizer read
    back."""

    args: tuple
    width: int
    kv_read: int
    rids: list
    tables: np.ndarray
    gens: Optional[np.ndarray]


class ServingEngine:
    """Continuous-batching inference engine over the repro model stack.

    Construct with an ``ArchConfig`` (what model) and an ``EngineConfig``
    (how to serve it); ``submit()`` requests and drive ``step()`` until it
    returns False, or use ``run()`` for a whole request set.  Telemetry
    comes out of ``summary()`` / ``metrics`` / ``dispatch_stats()`` and
    the executed per-site tile plan out of ``gemm_plan``.  See the module
    docstring for the execution model and ``docs/SERVING.md`` for the
    request lifecycle (admit -> [chunked] prefill -> paged decode ->
    retire/preempt) and the KV page accounting."""

    def __init__(self, cfg: ArchConfig, engine: EngineConfig = None,
                 params=None, dispatcher: Optional[SaraDispatcher] = None):
        from repro.models.api import build_model

        self.cfg = cfg
        self.ecfg = engine or EngineConfig()
        self.model = build_model(cfg)
        self.params = params if params is not None \
            else self.model.init(jax.random.PRNGKey(self.ecfg.seed))
        self.dispatcher = dispatcher if dispatcher is not None \
            else self._build_dispatcher(self.ecfg)
        self.metrics = ServingMetrics()
        # observability: one recorder for every layer (engine steps,
        # request spans, dispatch/compile/arena events); counters always
        # on, span recording behind EngineConfig.trace
        self.obs = TraceRecorder(capacity=self.ecfg.trace_capacity,
                                 spans=self.ecfg.trace)
        self.req_spans = RequestTracker(self.obs)
        self.timeline = StepTimeline(self.obs)

        e = self.ecfg
        layout = e.kv_layout
        if layout == "auto":
            layout = ("paged" if cfg.family in PAGED_FAMILIES
                      and jax.default_backend() == "tpu" else "dense")
        if layout not in ("paged", "dense"):
            raise ValueError(f"unknown kv_layout {e.kv_layout!r}")
        if layout == "paged" and cfg.family not in PAGED_FAMILIES:
            raise ValueError(
                f"family {cfg.family!r} keeps recurrent state in the dense "
                f"slot layout; kv_layout='paged' supports {PAGED_FAMILIES}")
        self.kv_layout = layout

        self.prefill_chunk = e.prefill_chunk
        if self.prefill_chunk is not None:
            from repro.models.serving import CHUNKED_PREFILL_FAMILIES
            if self.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            # no prompt exceeds max_len, so a larger chunk would only pad
            # the batch with dead query rows the kernel still computes
            self.prefill_chunk = min(self.prefill_chunk, e.max_len)
            if layout != "paged":
                raise ValueError(
                    "prefill_chunk streams prompts directly into KV pages; "
                    "it requires kv_layout='paged' (got "
                    f"{self.kv_layout!r})")
            if cfg.family not in CHUNKED_PREFILL_FAMILIES:
                raise ValueError(
                    f"family {cfg.family!r} keeps the bucketed prefill "
                    f"(chunked prefill supports {CHUNKED_PREFILL_FAMILIES})")

        # vlm frontend rows share the per-slot KV cache; under the paged
        # layout they live in pool pages, so reservations must cover them
        self._fe_rows = (cfg.frontend.num_tokens
                         if cfg.family == "vlm" else 0)
        self._cache_len = e.max_len + self._fe_rows
        row_overhead = self._fe_rows if layout == "paged" else 0
        blocks_per_slot = -(-(e.max_len + row_overhead) // e.block_size)
        num_blocks = (e.num_blocks if e.num_blocks is not None
                      else e.num_slots * blocks_per_slot)
        self.pool = KVBlockPool(num_blocks, e.block_size,
                                sanitize=e.sanitize)
        self.pool.attach_recorder(self.obs)
        self._leak_audit: Dict[str, int] = {}
        self.prefix_cache: Optional[PrefixCache] = None
        if e.prefix_cache:
            if self.prefill_chunk is None:
                raise ValueError(
                    "prefix_cache requires prefill_chunk: a cache hit "
                    "admits a request mid-prompt, and only the chunked "
                    "prefill path can resume from a per-lane offset")
            self.prefix_cache = PrefixCache(self.pool, recorder=self.obs)
        self.sched = ContinuousScheduler(
            e.num_slots, self.pool,
            max_prefills_per_step=e.max_prefills_per_step, reserve=e.reserve,
            token_overhead=row_overhead, prefill_chunk=self.prefill_chunk,
            tracker=self.req_spans, prefix_cache=self.prefix_cache,
            metrics=self.metrics)
        # every submitted request, live or terminal — how the step error
        # boundary maps a fault's rids back to Request objects
        self.requests: Dict[str, Request] = {}
        self.chaos: Optional[FaultInjector] = None
        if e.chaos is not None and e.chaos.any_enabled():
            if e.chaos.poison_p > 0 and not e.sanitize:
                raise ValueError(
                    "chaos.poison_p needs sanitize=True: the sanitizer's "
                    "poison scan is what detects (and contains) the "
                    "injected page — without it the fault surfaces as "
                    "silent garbage tokens")
            self.chaos = FaultInjector(e.chaos, recorder=self.obs)
        self._step_idx = 0               # monotonic, drives chaos schedules
        # analytic per-token prefill cost (2*M*K*N over every GEMM site at
        # M=1, layer sites times the stack depth) — what each cache-hit
        # token avoids recomputing; feeds metrics.prefill_flops_saved
        self._flops_per_token = float(sum(
            2 * m * k * n * (1 if name == "lm_head" else cfg.num_layers)
            for name, m, k, n in gemm_sites(cfg, 1)))
        self._last_tok = np.zeros((e.num_slots, 1), np.int32)
        self._prefill = JitWatch(jax.jit(self.model.prefill), "prefill",
                                 self.obs)

        if layout == "paged":
            # physical page arena (pool pages + one write-discard scratch
            # page for masked lanes), per-slot row counts, and the slot-
            # stacked residue that stays dense (encdec cross K/V).  The
            # scratch prefill cache is rounded up to whole pages so the
            # bucket-granularity arena scatter can always slice full blocks.
            # (under this layout row_overhead == self._fe_rows, so
            # blocks_per_slot already covers the full _cache_len rows)
            self._max_blocks_per_slot = blocks_per_slot
            self._prefill_rows = self._max_blocks_per_slot * e.block_size
            self.arena = KVArena(
                self.model.init_paged_arena(num_blocks + 1, e.block_size),
                e.block_size)
            self.pool.bind_arena(self.arena)
            self._state = self.model.init_paged_state(e.num_slots,
                                                      src_len=e.src_len)
            self._kv_rows = np.zeros((e.num_slots,), np.int32)
            # every step program donates the arena it is handed: the new
            # rows are written into it in place and the result replaces
            # arena.leaves at once, so no path reads a donated leaf.  A MoE
            # model's step programs also return each MoE layer's expert
            # load inside their first output (``_moe_stats``)
            self._moe_loads = cfg.moe is not None
            loads = dict(moe_loads=True) if self._moe_loads else {}
            self._paged_decode = JitWatch(
                jax.jit(functools.partial(self.model.paged_decode_step,
                                          **loads), donate_argnums=(3,)),
                "paged_decode", self.obs)
            self._paged_write = JitWatch(
                jax.jit(self.model.paged_prefill_write, donate_argnums=(0,)),
                "paged_write", self.obs)
            if self.prefill_chunk is not None:
                self._chunk_prefill = JitWatch(
                    jax.jit(functools.partial(self.model.paged_prefill_step,
                                              **loads),
                            donate_argnums=(2,)), "chunk_prefill", self.obs)
            self._cache = None
        else:
            # stacked per-slot caches: leading axis = slot, lane batch=1
            self._prefill_rows = self._cache_len
            proto = self.model.init_cache(1, self._cache_len,
                                          src_len=e.src_len)
            self._cache = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    a, (e.num_slots,) + a.shape).copy(), proto)
            self._decode = JitWatch(
                jax.jit(jax.vmap(self.model.decode_step,
                                 in_axes=(None, 0, 0))), "decode", self.obs)
        self.spec: Optional[SpecDecoder] = None
        if e.spec_draft is not None:
            if self.prefill_chunk is None:
                raise ValueError(
                    "spec_draft requires prefill_chunk: the verify pass IS "
                    "the ragged chunked-prefill kernel (spec_k + 1 rows "
                    "per lane through block tables)")
            if e.temperature > 0.0:
                raise ValueError(
                    "spec_draft requires greedy decoding (temperature <= "
                    "0): the accept rule compares drafts against the "
                    "verify argmax, which is only the sampling rule when "
                    "greedy")
            if e.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            draft_cfg, draft_params = resolve_draft(
                cfg, self.params, e.spec_draft, e.seed)
            self.spec = SpecDecoder(
                draft_cfg, draft_params, num_slots=e.num_slots,
                block_size=e.block_size,
                num_blocks=(e.spec_draft_blocks
                            if e.spec_draft_blocks is not None
                            else num_blocks),
                max_blocks_per_slot=self._max_blocks_per_slot,
                chunk=self.prefill_chunk, spec_k=e.spec_k,
                recorder=self.obs)
            self._spec_verify = JitWatch(
                jax.jit(functools.partial(self.model.paged_prefill_step,
                                          every_row=True),
                        donate_argnums=(2,)), "spec_verify", self.obs)
        # what one masked-dense decode step would stream: every slot's full
        # capacity (recurrent-state families have no KV rows to speak of)
        self._dense_kv_rows = (e.num_slots * self._cache_len
                               if cfg.attention_type != "none" else 0)
        self._key = jax.random.PRNGKey(e.seed + 1)
        self._vtime = 0.0
        self._t0 = time.time()
        # registry-backed executed-plan bookkeeping: each traced entry point
        # (one per prefill bucket + one for the vmapped decode) records its
        # sites under a scope; _dispatch() reads the plan back (memoized per
        # scope) instead of re-running any recommendation sweep.  The
        # recorder hook turns each record into a "dispatch" trace event.
        self.registry = SiteRegistry(recorder=self.obs)
        self.gemm_plan: Dict[str, str] = {}
        self.plan_changes = 0
        self._plan_memo: Dict[str, Dict[str, str]] = {}

    @staticmethod
    def _build_dispatcher(ecfg: EngineConfig) -> SaraDispatcher:
        if ecfg.dispatcher_mode == "adaptnet":
            if not ecfg.adaptnet_dir:
                raise ValueError(
                    "dispatcher_mode='adaptnet' needs adaptnet_dir: a "
                    "checkpoint saved by `python -m repro.launch."
                    "train_adaptnet --out <dir>`")
            return SaraDispatcher.from_checkpoint(ecfg.adaptnet_dir)
        if ecfg.dispatcher_mode != "oracle":
            raise ValueError(f"unknown dispatcher_mode "
                             f"{ecfg.dispatcher_mode!r}")
        return SaraDispatcher()

    # -- time -----------------------------------------------------------------
    def now(self) -> float:
        if self.ecfg.clock == "steps":
            return self._vtime
        return time.time() - self._t0

    # -- SARA dispatch --------------------------------------------------------
    @contextlib.contextmanager
    def _dispatch_scope(self, scope: str):
        """Install this engine's dispatch policy + registry scope around a
        jitted call: if the call traces (first time this shape is seen),
        every GEMM site records its executed configuration under ``scope``."""
        with dispatch.use(self.dispatcher, execute=self.ecfg.execute,
                          registry=self.registry), \
                self.registry.scope(scope):
            yield

    def _dispatch(self, scope: str) -> None:
        """Adopt the executed plan of ``scope`` (memoized per scope — the
        scope name encodes the token count, so an unchanged batch shape is
        a dict lookup, not a recommendation sweep)."""
        plan = self._plan_memo.get(scope)
        if plan is None:
            plan = self.registry.plan(scope)
            self._plan_memo[scope] = plan
        if plan != self.gemm_plan:
            self.plan_changes += 1       # a real reconfiguration
            self.gemm_plan = plan

    # -- buckets --------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        if self.cfg.family in ("ssm", "hybrid"):
            return n                   # recurrent state: no padded prefill
        b = None
        if self.ecfg.buckets:
            fits = [x for x in sorted(self.ecfg.buckets) if x >= n]
            if fits:
                b = fits[0]
        if b is None:
            b = 16
            while b < n:
                b *= 2
        # prefill writes `bucket` KV rows, so never pad past the slot arena
        # (submit() guarantees n itself fits)
        return max(n, min(b, self.ecfg.max_len))

    # -- request lifecycle ----------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.prompt_len < 1:
            raise ValueError(f"request {req.rid}: prompt must be non-empty "
                             "(there is no last-token position to sample "
                             "the first token from)")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be >= 1 "
                             "(prefill always yields the first token)")
        need = req.prompt_len + req.max_new_tokens + 1
        if need > self.ecfg.max_len:
            raise ValueError(f"request {req.rid} needs {need} tokens > "
                             f"max_len {self.ecfg.max_len}")
        need_rows = need + self.sched.token_overhead
        if self.pool.blocks_for(need_rows) > self.pool.num_blocks:
            raise ValueError(
                f"request {req.rid} needs {self.pool.blocks_for(need_rows)} "
                f"KV blocks > pool total {self.pool.num_blocks}; it could "
                "never be admitted")
        if req.eos_id is None:
            req.eos_id = self.ecfg.eos_id
        self.sched.submit(req)
        self.requests[req.rid] = req

    def _slot_snapshot(self, slot: int):
        return jax.tree_util.tree_map(lambda a: a[slot], self._cache)

    def _slot_restore(self, slot: int, snap) -> None:
        self._cache = jax.tree_util.tree_map(
            lambda big, one: big.at[slot].set(one), self._cache, snap)

    def _do_prefill(self, req: Request) -> None:
        e, cfg = self.ecfg, self.cfg
        context = req.context()
        n = int(context.shape[0])
        bucket = self.bucket_for(n)
        with self.timeline.phase("inputs"):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = context
            batch = {"tokens": jnp.asarray(toks)}
            if cfg.family == "vlm":
                batch["patch_embeds"] = jnp.asarray(
                    (req.extras or {}).get(
                        "patch_embeds",
                        np.zeros((1, cfg.frontend.num_tokens,
                                  cfg.frontend.feature_dim), np.float32)),
                    jnp.dtype(cfg.compute_dtype))
            if cfg.family == "encdec":
                batch["src_features"] = jnp.asarray(
                    (req.extras or {}).get(
                        "src_features",
                        np.zeros((1, e.src_len, cfg.frontend.feature_dim),
                                 np.float32)),
                    jnp.dtype(cfg.compute_dtype))
            fresh = self.model.init_cache(1, self._prefill_rows,
                                          src_len=e.src_len)

        scope = f"prefill:m{bucket}"
        t0 = time.time()
        with self._dispatch_scope(scope), \
                self.timeline.phase("prefill", rid=req.rid, bucket=bucket):
            logits, new_cache = self._prefill(
                self.params, batch, fresh, jnp.int32(n))
        with self.timeline.phase("sync"):
            logits, new_cache = jax.block_until_ready((logits, new_cache))
        dt = time.time() - t0
        self._dispatch(scope)
        with self.timeline.phase("sample"):
            self._key, k = jax.random.split(self._key)
            tok = int(np.asarray(sample_logits(
                k, logits, e.temperature, e.top_k))[0])
        with self.timeline.phase("commit"):
            self.req_spans.on_prefill_chunk(req.rid, n, dt, bucket=bucket)
            if self.kv_layout == "paged":
                # commit the prefilled KV rows into this request's pool
                # pages (bucket-granularity scatter); the scratch dense
                # cache is gone after this — only pages + the row count
                # persist per slot
                rows = n + self._fe_rows
                nblk = self.pool.blocks_for(rows)
                table = self.pool.table(req.rid).blocks
                # saralint: ok[cow-gate] bucketed prefill writes only freshly alloc'd pages; this path never coexists with prefix-cache sharing (cache requires prefill_chunk)
                self.arena.leaves = self._paged_write(
                    self.arena.leaves, new_cache["layers"],
                    jnp.asarray(table[:nblk], jnp.int32))
                self._kv_rows[req.slot] = rows
                self.metrics.on_prefill(
                    n, dt, kv_write_rows=nblk * e.block_size,
                    kv_write_rows_padded=bucket + self._fe_rows)
                if cfg.family == "encdec":
                    self._state["cross_k"] = self._state["cross_k"].at[
                        :, req.slot].set(new_cache["cross_k"][:, 0])
                    self._state["cross_v"] = self._state["cross_v"].at[
                        :, req.slot].set(new_cache["cross_v"][:, 0])
            else:
                self.metrics.on_prefill(n, dt)
                self._slot_restore(req.slot, new_cache)
            req.prefill_pos = n
            req.prefilling = False
            first = not req.generated
            req.generated.append(tok)
            self._last_tok[req.slot, 0] = tok
            if first and req.t_first_token < 0:
                req.t_first_token = self.now()
                self.metrics.on_first_token(req.arrival_time,
                                            req.t_first_token)
                self.req_spans.on_first_token(req.rid)
            if req.done():
                self._retire(req)

    def _do_chunk_prefills(self) -> None:
        """One chunked-prefill step over every mid-prefill lane.

        The batch is ragged: each lane contributes up to ``prefill_chunk``
        of its remaining context (per-row lengths), lanes with nothing to
        stream (or whose page extension stalled) ride along with a zero
        chunk — their rows write to the arena's trash page and their
        logits row is ignored.  KV rows land directly in the lane's pool
        pages; there is no dense scratch cache and no bucket-granularity
        copy, so prefill writes scale with real prompt tokens.  A lane
        whose final chunk lands here samples its first token (TTFT is
        stamped per chunk completion, so short prompts admitted alongside
        long ones stop waiting on the long prefill)."""
        e = self.ecfg
        C, S = self.prefill_chunk, e.num_slots
        lanes = {s: r for s, r in self.sched.active.items() if r.prefilling}
        if not lanes:
            return
        with self.timeline.phase("inputs"):
            toks = np.zeros((S, C), np.int32)
            chunk = np.zeros((S,), np.int32)
            for slot, req in sorted(lanes.items()):
                n = min(C, req.context_len - req.prefill_pos)
                # the coming chunk writes n KV rows: the block table must
                # cover them (chunk-incremental reservation extends here; a
                # failed extension stalls the lane's prefill until pages
                # free up)
                if not self.sched.grow(req, req.prefill_pos + n):
                    self.metrics.stalls += 1
                    continue
                if not self._cow_chunk_pages(req, req.prefill_pos, n):
                    self.metrics.stalls += 1
                    continue
                ctx = req.context()
                toks[slot, :n] = ctx[req.prefill_pos:req.prefill_pos + n]
                chunk[slot] = n
            if not chunk.any():
                return                   # every prefilling lane stalled
            kv = np.where(chunk > 0, self._kv_rows, 0).astype(np.int32)
            # fixed table width -> the chunk step compiles exactly once.
            # Unlike decode (where narrow tables ARE the read-scaling win),
            # a chunk must attend over its lane's whole prefix anyway, and
            # dead table columns cost (almost) nothing in the kernel: the
            # DMA is elided for repeated trailing ids and `j*bs < kv_len`
            # skips the compute.
            width = self._max_blocks_per_slot
            rids = [lanes[s].rid if chunk[s] > 0 else None for s in range(S)]
            tables = self.pool.dense_block_table(rids, width)
            args = (jnp.asarray(toks), self.arena.leaves, jnp.asarray(tables),
                    jnp.asarray(kv), jnp.asarray(chunk))

        scope = "prefill_chunk"
        t0 = time.time()
        with self._dispatch_scope(scope), \
                self.timeline.phase("prefill_chunk",
                                    lanes=int((chunk > 0).sum()),
                                    live_rows=int(chunk.sum()),
                                    rows=S * C):
            logits, leaves = self._chunk_prefill(self.params, *args)
        with self.timeline.phase("sync") as sync:
            logits, leaves = jax.block_until_ready((logits, leaves))
            logits = self._moe_stats(sync, logits)
        dt = time.time() - t0
        self.arena.leaves = leaves
        self._dispatch(scope)

        # only a lane whose FINAL chunk landed this step consumes logits;
        # skip the key split + sampling entirely when none did (keeps the
        # hot loop lean and the RNG stream free of discarded draws)
        sampled = None
        if any(chunk[s] and r.prefill_pos + chunk[s] >= r.context_len
               for s, r in lanes.items()):
            with self.timeline.phase("sample"):
                self._key, k = jax.random.split(self._key)
                sampled = np.asarray(sample_logits(
                    k, logits, e.temperature, e.top_k))
        with self.timeline.phase("commit"):
            self._commit_chunks(lanes, chunk, dt, sampled)

    def _commit_chunks(self, lanes: Dict[int, Request], chunk: np.ndarray,
                       dt: float, sampled: Optional[np.ndarray]) -> None:
        """Advance every lane by its chunk; a lane whose prompt completed
        takes its first token from ``sampled``, is indexed in the prefix
        cache, and retires if that token ends it."""
        e = self.ecfg
        for slot, req in sorted(lanes.items()):
            if chunk[slot] > 0:
                self.req_spans.on_prefill_chunk(req.rid, int(chunk[slot]),
                                                dt, pos=req.prefill_pos)
        total = int(chunk.sum())
        # padded-bucket equivalent accrues proportionally per chunk
        # (telescoping integer shares that sum to bucket_for(ctx) over a
        # complete stream), so a request preempted mid-prefill has
        # contributed to both sides of the reduction ratio symmetrically —
        # and contributes again when it re-streams after readmission
        padded = 0
        for slot, req in lanes.items():
            n = int(chunk[slot])
            if n == 0:
                continue
            ctx, pos = req.context_len, req.prefill_pos
            b = self.bucket_for(ctx)
            padded += (b * (pos + n)) // ctx - (b * pos) // ctx
        self.metrics.on_prefill(total, dt, kv_write_rows=total,
                                kv_write_rows_padded=padded)
        for slot, req in sorted(lanes.items()):
            n = int(chunk[slot])
            if n == 0:
                continue
            req.prefill_pos += n
            self._kv_rows[slot] += n
            if req.prefill_pos < req.context_len:
                continue                 # more chunks to stream next step
            req.prefilling = False
            if self.prefix_cache is not None:
                # index the finished prompt's fully-covered pages; its
                # content is frozen by construction from here on (decode
                # only appends rows >= prompt_len) so pinning is safe.
                # Spans already cached keep their existing page.
                nfull = req.prompt_len // e.block_size
                if nfull:
                    self.prefix_cache.insert(
                        req.prompt, self.pool.table(req.rid).blocks[:nfull])
            tok = int(sampled[slot])
            first = not req.generated
            req.generated.append(tok)
            self._last_tok[slot, 0] = tok
            if first and req.t_first_token < 0:
                req.t_first_token = self.now()
                self.metrics.on_first_token(req.arrival_time,
                                            req.t_first_token)
                self.req_spans.on_first_token(req.rid)
            if req.done():
                self._retire(req)

    def _cow_chunk_pages(self, req: Request, pos: int, n: int) -> bool:
        """Copy-on-write gate for the pages the coming chunk writes (rows
        ``[pos, pos + n)``).  A cache-hit lane's first recomputed token can
        land inside a shared or pinned page (the minus-one resume offset,
        or a readmitted lane re-streaming over pages it donated to the
        cache), and writing through the arena would corrupt every other
        owner — so each target page is made private first.  A COW that
        cannot get a free page evicts cache entries; if the pool is still
        dry the lane stalls exactly like a failed ``grow()``."""
        if self.prefix_cache is None or n <= 0:
            return True
        bs = self.ecfg.block_size
        for pi in range(pos // bs, (pos + n - 1) // bs + 1):
            while True:
                try:
                    self.pool.ensure_writable(req.rid, pi)
                    break
                except PoolError:
                    # ensure_writable only raises when the free list is
                    # empty; any successful eviction guarantees progress
                    if self.prefix_cache.evict(1) == 0:
                        req.stalled = True
                        return False
        req.stalled = False
        return True

    def _retire(self, req: Request) -> None:
        slot = req.slot
        self.sched.retire(req, self.now())
        req.outcome = "done"
        self.metrics.on_retire(req.arrival_time, req.t_admit, req.t_done,
                               in_deadline=not req.expired_at(req.t_done))
        if self.spec is not None:
            self.spec.release(req.rid)
        if self.kv_layout == "paged":
            self._kv_rows[slot] = 0      # pages already back in the free list

    def _finish(self, req: Request, outcome: str, reason: str = "") -> None:
        """Terminal-failure bookkeeping shared by fault containment, the
        scheduler's deadline/cancel sweep, and preempt-budget exhaustion.
        When the scheduler already closed the request (``plan.finished``
        hands them over with ``outcome`` set and slot/pages/span gone)
        only the engine-side counters remain; otherwise the scheduler
        teardown runs here too."""
        slot = req.slot
        if not req.outcome:
            self.sched.finish(req, outcome, self.now(), reason=reason)
        self.metrics.on_finish(req.outcome)
        self.obs.count(OUTCOME_COUNTERS[req.outcome], 1)
        if self.spec is not None:
            self.spec.release(req.rid)
        if slot >= 0:
            self._last_tok[slot, 0] = 0
            if self.kv_layout == "paged":
                self._kv_rows[slot] = 0

    def _preempt(self, victim: Request) -> None:
        """Preempt one admitted request (recompute-on-readmit) — unless
        its preemption budget is spent, in which case it fails instead of
        requeueing: a victim the pool can never hold would otherwise
        cycle preempt->readmit->stall->preempt forever (livelock), and
        each cycle re-prefills its whole context."""
        victim.preempt_count += 1
        if victim.preempt_count > self.ecfg.preempt_budget:
            self.obs.count("preempt_budget_exhausted", 1)
            self._finish(victim, "failed",
                         reason=f"preemption budget "
                                f"({self.ecfg.preempt_budget}) exhausted")
            return
        slot = victim.slot
        self.sched.preempt(victim)
        self.metrics.preemptions += 1
        if self.spec is not None:
            self.spec.release(victim.rid)
        self._last_tok[slot, 0] = 0
        if self.kv_layout == "paged":
            self._kv_rows[slot] = 0

    def _preempt_newest(self) -> None:
        """Every lane is stalled: preempt the newest request so the rest can
        make progress.  Its pages free immediately — under the paged layout
        nothing is copied, the block table entries just return to the pool —
        and it re-enters the queue head to re-prefill prompt+generated at
        the next admission.  ``sched.preempt`` (not ``retire``) keeps the
        request's lifecycle fields clean: no ``t_done`` is stamped until it
        actually finishes."""
        self._preempt(max(self.sched.active.values(),
                          key=lambda r: r.t_admit))

    # -- chaos injection points -----------------------------------------------
    def _inject_admission_chaos(self) -> None:
        """Post-schedule chaos: force-preempt a mid-prefill lane
        (exercising recompute-on-readmit and the preemption budget), then
        possibly raise a simulated pool OOM attributed to one live lane —
        the containment path's bread and butter."""
        step = self._step_idx
        mid_prefill = [r for _, r in sorted(self.sched.active.items())
                       if r.prefilling]
        victim = self.chaos.preempt(step, mid_prefill)
        if victim is not None:
            self._preempt(victim)
        live = [r for _, r in sorted(self.sched.active.items())]
        victim = self.chaos.pool_oom(step, live)
        if victim is not None:
            raise self.chaos.oom_error(step, victim)

    def _inject_decode_chaos(self, active: Dict[int, Request],
                             snaps: Dict) -> None:
        """Pre-decode chaos: forced lane stalls (writes land in the trash
        page, the token replays — dense lanes get the rollback snapshot a
        real stall would have taken) and, under paged+sanitize,
        NaN-poisoning one fully-written exclusively-owned page of a lane
        so the post-decode poison scan must trap and attribute it."""
        step = self._step_idx
        lanes = [r for _, r in sorted(active.items())]
        for req in self.chaos.stall_lanes(step, lanes):
            if not req.stalled:
                req.stalled = True
                self.metrics.stalls += 1
                if self.kv_layout == "dense" and req.slot not in snaps:
                    snaps[req.slot] = self._slot_snapshot(req.slot)
        if self.kv_layout != "paged" or self.ecfg.chaos.poison_p <= 0:
            return
        bs = self.ecfg.block_size
        cands = []
        for slot, req in sorted(active.items()):
            if req.stalled:
                continue
            full = int(self._kv_rows[slot]) // bs
            pages = [b for b in self.pool.table(req.rid).blocks[:full]
                     if self.pool.refcount(b) == 1
                     and self.pool.pincount(b) == 0]
            cands.append((req, pages))
        hit = self.chaos.poison(step, cands)
        if hit is not None:
            _, page = hit
            self.arena.poison_page(page)

    # -- main loop ------------------------------------------------------------
    def step(self) -> bool:
        """One engine step: admissions + prefill work (one padded-bucket
        call per admitted request, or one ragged chunk batch over every
        mid-prefill lane under chunked prefill), then one batched decode
        over the fully-prefilled lanes.  Returns False when there is
        nothing left to do."""
        if self.sched.idle():
            return False
        retries = max(0, self.ecfg.max_step_retries)
        delay = self.ecfg.retry_backoff_s
        for attempt in range(retries + 1):
            fault = None
            stalls, preempts = self.metrics.stalls, self.metrics.preemptions
            self.timeline.begin()
            try:
                self._step_body()
                thr = self.ecfg.defrag_threshold
                if thr is not None and self.pool.fragmentation() > thr:
                    self.obs.count("kv_defrag_auto", 1)
                    self.defrag()
            except (PoolError, SanitizerError) as exc:
                # step error boundary: decide below whether this is one
                # request's fault or engine-level trouble — either way
                # the timeline closes cleanly first
                fault = exc
            finally:
                e = self.ecfg
                self.obs.gauge("kv_pages_in_use", self.pool.num_in_use)
                self.obs.gauge("kv_fragmentation", self.pool.fragmentation())
                self.obs.gauge("slot_occupancy",
                               len(self.sched.active) / e.num_slots)
                self.timeline.end(
                    active=len(self.sched.active),
                    waiting=self.sched.pending(),
                    stalls=self.metrics.stalls - stalls,
                    preempts=self.metrics.preemptions - preempts)
            if fault is None:
                break
            if self._contain_fault(fault):
                break                    # victims failed; engine lives on
            if attempt >= retries:
                raise fault              # unattributable and out of retries
            self.obs.count("engine_step_retries", 1)
            self.obs.instant("fault", "step_retry", track="faults",
                             attempt=attempt + 1,
                             error=type(fault).__name__)
            if self.ecfg.clock == "wall" and delay > 0:
                time.sleep(delay)        # virtual clocks retry immediately
            delay *= 2
        if self.ecfg.sanitize:
            # full invariant sweep every step: refcount drift and
            # free-list corruption surface at the step that caused them,
            # not at teardown
            self.pool.check()
            if self.spec is not None:
                self.spec.check()
            self.obs.count("kv_sanitize_checks", 1)
        self._vtime += 1.0
        self._step_idx += 1
        if self.ecfg.snapshot_dir and self.ecfg.snapshot_every > 0 \
                and self._step_idx % self.ecfg.snapshot_every == 0:
            self.snapshot()
        return True

    def _contain_fault(self, fault: Exception) -> bool:
        """Fail exactly the request(s) a step fault names instead of the
        whole engine.  Returns True when the fault was attributed to at
        least one live request — its pages free, its span closes with
        outcome ``failed``, and surviving lanes simply replay their
        pending token next step (the raise always precedes token commit,
        so no generated sequence observes the abandoned step)."""
        victims = [self.requests[rid] for rid in fault_rids(fault)
                   if rid in self.requests
                   and not self.requests[rid].outcome]
        if not victims:
            return False
        self.obs.count("faults_contained", len(victims))
        for req in victims:
            self.obs.instant("fault", "contained", track="faults",
                             rid=req.rid, error=type(fault).__name__)
            self._finish(req, "failed", reason=str(fault)[:200])
        return True

    def _step_body(self) -> None:
        with self.timeline.phase("schedule"):
            plan = self.sched.plan(self.now())
        # requests the scheduling pass terminated (expired/shed/cancelled):
        # the scheduler already tore down slot/pages/span, the counters and
        # lane arrays are the engine's side
        for req in plan.finished:
            self._finish(req, req.outcome)
        for req in plan.prefills:
            if self.kv_layout == "paged":
                # reset lane bookkeeping on EVERY admission — a lane whose
                # previous occupant left through containment or the
                # deadline sweep never zeroed its row count, and chunked
                # prefill extends with `+=` from whatever is here.  Cache
                # hits resume at the cached offset (prefill_pos), misses
                # at 0.
                self._kv_rows[req.slot] = req.prefill_pos
            if req.cached_prefix_tokens:
                self.metrics.on_cache_hit(req.cached_prefix_tokens,
                                          req.cached_pages,
                                          self._flops_per_token)
                self.req_spans.on_cache_hit(req.rid,
                                            tokens=req.cached_prefix_tokens,
                                            pages=req.cached_pages)
        if self.chaos is not None:
            self._inject_admission_chaos()
        if self.prefill_chunk is not None:
            self._do_chunk_prefills()
        else:
            # every still-prefilling active lane, not just this plan's
            # admissions: an aborted step (contained fault between
            # admission and prefill) leaves admitted-but-unprefilled
            # lanes behind, and they must prefill on the retry
            for req in [r for _, r in sorted(self.sched.active.items())
                        if r.prefilling]:
                self._do_prefill(req)

        # a request can finish at prefill (first token == budget/EOS) and
        # chunked lanes may still be mid-prefill, so re-check the planned
        # decode slots against the live, fully-prefilled set
        active = {s: self.sched.active[s] for s in plan.decode_slots
                  if s in self.sched.active
                  and not self.sched.active[s].prefilling}
        if active and self.spec is not None:
            self._spec_decode_step(active)
        elif active:
            snaps = {}
            with self.timeline.phase("inputs"):
                # decide stalls BEFORE decoding: the coming step writes the
                # KV of each lane's pending token, so its block table must
                # cover prompt + generated tokens
                for slot, req in active.items():
                    if not self.sched.grow(
                            req, req.prompt_len + len(req.generated)):
                        self.metrics.stalls += 1
                        if self.kv_layout == "dense":
                            snaps[slot] = self._slot_snapshot(slot)
                if self.chaos is not None:
                    # after grow() (which clears stalled on success), so a
                    # forced stall survives into this step's decode mask
                    self._inject_decode_chaos(active, snaps)
                if self.kv_layout == "paged":
                    feed = self._paged_decode_inputs(active)
                else:
                    toks = jnp.asarray(self._last_tok)[:, :, None]  # (S,1,1)
            if self.kv_layout == "paged":
                logits, dt = self._decode_paged(active, feed)
                kv_read = feed.kv_read
            else:
                t0 = time.time()
                with self._dispatch_scope("decode"), \
                        self.timeline.phase("decode", lanes=len(active)):
                    logits, cache = self._decode(
                        self.params, toks, self._cache)
                with self.timeline.phase("sync"):
                    logits, self._cache = jax.block_until_ready(
                        (logits, cache))
                dt = time.time() - t0
                logits = logits[:, 0, :]
                kv_read = self._dense_kv_rows
            self._dispatch("decode")
            with self.timeline.phase("sample"):
                self._key, k = jax.random.split(self._key)
                sampled = np.asarray(sample_logits(
                    k, logits, self.ecfg.temperature, self.ecfg.top_k))
            with self.timeline.phase("commit"):
                committed = 0
                for slot, req in sorted(active.items()):
                    if req.stalled:
                        # the lane replays this token once the pool can
                        # cover it; paged lanes wrote nothing (trash page),
                        # dense lanes roll back to the pre-step snapshot
                        if self.kv_layout == "dense":
                            self._slot_restore(slot, snaps[slot])
                        continue
                    req.generated.append(int(sampled[slot]))
                    self._last_tok[slot, 0] = req.generated[-1]
                    if self.kv_layout == "paged":
                        self._kv_rows[slot] += 1
                    committed += 1
                    if req.t_first_token < 0:
                        req.t_first_token = self.now()
                        self.metrics.on_first_token(req.arrival_time,
                                                    req.t_first_token)
                        self.req_spans.on_first_token(req.rid)
                    if req.done():
                        self._retire(req)
                self.metrics.on_decode_step(
                    len(active), self.ecfg.num_slots, committed, dt,
                    kv_read_tokens=kv_read,
                    kv_read_tokens_dense=self._dense_kv_rows)
        # every live lane stalled — whether on a decode-step block-table
        # extension or a prefill-chunk one — preempt the newest request so
        # the rest can make progress
        if self.sched.active and \
                all(r.stalled for r in self.sched.active.values()):
            self._preempt_newest()

    def _paged_decode_inputs(self, active: Dict[int, Request]) -> _PagedFeed:
        """The host side of one batched paged decode: per-lane lengths and
        write mask, block tables at the step's width, and their device
        copies."""
        e = self.ecfg
        S = e.num_slots
        wm = np.zeros((S,), np.int32)
        for slot, req in active.items():
            wm[slot] = 0 if req.stalled else 1
        # lanes outside the decode set (empty slots, mid-prefill lanes
        # under chunked prefill) contribute no pages: length 0 masks them
        # in the kernel and their rows are never streamed
        kv = np.where([s in active for s in range(S)],
                      self._kv_rows, 0).astype(np.int32)
        # pages each lane touches this step (stalled lanes attend without
        # their pending token; empty lanes touch nothing)
        need = [self.pool.blocks_for(int(kv[s]) + int(wm[s]))
                for s in range(S)]
        # table width = max live pages rounded up to a power of two (one
        # compilation per width bucket) — the kernel grid walks only these
        # columns, which is what makes decode cost track live tokens
        width = KVBlockPool.table_width(max(need),
                                        self._max_blocks_per_slot)
        rids = [active[s].rid if s in active else None for s in range(S)]
        tables = self.pool.dense_block_table(rids, width)
        # snapshot the generation stamp of every page the table names;
        # replayed after the kernel to trap tables that outlived a free
        gens = (self.pool.table_generations(rids, width)
                if e.sanitize else None)
        self.obs.gauge("decode_table_width", width)
        args = (jnp.asarray(self._last_tok), self._state, self.arena.leaves,
                jnp.asarray(tables), jnp.asarray(kv), jnp.asarray(wm))
        return _PagedFeed(args, width, e.block_size * sum(need), rids,
                          tables, gens)

    def _decode_paged(self, active: Dict[int, Request], feed: _PagedFeed):
        """One batched decode over every lane through the page arena.
        Returns (logits (S, V) on the host, seconds)."""
        t0 = time.time()
        with self._dispatch_scope("decode"), \
                self.timeline.phase("paged_decode", lanes=len(active),
                                    width=feed.width, kv_rows=feed.kv_read):
            # saralint: ok[cow-gate] decode appends one row into the lane's exclusively-owned tail page; shared prefix pages cover only rows < kv_len
            logits, leaves = self._paged_decode(self.params, *feed.args)
        with self.timeline.phase("sync") as sync:
            logits, leaves = jax.block_until_ready((logits, leaves))
            logits = self._moe_stats(sync, logits)
        dt = time.time() - t0
        self.arena.leaves = leaves
        with self.timeline.phase("readback"):
            logits = np.asarray(logits)
        if self.ecfg.sanitize:
            self._sanitize_decode(active, feed.rids, feed.tables, feed.gens,
                                  logits)
        return logits, dt

    def _moe_stats(self, sync, out):
        """A MoE model's step program returns (logits, loads): loads (L, 2,
        E) int32, each MoE layer's routed rows and grouped-GEMM rows per
        expert.  Put their sums over layers on the ``engine.sync`` span
        that ends the program (its own span closes at the enqueue, before
        the counts exist): ``moe_routed_rows`` (live tokens x experts per
        token), ``moe_gemm_rows`` (rows the grouped GEMMs computed, padding
        included) and ``moe_experts_hit`` (experts with a row, summed over
        layers).  Returns the logits."""
        if not self._moe_loads:
            return out
        logits, loads = out
        loads = np.asarray(loads)
        sync.note(moe_routed_rows=int(loads[:, 0].sum()),
                  moe_gemm_rows=int(loads[:, 1].sum()),
                  moe_experts_hit=int((loads[:, 0] > 0).sum()))
        return logits

    def _sanitize_decode(self, active: Dict[int, Request],
                         rids, tables, gens, logits: np.ndarray) -> None:
        """Post-decode sanitizer traps.  (1) generation replay: every
        (page, generation) pair the step's block table named must still
        be current — a page freed and re-handed-out since table build is
        a use-after-free.  (2) poison scan: a non-finite logit row on a
        live lane means the kernel streamed a poisoned (freed) page."""
        try:
            self.pool.assert_generations(rids, tables, gens)
        except SanitizerError:
            self.obs.count("kv_generation_faults", 1)
            raise
        bad = [s for s, r in sorted(active.items())
               if not r.stalled and not np.isfinite(logits[s]).all()]
        if bad:
            self.obs.count("kv_poison_hits", len(bad))
            lanes = ", ".join(f"{s} ({active[s].rid})" for s in bad)
            err = SanitizerError(
                f"poisoned KV page read: decode produced non-finite "
                f"logits on lane(s) {lanes} — a freed (NaN-filled) arena "
                "page is still reachable through a live block table")
            # attributed: the step error boundary fails exactly these
            # lanes instead of crashing the engine
            err.rids = [active[s].rid for s in bad]
            raise err

    def _spec_decode_step(self, active: Dict[int, Request]) -> None:
        """One speculative step over the fully-prefilled lanes: draft up
        to ``spec_k`` tokens per lane (draft model, own page arena),
        verify every lane's pending token + drafts with ONE target pass
        through the ragged chunked-prefill kernel (C = spec_k + 1 rows
        per lane), and commit the longest draft prefix the verify argmax
        agrees with plus one corrected/bonus token.  Every committed
        token is a target verify argmax, so sequences are bitwise
        greedy-parity with plain decode — speculation only changes how
        many commit per step.  Rejected drafts roll back by NOT
        advancing per-lane lengths: the rows they wrote (target and
        draft arenas alike) sit past the new kv length inside
        already-reserved pages and are overwritten in place next step —
        COW-gated below where target pages are shared with the prefix
        cache, so the rewind can never scribble on another request."""
        e = self.ecfg
        S, K = e.num_slots, e.spec_k
        # per-lane draft quota: the bonus token always commits one, so
        # never draft past the remaining budget; reserve verify rows
        # [L-1, L-1+k] up front, degrading k -> 0 before stalling
        quota: Dict[int, int] = {}
        with self.timeline.phase("inputs"):
            for slot, req in sorted(active.items()):
                L = req.prompt_len + len(req.generated)
                k = max(0, min(K,
                               req.max_new_tokens - len(req.generated) - 1))
                if not self.sched.grow(req, L + k):
                    k = 0
                    if not self.sched.grow(req, L):
                        self.metrics.stalls += 1
                quota[slot] = k
            if self.chaos is not None:
                self._inject_decode_chaos(active, {})
            for slot, req in sorted(active.items()):
                if req.stalled:
                    continue             # writes nothing: no fork needed
                L = req.prompt_len + len(req.generated)
                if not self._cow_chunk_pages(req, L - 1, quota[slot] + 1):
                    self.metrics.stalls += 1

        draft_lanes = {s: (r, quota[s]) for s, r in active.items()
                       if not r.stalled and quota[s] > 0}
        drafts: Dict[int, List[int]] = {}
        preempts = 0
        dt_draft = 0.0
        if draft_lanes:
            t0 = time.time()
            with self._dispatch_scope("spec_draft"), \
                    self.timeline.phase("spec_draft",
                                        lanes=len(draft_lanes)):
                drafts, preempts = self.spec.draft(draft_lanes)
            dt_draft = time.time() - t0
            if preempts:
                self.obs.count("spec_draft_preempts", preempts)

        # verify: one ragged chunk batch at fixed width (compiles once).
        # Row 0 is the pending token (exactly what plain decode would
        # process), rows 1..k are the drafts; stalled lanes ride along
        # with chunk 0 (rows land in the trash page, logits ignored).
        C = K + 1
        with self.timeline.phase("inputs"):
            toks = np.zeros((S, C), np.int32)
            clens = np.zeros((S,), np.int32)
            for slot, req in sorted(active.items()):
                if req.stalled:
                    continue
                d = drafts.get(slot, [])
                toks[slot, 0] = self._last_tok[slot, 0]
                toks[slot, 1:1 + len(d)] = d
                clens[slot] = 1 + len(d)
            kv = np.where(clens > 0, self._kv_rows, 0).astype(np.int32)
            width = self._max_blocks_per_slot
            rids = [active[s].rid if s in active and clens[s] > 0 else None
                    for s in range(S)]
            tables = self.pool.dense_block_table(rids, width)
            gens = (self.pool.table_generations(rids, width)
                    if e.sanitize else None)
            kv_read = e.block_size * sum(
                self.pool.blocks_for(int(kv[s]) + int(clens[s]))
                for s in range(S))
            args = (jnp.asarray(toks), self.arena.leaves, jnp.asarray(tables),
                    jnp.asarray(kv), jnp.asarray(clens))
        t0 = time.time()
        with self._dispatch_scope("spec_verify"), \
                self.timeline.phase("spec_verify",
                                    lanes=int((clens > 0).sum()),
                                    width=width):
            # saralint: ok[cow-gate] verify rows are COW-forked above (_cow_chunk_pages over [L-1, L-1+k]) before this write
            logits, leaves = self._spec_verify(self.params, *args)
        with self.timeline.phase("sync"):
            logits, leaves = jax.block_until_ready((logits, leaves))
        dt = time.time() - t0
        self.arena.leaves = leaves
        self._dispatch("spec_verify")
        with self.timeline.phase("readback"):
            logits = np.asarray(logits)      # (S, C, V)
        if e.sanitize:
            self._sanitize_spec(active, rids, tables, gens, logits, clens)

        with self.timeline.phase("sample"):
            argm = np.argmax(logits, -1)     # (S, C) greedy verify picks
        with self.timeline.phase("commit"):
            committed = accepted = bonus = drafted = live = 0
            for slot, req in sorted(active.items()):
                if req.stalled:
                    continue             # replays the pending token
                live += 1
                d = drafts.get(slot, [])
                drafted += len(d)
                a, commit = accept_tokens(d, argm[slot, :len(d) + 1])
                c = 0
                for t in commit:         # EOS can land mid-commit
                    req.generated.append(int(t))
                    c += 1
                    if req.done():
                        break
                accepted += min(a, c)
                bonus += c - min(a, c)
                committed += c
                self._kv_rows[slot] += c
                self._last_tok[slot, 0] = req.generated[-1]
                self.spec.commit(req.rid, int(self._kv_rows[slot]))
                if req.t_first_token < 0:
                    req.t_first_token = self.now()
                    self.metrics.on_first_token(req.arrival_time,
                                                req.t_first_token)
                    self.req_spans.on_first_token(req.rid)
                if req.done():
                    self._retire(req)
        self.obs.count("spec_steps", 1)
        self.obs.count("spec_drafted_tokens", drafted)
        self.obs.count("spec_accepted_tokens", accepted)
        self.obs.count("spec_bonus_tokens", bonus)
        self.obs.gauge("spec_accepted_per_step", committed / max(live, 1))
        self.metrics.on_spec_step(live, drafted, accepted, bonus, preempts)
        self.metrics.on_decode_step(
            len(active), e.num_slots, committed, dt + dt_draft,
            kv_read_tokens=kv_read,
            kv_read_tokens_dense=self._dense_kv_rows)

    def _sanitize_spec(self, active: Dict[int, Request], rids, tables,
                       gens, logits: np.ndarray, clens: np.ndarray) -> None:
        """Post-verify sanitizer traps — the spec twin of
        ``_sanitize_decode``, scanning only each lane's live chunk rows
        (rows past ``clens`` are trash-page garbage by construction)."""
        try:
            self.pool.assert_generations(rids, tables, gens)
        except SanitizerError:
            self.obs.count("kv_generation_faults", 1)
            raise
        bad = [s for s, r in sorted(active.items())
               if not r.stalled
               and not np.isfinite(logits[s, :int(clens[s])]).all()]
        if bad:
            self.obs.count("kv_poison_hits", len(bad))
            lanes = ", ".join(f"{s} ({active[s].rid})" for s in bad)
            err = SanitizerError(
                f"poisoned KV page read: spec verify produced non-finite "
                f"logits on lane(s) {lanes} — a freed (NaN-filled) arena "
                "page is still reachable through a live block table")
            err.rids = [active[s].rid for s in bad]
            raise err

    def run(self, requests: Sequence[Request]) -> Dict[str, np.ndarray]:
        """Serve a request set to completion; returns {rid: generated}.
        An invalid request (empty prompt, oversized, never-admittable) is
        recorded as ``rejected`` and skipped — one bad request in a batch
        must not take the server down with it."""
        for r in requests:
            try:
                self.submit(r)
            except (ValueError, PoolError) as exc:
                r.outcome = "rejected"
                self.requests[r.rid] = r
                self.metrics.on_finish("rejected")
                self.obs.count("requests_rejected", 1)
                self.obs.instant("request", "rejected", f"req:{r.rid}",
                                 rid=r.rid, reason=str(exc)[:200])
        while self.step():
            pass
        if self.ecfg.sanitize:
            # teardown audit: every request drained, so every page must be
            # reclaimed and the only surviving pins are the prefix trie's
            expected = (self.prefix_cache.pages()
                        if self.prefix_cache is not None else ())
            self._leak_audit = self.pool.audit_leaks(expected)
            if self.spec is not None:
                # draft pages are released with their target request, so
                # a drained engine must leave the draft pool empty too
                self.spec.check()
                self._leak_audit["kv_draft_leaked_blocks"] = \
                    self.spec.live_pages()
        return {r.rid: np.asarray(r.generated, np.int32) for r in requests}

    def dispatch_stats(self) -> Dict[str, int]:
        """Executed-GEMM dispatch telemetry (registry-backed)."""
        backends: Dict[str, int] = {}
        sources: Dict[str, int] = {}
        for scope in self.registry.scopes():
            for b, c in self.registry.backends(scope).items():
                backends[b] = backends.get(b, 0) + c
            for s, c in self.registry.sources(scope).items():
                sources[s] = sources.get(s, 0) + c
        return {"gemm_plan_changes": self.plan_changes,
                "gemm_sites_executed": len(self.gemm_plan),
                "gemm_traced_scopes": len(self.registry.scopes()),
                "gemm_pallas_sites": backends.get("pallas", 0),
                "gemm_xla_sites": backends.get("xla", 0),
                "rec_adaptnet_sites": sources.get("adaptnet", 0),
                "rec_oracle_sites": sources.get("oracle", 0),
                "rec_fallback_sites": sources.get("oracle_fallback", 0),
                # retraces, from the compile-event counter: the signal a
                # shape-diversity regression shows up in directly, instead
                # of having to be inferred from wall time
                "jit_compiles": int(self.obs.counters.get("jit_compiles",
                                                          0))}

    def defrag(self) -> int:
        """Compact live KV pages to the front of the arena between steps:
        the pool rewrites every block table and (paged layout) mirrors the
        move map into page storage as one batched gather.  Returns the
        number of pages moved.  The next decode step picks the remapped
        tables up automatically."""
        return len(self.pool.defrag())

    def summary(self) -> Dict[str, float]:
        s = self.metrics.summary(self.dispatcher.cache_info(),
                                 dispatch=self.dispatch_stats())
        s["kv_layout"] = self.kv_layout
        s["kv_peak_blocks"] = self.pool.peak_in_use
        s["kv_fragmentation"] = self.pool.fragmentation()
        s["kv_defrag_block_moves"] = self.pool.defrag_moves
        s["kv_defrag_auto"] = int(self.obs.counters.get("kv_defrag_auto", 0))
        s["kv_shared_pages"] = self.pool.shared_pages
        s["kv_cow_copies"] = self.pool.cow_copies
        if self.prefix_cache is not None:
            s.update(self.prefix_cache.stats())
        s["faults_contained"] = int(
            self.obs.counters.get("faults_contained", 0))
        s["engine_step_retries"] = int(
            self.obs.counters.get("engine_step_retries", 0))
        s["preempt_budget_exhausted"] = int(
            self.obs.counters.get("preempt_budget_exhausted", 0))
        s["engine_snapshots"] = int(
            self.obs.counters.get("engine_snapshots", 0))
        s["engine_restores"] = int(
            self.obs.counters.get("engine_restores", 0))
        if self.chaos is not None:
            s["faults_injected"] = self.chaos.total_injected()
            s.update(self.chaos.summary())
        if self.ecfg.sanitize:
            s["kv_sanitize_checks"] = self.pool.sanitize_checks
            s["kv_poison_fills"] = self.pool.poison_fills
            s["kv_poison_hits"] = int(
                self.obs.counters.get("kv_poison_hits", 0))
            s["kv_generation_faults"] = self.pool.generation_faults
            s.update(self._leak_audit)
        return s

    # -- crash safety ---------------------------------------------------------
    def snapshot(self, directory: Optional[str] = None,
                 blocking: bool = True) -> int:
        """Write a restorable engine snapshot (KV storage, scheduler
        queue/slots, live requests, pool ownership, prefix-cache trie,
        metrics, PRNG) through ``checkpoint/manager`` — atomic rename,
        so a crash mid-save never corrupts the latest snapshot.  Returns
        the snapshot's step index."""
        from repro.serving.snapshot import save_engine
        d = directory or self.ecfg.snapshot_dir
        if not d:
            raise ValueError("snapshot needs a directory: pass one or set "
                             "EngineConfig.snapshot_dir")
        step = save_engine(self, d, blocking=blocking)
        self.obs.count("engine_snapshots", 1)
        return step

    def restore(self, directory: Optional[str] = None,
                step: Optional[int] = None) -> int:
        """Resume from a snapshot into this freshly-built engine (same
        configs, nothing submitted yet).  Surviving requests continue
        token-for-token under greedy decoding.  Returns the restored
        step index."""
        from repro.serving.snapshot import restore_engine
        d = directory or self.ecfg.snapshot_dir
        if not d:
            raise ValueError("restore needs a directory: pass one or set "
                             "EngineConfig.snapshot_dir")
        step = restore_engine(self, d, step=step)
        self.obs.count("engine_restores", 1)
        return step

    # -- observability export -------------------------------------------------
    def export_trace(self, path: str) -> str:
        """Write the trace as Chrome/Perfetto trace-event JSON at ``path``
        plus a structured JSONL sibling (``.jsonl``).  Loadable in
        https://ui.perfetto.dev or chrome://tracing; see
        docs/OBSERVABILITY.md.  Returns the JSONL path."""
        meta = {"arch": self.cfg.name, "kv_layout": self.kv_layout,
                "prefill_chunk": self.prefill_chunk,
                "dispatcher_mode": self.ecfg.dispatcher_mode}
        write_chrome_trace(path, self.obs, meta)
        jsonl = (path[:-5] if path.endswith(".json") else path) + ".jsonl"
        write_jsonl(jsonl, self.obs, meta)
        return jsonl
