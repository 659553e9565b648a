"""Speculative decoding over the paged KV arena.

The engine's spec path (``ServingEngine._spec_decode_step``) splits one
decode step into three moves:

1. **Draft** — a cheap draft model proposes up to ``spec_k`` tokens per
   lane.  The draft keeps its own KV in a *second* page arena managed by
   a second :class:`KVBlockPool` (same page economics as the target:
   per-request block tables, alloc/extend/free, preemption when dry).
2. **Verify** — ONE target-model pass checks every lane's pending token
   plus all its drafts through the ragged chunked-prefill kernel
   (``models/serving.paged_prefill_step`` with ``every_row``: C =
   spec_k + 1 rows per lane, logits at every row).  Row ``i`` answers
   "what would greedy decode emit after draft ``i`` tokens?".
3. **Accept** — :func:`accept_tokens` commits the longest draft prefix
   the verify argmax agrees with, plus one corrected token from the
   first disagreeing row (or a bonus extension when all drafts match).

Every committed token is a target verify argmax, so the generated
sequence is bitwise-identical to plain greedy decode — speculation only
changes how many tokens commit per step.  Rejected drafts need no
physical rollback on either arena: per-lane lengths simply don't advance
over the rejected rows, and the next step's writes land at the same kv
positions (the stale-row contract stalled lanes already rely on).
Where target pages are shared with the prefix cache the engine COW-gates
the verify rows first; draft pages are never shared with anything.

Draft-KV bookkeeping
--------------------
``_rows[rid]`` counts the draft-arena rows that hold the request's real
context (``req.context()`` tokens).  Drafting "catches up" any gap
below ``L - 1`` by streaming ``context[rows:L-1]`` through draft
prefill chunks, then ONE fused ``lax.scan`` kernel feeds every lane's
pending token and greedily feeds each round's argmax back for ``k``
rounds — all k draft tokens come out of a single dispatch instead of k
host round-trips (per-lane round counts are masked inside the scan, so
the kernel compiles once at ``spec_k`` rounds).  After a commit of
``c`` tokens the engine calls :meth:`SpecDecoder.commit` with the new
target row count ``L + c - 1``: accepted draft rows are already correct
in the draft arena, so the steady-state catch-up is empty and a spec
step costs exactly two dispatches (draft scan + verify).  This one
mechanism uniformly covers fresh admissions (full-context catch-up),
post-rejection divergence, aborted steps (the engine's fault boundary
re-runs the step; :meth:`draft` re-derives the pending row), and
readmission after preemption (:meth:`release` drops the rows).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.obs import JitWatch
from repro.serving.kv_pool import KVArena, KVBlockPool, PoolError


def accept_tokens(drafts: Sequence[int],
                  verify_argmax: Sequence[int]) -> Tuple[int, List[int]]:
    """The accept rule: longest matching draft prefix plus one corrected
    token.

    ``drafts`` are the k proposed tokens; ``verify_argmax`` are the
    target's greedy picks at the k+1 verify rows (row ``i`` conditions
    on the pending token plus drafts ``< i``).  Returns ``(a,
    committed)`` where ``a`` is the number of accepted draft tokens and
    ``committed == verify_argmax[:a + 1]`` — in the accepted region the
    argmax equals the draft by construction, and entry ``a`` is the
    target's correction (all-accept: the free "bonus" extension token).
    The caller commits ``committed`` in order, stopping early on EOS.
    """
    a = 0
    while a < len(drafts) and int(drafts[a]) == int(verify_argmax[a]):
        a += 1
    return a, [int(t) for t in verify_argmax[:a + 1]]


def resolve_draft(cfg: ArchConfig, params, name: str, seed: int):
    """Resolve ``EngineConfig.spec_draft`` to ``(draft_cfg,
    draft_params)``.

    ``"self"`` shares the target's config AND params (self-speculation:
    the draft always agrees with the verifier, so acceptance is ~100% —
    the upper bound, used by the benchmark to isolate engine overheads).
    Any other value names a registry arch; it is reduced when the target
    is a reduced config so both sides stay CPU-test sized, shares params
    when it resolves to the target's exact config, and otherwise
    initializes fresh draft params from a different seed (a genuinely
    disagreeing draft — what the partial-accept tests use)."""
    from repro.models.api import build_model
    from repro.models.serving import CHUNKED_PREFILL_FAMILIES

    if name == "self":
        return cfg, params
    from repro.configs.registry import get_arch
    draft = get_arch(name)
    if cfg.name.endswith("-reduced"):
        draft = draft.reduced()
    if draft.family not in CHUNKED_PREFILL_FAMILIES:
        raise ValueError(
            f"spec_draft {name!r} has family {draft.family!r}; the draft "
            f"runs the chunked paged path, which supports "
            f"{CHUNKED_PREFILL_FAMILIES}")
    if draft.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"spec_draft {name!r} vocab {draft.vocab_size} != target "
            f"vocab {cfg.vocab_size}: draft tokens must be target tokens")
    if draft == cfg:
        return draft, params
    return draft, build_model(draft).init(jax.random.PRNGKey(seed + 2))


class SpecDecoder:
    """Owns the draft side of speculative decoding: the draft model, its
    page pool + arena, and the per-request draft row counts.

    The engine drives it with :meth:`draft` (inside its ``spec_draft``
    dispatch scope), then :meth:`commit` per lane after acceptance, and
    :meth:`release` whenever a request leaves its slot (retire, terminal
    failure, preemption) so draft pages never outlive target pages."""

    def __init__(self, draft_cfg: ArchConfig, draft_params, *,
                 num_slots: int, block_size: int, num_blocks: int,
                 max_blocks_per_slot: int, chunk: int, spec_k: int,
                 recorder=None):
        from repro.models.api import build_model

        self.cfg = draft_cfg
        self.model = build_model(draft_cfg)
        self.params = draft_params
        self.num_slots = num_slots
        self.chunk = max(1, int(chunk))
        self.spec_k = max(1, int(spec_k))
        self._max_blocks = max_blocks_per_slot
        # same pool economics as the target arena: per-request tables,
        # +1 trailing write-discard page for masked rows.  The sanitizer
        # stays off — draft pages are private (never shared, pinned, or
        # reachable from the prefix cache) and draft logits never become
        # output tokens, only proposals the verify pass re-derives.
        self.pool = KVBlockPool(num_blocks, block_size)
        self.arena = KVArena(
            self.model.init_paged_arena(num_blocks + 1, block_size),
            block_size)
        self.pool.bind_arena(self.arena)
        if recorder is not None:
            self.pool.attach_recorder(recorder)
        self._state = self.model.init_paged_state(num_slots)
        self._rows: Dict[str, int] = {}
        # both draft programs donate the draft arena, as the target's do
        self._draft_prefill = JitWatch(
            jax.jit(self.model.paged_prefill_step, donate_argnums=(2,)),
            "spec_draft_prefill", recorder)

        # all k draft rounds fused into one dispatch: feed each round's
        # greedy argmax back inside a lax.scan, so drafting costs one
        # host round-trip regardless of k.  Round i writes a lane's KV
        # only while i < nwrites[lane] (per-lane k, masked like stalled
        # lanes in plain decode), which keeps the compiled shape fixed
        # at spec_k rounds.
        def _loop(params, first, state, leaves, tables, kv, nwrites):
            def body(carry, i):
                feed, lv, pos = carry
                wm = (i < nwrites).astype(jnp.int32)
                logits, lv = self.model.paged_decode_step(
                    params, feed, state, lv, tables, pos, wm)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (nxt[:, None], lv, pos + wm), nxt
            (_, leaves, _), toks = jax.lax.scan(
                body, (first, leaves, kv), jnp.arange(self.spec_k))
            return toks, leaves      # toks: (spec_k, S)

        self._draft_loop = JitWatch(jax.jit(_loop, donate_argnums=(3,)),
                                    "spec_draft_loop", recorder)

    # -- lifecycle ------------------------------------------------------------
    def rows(self, rid: str) -> int:
        """Draft-arena rows currently holding ``rid``'s real context."""
        return self._rows.get(rid, 0)

    def commit(self, rid: str, rows: int) -> None:
        """Record the post-accept draft row count (== the target's new kv
        rows: context minus the new pending token).  Accepted draft rows
        already hold the committed tokens; everything past ``rows`` is
        rejected garbage the next catch-up overwrites in place."""
        self._rows[rid] = int(rows)

    def release(self, rid: str) -> None:
        """Drop ``rid``'s draft pages and row count (request retired /
        failed / preempted, or draft-lane preemption under pool
        pressure).  Safe to call for requests that never drafted."""
        if rid in self.pool.live_requests():
            self.pool.free(rid)
        self._rows.pop(rid, None)

    def live_pages(self) -> int:
        return self.pool.num_in_use

    def check(self) -> None:
        self.pool.check()

    # -- drafting -------------------------------------------------------------
    def _reserve(self, rid: str, num_tokens: int) -> None:
        if rid in self.pool.live_requests():
            table = self.pool.table(rid)
            if table.capacity(self.pool.block_size) >= num_tokens:
                table.num_tokens = max(table.num_tokens, num_tokens)
                return
            self.pool.extend(rid, num_tokens)
        else:
            self.pool.alloc(rid, num_tokens)

    def draft(self, lanes: Dict[int, Tuple[object, int]]
              ) -> Tuple[Dict[int, List[int]], int]:
        """Propose draft tokens for ``lanes`` (slot -> (request, k)).

        Returns ``(drafts, preempts)``: ``drafts[slot]`` is the lane's k
        proposed tokens; a lane whose draft-page reservation failed is
        *draft-preempted* — its pages free immediately (making room for
        the other lanes), it is absent from ``drafts`` (the engine runs
        it as a plain C=1 verify this step), and it re-catches-up in
        full once the draft pool can hold it again.

        Catch-up chunks and the fused draft scan are both batched across
        all drafting lanes at fixed shapes (chunk width, table width,
        spec_k rounds), so the draft side compiles once like the
        target's chunked prefill — and a steady-state step (no catch-up
        gap) is a single draft dispatch.
        """
        S, C = self.num_slots, self.chunk
        preempts = 0
        jobs: Dict[int, List] = {}     # slot -> [req, k, pos]
        for slot, (req, k) in sorted(lanes.items()):
            if k <= 0:
                continue
            L = req.context_len
            # rows beyond L-1 may hold rejected drafts from an earlier
            # (possibly aborted) step; the scan re-feeds the pending row
            # so round one always yields this step's d_1
            pos = min(self._rows.get(req.rid, 0), L - 1)
            try:
                self._reserve(req.rid, L + k - 1)
            except PoolError:
                self.release(req.rid)
                preempts += 1
                continue
            jobs[slot] = [req, k, pos]
        if not jobs:
            return {}, preempts

        # catch-up: stream context[pos:L-1] through ragged prefill
        # chunks.  Steady-state lanes (pos == L-1 after a commit) skip
        # this entirely — their only unwritten row is the pending token,
        # which the scan's first round writes.
        while any(j[2] < j[0].context_len - 1 for j in jobs.values()):
            toks = np.zeros((S, C), np.int32)
            chunk = np.zeros((S,), np.int32)
            kv = np.zeros((S,), np.int32)
            for slot, (req, k, pos) in sorted(jobs.items()):
                n = min(C, req.context_len - 1 - pos)
                if n <= 0:
                    continue
                toks[slot, :n] = req.context()[pos:pos + n]
                chunk[slot] = n
                kv[slot] = pos
            rids = [jobs[s][0].rid if s in jobs and chunk[s] > 0 else None
                    for s in range(S)]
            tables = self.pool.dense_block_table(rids, self._max_blocks)
            # saralint: ok[cow-gate] draft arena pages are private per request (never shared, pinned, or reachable from the prefix cache)
            _, leaves = self._draft_prefill(
                self.params, jnp.asarray(toks), self.arena.leaves,
                jnp.asarray(tables), jnp.asarray(kv), jnp.asarray(chunk))
            self.arena.leaves = jax.block_until_ready(leaves)
            for slot in sorted(jobs):
                jobs[slot][2] += int(chunk[slot])

        # fused draft rounds: every lane feeds its pending token at row
        # L-1 and the scan greedily extends k rounds in one dispatch;
        # lanes needing fewer rounds stop writing via nwrites masking
        # (their later outputs are garbage the slicing below drops)
        first = np.zeros((S, 1), np.int32)
        kv = np.zeros((S,), np.int32)
        nwrites = np.zeros((S,), np.int32)
        for slot, (req, k, pos) in sorted(jobs.items()):
            first[slot, 0] = req.context()[req.context_len - 1]
            kv[slot] = req.context_len - 1
            nwrites[slot] = k
        rids = [jobs[s][0].rid if s in jobs else None for s in range(S)]
        tables = self.pool.dense_block_table(rids, self._max_blocks)
        # saralint: ok[cow-gate] draft arena pages are private per request (never shared, pinned, or reachable from the prefix cache)
        toks, leaves = self._draft_loop(
            self.params, jnp.asarray(first), self._state,
            self.arena.leaves, jnp.asarray(tables), jnp.asarray(kv),
            jnp.asarray(nwrites))
        toks, leaves = jax.block_until_ready((toks, leaves))
        self.arena.leaves = leaves
        toks = np.asarray(toks)
        drafts = {slot: [int(t) for t in toks[:jobs[slot][1], slot]]
                  for slot in jobs}
        for slot, (req, k, pos) in jobs.items():
            self._rows[req.rid] = req.context_len - 1 + k
        return drafts, preempts
