"""The paged step programs update the stacked KV arena in place.

- aliasing: every engine step program that takes the arena (decode,
  chunked prefill, speculative verify, the draft programs and the
  bucketed prefill write) donates it, so its compiled
  executable aliases at least the arena's bytes, and no
  dynamic-update-slice in it produces a whole stacked arena: the layer
  scan scatters each layer's rows into the carried stack instead of
  slicing a layer out and stacking the result back
- parity: chunk, decode and verify (the chunk step with logits at every
  row) steps give logits and arena leaves bitwise equal to the scan they
  replace (each layer's pages in as ``xs``, out as ``ys``, restacked),
  for GQA, absorbed MLA and MLA behind a leading dense stack
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.models import serving
from repro.models.api import build_model
from repro.obs.trace import JitWatch
from repro.serving import EngineConfig, Request, ServingEngine

GQA_ARCH = "llama3.2-1b"
MLA_ARCH = "deepseek-v3-671b"        # MLA + moe + one leading dense layer

# engine step program -> position of the arena among its arguments
ARENA_ARG = {"paged_decode": 3, "chunk_prefill": 2, "spec_verify": 2,
             "spec_draft_prefill": 2, "spec_draft_loop": 3,
             "paged_write": 0}
HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def _abstract(args):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)


@pytest.fixture(scope="module")
def step_programs():
    """Run small engines that reach every arena-taking step program and
    record each program's jitted function and argument shapes."""
    seen = {}
    real_call = JitWatch.__call__

    def record(self, *args):
        if self.name in ARENA_ARG and self.name not in seen:
            seen[self.name] = (self.fn, _abstract(args))
        return real_call(self, *args)

    cfg = get_arch(GQA_ARCH).reduced()
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = []
    for _ in range(4):
        p = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
        p[:16] = shared                  # r2/r3 decode through r0's pages
        prompts.append(p)
    base = dict(num_slots=2, block_size=8, temperature=0.0,
                kv_layout="paged")
    engines = [
        EngineConfig(max_len=30, prefill_chunk=8, prefix_cache=True,
                     **base),
        EngineConfig(max_len=40, prefill_chunk=8, spec_draft="self",
                     spec_k=2, **base),
        EngineConfig(max_len=40, **base),            # bucketed prefill
    ]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(JitWatch, "__call__", record)
        for ecfg in engines:
            eng = ServingEngine(cfg, ecfg)
            eng.run([Request(f"r{i}", p, 3) for i, p in enumerate(prompts)])
    return seen


@pytest.mark.parametrize("name", sorted(ARENA_ARG))
def test_step_program_updates_arena_in_place(step_programs, name):
    fn, args = step_programs[name]
    arena = args[ARENA_ARG[name]]
    leaves = jax.tree_util.tree_leaves(arena)
    nbytes = sum(a.size * jnp.dtype(a.dtype).itemsize for a in leaves)
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    hlo = compiled.as_text()
    for a in leaves:
        result = (f"= {HLO_DTYPE[jnp.dtype(a.dtype).name]}"
                  f"[{','.join(str(d) for d in a.shape)}]")
        assert not [line for line in hlo.splitlines()
                    if result in line and "dynamic-update-slice" in line], \
            f"{name} rebuilds a {a.shape} arena with a dynamic-update-slice"


# ---------------------------------------------------------------------------
# parity with the xs/ys layer scan
# ---------------------------------------------------------------------------

def _xs_ys_scan(layer_fn, x, params, arena, extra=()):
    """The layer scan as it was before the arena moved into the carry: each
    layer's pages go in as ``xs``, are written as a one-layer stack, come
    back as ``ys`` and are stacked anew (split around a leading dense
    stack, then concatenated)."""

    def body(h, xs):
        layer_p, k, v = xs[:3]
        h, k, v = layer_fn(h, layer_p, k[None], v[None], 0, *xs[3:])
        return h, (k[0], v[0])

    if "dense_layers" in params:
        nd = jax.tree_util.tree_leaves(params["dense_layers"])[0].shape[0]
        x, (hk, hv) = jax.lax.scan(body, x, (params["dense_layers"],
                                             arena["k"][:nd],
                                             arena["v"][:nd]))
        x, (tk, tv) = jax.lax.scan(body, x, (params["layers"],
                                             arena["k"][nd:],
                                             arena["v"][nd:]) + tuple(extra))
        return x, {"k": jnp.concatenate([hk, tk]),
                   "v": jnp.concatenate([hv, tv])}
    x, (k, v) = jax.lax.scan(body, x, (params["layers"], arena["k"],
                                       arena["v"]) + tuple(extra))
    return x, {"k": k, "v": v}


BS, C = 4, 5                         # page rows, chunk rows
LENS = np.array([9, 4, 7], np.int32)
# lanes 0 and 1 share page 0 (and the prompt rows it holds); the trash
# page is the arena's last
TABLES = np.array([[0, 1, 2, 3], [0, 5, 6, 7], [8, 9, 10, 11]], np.int32)
NUM_BLOCKS = 13


def _cfg(name):
    if name == "gqa":
        return get_arch(GQA_ARCH).reduced()
    cfg = get_arch(MLA_ARCH).reduced()
    if name == "mla":
        return dataclasses.replace(cfg, first_dense_layers=0)
    assert cfg.first_dense_layers == 1 and cfg.num_layers == 2
    return cfg                                       # "mla-split"


def _drive(model, params, arena, program):
    """Two chunk steps over a ragged prompt batch, then three ``program``
    steps; returns every step's (logits, arena)."""
    cfg = model.cfg
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in LENS]
    prompts[1][:BS] = prompts[0][:BS]
    tables = jnp.asarray(TABLES)
    chunk_step = jax.jit(lambda *a: model.paged_prefill_step(*a))
    outs = []
    pos = np.zeros_like(LENS)
    while (pos < LENS).any():
        n = np.minimum(C, LENS - pos)
        toks = np.zeros((len(LENS), C), np.int32)
        for s in range(len(LENS)):
            toks[s, :n[s]] = prompts[s][pos[s]:pos[s] + n[s]]
        logits, arena = chunk_step(params, jnp.asarray(toks), arena, tables,
                                   jnp.asarray(np.where(n > 0, pos, 0)),
                                   jnp.asarray(n))
        outs.append((logits, arena))
        pos += n

    kv = LENS.copy()
    wm = np.array([1, 1, 0], np.int32)               # lane 2 masked
    if program == "verify":
        step = jax.jit(lambda *a: model.paged_prefill_step(*a,
                                                           every_row=True))
    else:
        step = jax.jit(lambda *a: model.paged_decode_step(*a))
    for _ in range(3):
        if program == "verify":
            n = np.array([3, 2, 0], np.int32)
            toks = rng.integers(0, cfg.vocab_size, (len(LENS), C))
            logits, arena = step(params, jnp.asarray(toks, jnp.int32), arena,
                                 tables, jnp.asarray(kv), jnp.asarray(n))
            kv = kv + n
        else:
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                            (len(LENS), 1)), jnp.int32)
            logits, arena = step(params, toks, {}, arena, tables,
                                 jnp.asarray(kv), jnp.asarray(wm))
            kv = kv + wm
        outs.append((logits, arena))
    return outs


@pytest.mark.parametrize("arch,program", [
    ("gqa", "decode"), ("mla", "decode"), ("mla-split", "decode"),
    ("gqa", "verify"), ("mla", "verify"), ("mla-split", "verify")])
def test_inplace_scan_bitwise_equals_xs_ys_scan(arch, program):
    cfg = _cfg(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # random pages, so rows no step writes must come through unchanged
    arena = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape, a.dtype),
        model.init_paged_arena(NUM_BLOCKS, BS))

    got = _drive(model, params, arena, program)
    traced = []

    def reference(*args):
        traced.append(True)
        return _xs_ys_scan(*args)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serving, "_scan_paged_layers", reference)
        want = _drive(model, params, arena, program)
    assert traced                                    # the reference ran
    assert len(got) == len(want) == 5
    for (g_logits, g_arena), (w_logits, w_arena) in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_logits),
                                      np.asarray(w_logits))
        for name in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(g_arena[name]),
                                          np.asarray(w_arena[name]))
