"""Compile the main-path kernels for a TPU v5e chip, without one.

The TPU compiler is installed beside JAX and compiles for a described
topology (``v5e:2x2``) with no chip attached, so these tests catch what
interpret mode cannot: BlockSpecs the Mosaic lowering refuses (unaligned
block dims), kernels that overflow VMEM, and tiles outside the RSA space.
Everything is at llama3.2-1b's published widths (KVH 8, G 4, hd 64, d 2048,
ff 8192, vocab 128256) and the serving engine's page size (16), and the
absorbed-MLA kernels and the grouped expert GEMM at Moonlight-16B-A3B's
(16 heads, latent 512 + rope 64, 64 experts top-6 of width 1408, d 2048)
with the benchmark cell's 64 lanes and 128-row chunks.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and each pytest-xdist worker
imports every test file.  Keep every such compile in this one file.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro import dispatch
from repro.configs.registry import get_arch
from repro.core.hw import IS, OS, WS
from repro.core.sara import SaraDispatcher
from repro.core.tpu_costmodel import BLOCK_K, BLOCK_MN
from repro.kernels import ops
from repro.serving.engine import gemm_sites

CFG = get_arch("llama3.2-1b")
SLOTS = 4                         # decode lanes (the chip smoke's slots)
BS = 16                           # EngineConfig.block_size
WIDTH = 35                        # table width at max_len 545 (512 + 32 + 1)
NUM_BLOCKS = SLOTS * WIDTH + 1    # pool pages + the trash page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under the system temp dir
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-device compile is written to the persistent cache but
        # can never be read back without the chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    """Compile for the described chip; return the executable's HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _arena_args(spec):
    arena = spec((NUM_BLOCKS, BS, CFG.num_kv_heads, CFG.head_dim))
    return (arena, arena, spec((SLOTS, WIDTH), jnp.int32),
            spec((SLOTS,), jnp.int32))


def test_paged_decode_compiles(spec):
    k, v, tables, lengths = _arena_args(spec)
    q = spec((SLOTS, CFG.num_heads, CFG.head_dim))
    hlo = _compile(lambda *a: ops.paged_attention(
        *a, impl="pallas", interpret=False), q, k, v, tables, lengths)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("chunk", [128, 512])
def test_paged_prefill_compiles(spec, chunk):
    """512 needs the kernel's query-row blocks: one (KVH, 2048, hd) block
    overflows VMEM."""
    k, v, tables, lengths = _arena_args(spec)
    q = spec((SLOTS, chunk, CFG.num_heads, CFG.head_dim))
    hlo = _compile(lambda *a: ops.paged_prefill_attention(
        *a, impl="pallas", interpret=False), q, k, v, tables, lengths,
        lengths)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mode", [OS, WS, IS], ids=["OS", "WS", "IS"])
def test_rsa_gemm_largest_tile_compiles(spec, mode):
    """The tile space's largest block fits VMEM in every residency mode
    (1024 x 2048 x 2048 does not: the space stops at 512 x 512 x 2048)."""
    bm = bn = max(BLOCK_MN)
    bk = max(BLOCK_K)
    a, b = spec((2 * bm, 2 * bk)), spec((2 * bk, 2 * bn))
    hlo = _compile(lambda x, y: ops.rsa_gemm(
        x, y, block_m=bm, block_n=bn, block_k=bk, mode=mode,
        interpret=False), a, b)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("site", ["attn_qkv", "mlp_up", "mlp_down",
                                  "lm_head"])
def test_oracle_decode_tiles_compile(spec, site):
    """Every decode-step GEMM site runs the RSA kernel at the tile the
    oracle recommends for it (through the dispatch seam the model uses)."""
    _, m, k, n = next(s for s in gemm_sites(CFG, SLOTS) if s[0] == site)
    reg = dispatch.SiteRegistry()
    with dispatch.use(SaraDispatcher(), execute="pallas", registry=reg,
                      interpret=False):
        hlo = _compile(lambda x, w: dispatch.gemm(x, w, site=site),
                       spec((m, k)), spec((k, n)))
    assert "tpu_custom_call" in hlo
    rec = reg.sites("_")[site]
    assert rec.backend == "pallas"
    assert (rec.m, rec.k, rec.n) == (m, k, n)


# Moonlight-16B-A3B (bench/configs/moonlight-16b-a3b.json) as its cell
# serves it: 64 lanes, 128-row chunks, 16-row pages
ML_LANES, ML_CHUNK, ML_HEADS, ML_R, ML_RD = 64, 128, 16, 512, 64
ML_D, ML_F, ML_E, ML_K = 2048, 1408, 64, 6
ML_PAGES = 8192 + 1


def _mla_arena(spec, width):
    return (spec((ML_PAGES, BS, ML_R)), spec((ML_PAGES, BS, ML_RD)),
            spec((ML_LANES, width), jnp.int32),
            spec((ML_LANES,), jnp.int32))


def test_mla_paged_decode_compiles(spec):
    ckv, krope, tables, lengths = _mla_arena(spec, 256)
    hlo = _compile(lambda *a: ops.mla_paged_attention(
        *a, qk_dim=192, impl="pallas", interpret=False),
        spec((ML_LANES, ML_HEADS, ML_R)), spec((ML_LANES, ML_HEADS, ML_RD)),
        ckv, krope, tables, lengths)
    assert "tpu_custom_call" in hlo


def test_mla_paged_prefill_compiles(spec):
    """128 rows a chunk at 16 heads: 256 needs 24 MB of scoped VMEM."""
    ckv, krope, tables, lengths = _mla_arena(spec, 512)
    hlo = _compile(lambda *a: ops.mla_paged_prefill_attention(
        *a, qk_dim=192, impl="pallas", interpret=False),
        spec((ML_LANES, ML_CHUNK, ML_HEADS, ML_R)),
        spec((ML_LANES, ML_CHUNK, ML_HEADS, ML_RD)), ckv, krope, tables,
        lengths, lengths)
    assert "tpu_custom_call" in hlo


ML_STACK = 6                      # MoE layers on the chip (7 less 1 dense)
# a per-layer copy of an expert bank out of its stack
BANK_COPY = re.compile(r"(fusion|copy|dynamic-slice)[\w.-]* = "
                       rf"bf16\[{ML_E},({ML_D},{ML_F}|{ML_F},{ML_D})\]")


@pytest.mark.parametrize("rows", [ML_LANES, ML_LANES * ML_CHUNK],
                         ids=["decode", "chunk"])
@pytest.mark.parametrize("k,n", [(ML_D, ML_F), (ML_F, ML_D)],
                         ids=["gate", "down"])
def test_grouped_expert_gemm_compiles(spec, rows, k, n):
    """The serving MoE's grouped GEMM at the row tile the recommender
    gives the step's segments (384 routed rows in decode, 49,152 in a
    chunk step) over its whole static buffer, reading one layer's bank
    of the six MoE layers' stack in place."""
    from repro.models.moe import group_rows
    routed = rows * ML_K
    reg = dispatch.SiteRegistry()
    with dispatch.use(SaraDispatcher(), execute="pallas", registry=reg,
                      interpret=False):
        bm = dispatch.group_block_m(routed, ML_E, ML_D, ML_F, jnp.bfloat16)
        R = group_rows(routed, ML_E, bm)
        hlo = _compile(
            lambda x, w, g, u, z, layer: dispatch.grouped_gemm(
                x, w, dispatch.GroupLayout(g, u, z, bm, routed // ML_E),
                site="moe.expert", layer=layer),
            spec((R, k)), spec((ML_STACK, ML_E, k, n)),
            spec((R // bm,), jnp.int32), spec((1,), jnp.int32),
            spec((ML_E,), jnp.int32), spec((), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert not BANK_COPY.search(hlo)
    rec = reg.sites("_")["moe.expert"]
    assert (rec.backend, rec.m, rec.block_m) == ("pallas", R, bm)
    assert (rec.k, rec.n) == (k, n)
    assert k % rec.block_k == 0 and n % rec.block_n == 0   # no bank padding


def test_moe_layer_scan_reads_banks_in_place(spec):
    """Moonlight's whole paged decode step (the dense layer, then the
    layer scan over six MoE layers, 64 lanes): every grouped GEMM reads
    its layer's banks from the stacked weights, so no fusion, copy or
    dynamic slice produces a (64, 2048, 1408) or (64, 1408, 2048) bank."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig
    from repro.models.api import build_model
    cfg = ArchConfig(
        name="moonlight-16b-a3b", family="moe", num_layers=ML_STACK + 1,
        d_model=ML_D, num_heads=ML_HEADS, num_kv_heads=ML_HEADS,
        head_dim=192, d_ff=11264, vocab_size=163840, attention_type="mla",
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=ML_R,
                      qk_nope_head_dim=128, qk_rope_head_dim=ML_RD,
                      v_head_dim=128),
        moe=MoEConfig(num_experts=ML_E, num_shared_experts=2,
                      experts_per_token=ML_K, d_ff_expert=ML_F,
                      scoring_func="sigmoid", topk_method="noaux_tc",
                      norm_topk_prob=True, routed_scaling_factor=2.446),
        first_dense_layers=1, rope_theta=50000.0, norm_eps=1e-5,
        tie_embeddings=False, param_dtype="bfloat16",
        compute_dtype="bfloat16")
    model = build_model(cfg)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), tree)

    params = shaped(model.init_abstract())
    assert params["layers"]["moe"]["w_gate"].shape == (
        ML_STACK, ML_E, ML_D, ML_F)
    arena = shaped(jax.eval_shape(
        lambda: model.init_paged_arena(ML_PAGES, BS)))
    lanes = spec((ML_LANES,), jnp.int32)
    with dispatch.use(SaraDispatcher(), execute="pallas", interpret=False):
        hlo = _compile(
            lambda *a: model.paged_decode_step(*a, moe_loads=True),
            params, spec((ML_LANES, 1), jnp.int32), {}, arena,
            spec((ML_LANES, 256), jnp.int32), lanes, lanes)
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") >= 3
    assert "moe_grouped_gemm" in hlo
    assert not BANK_COPY.search(hlo)
