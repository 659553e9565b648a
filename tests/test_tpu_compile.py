"""Compile the main-path kernels for a TPU v5e chip, without one.

The TPU compiler is installed beside JAX and compiles for a described
topology (``v5e:2x2``) with no chip attached, so these tests catch what
interpret mode cannot: BlockSpecs the Mosaic lowering refuses (unaligned
block dims), kernels that overflow VMEM, and tiles outside the RSA space.
Everything is at llama3.2-1b's published widths (KVH 8, G 4, hd 64, d 2048,
ff 8192, vocab 128256) and the serving engine's page size (16).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and each pytest-xdist worker
imports every test file.  Keep every such compile in this one file.
"""

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


def test_cascade_decode_compiles(spec):
    k, v, tables, lengths = _arena_args(spec)
    q = spec((SLOTS, CFG.num_heads, CFG.head_dim))
    pages = spec((8,), jnp.int32)
    hlo = _compile(lambda *a: ops.shared_paged_attention(
        *a, impl="pallas", interpret=False), q, k, v, tables, lengths, pages,
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
