"""RSA GEMM — the TPU-native reconfigurable-tiling GEMM kernel.

The RSA's (sub-array dims x dataflow) configuration space maps onto the
Pallas tiling space (DESIGN.md §2): BlockSpec tile sizes are the sub-array
dimensions, and the *residency mode* — which operand's tile stays pinned in
VMEM while the grid iterates — is the dataflow:

  OS (output-stationary): grid (Mt, Nt, Kt), K innermost; the f32
      accumulator tile lives in VMEM scratch for the whole K loop.
  WS (weight-stationary): grid (Nt, Kt, Mt), M innermost; the B (weight)
      tile is revisited with a constant index over the whole M sweep, so it
      stays resident.
  IS (input-stationary):  grid (Mt, Kt, Nt), N innermost; the A (input)
      tile stays resident.

In WS and IS an output tile is revisited once per K block, but not on
consecutive grid steps, and the TPU pipeline never reads an output block
back from HBM: it cannot accumulate there.  Each K block's partial product
goes to its own f32 slice of a (Kt, M, N) output instead, summed after the
kernel — the partial-sum traffic these dataflows spill on the array too.

Block shapes are the SARA-recommended configuration (core/sara.py); MXU
alignment wants multiples of 128 in M/N and the lane dim.  Validated in
interpret mode against kernels/ref.py on CPU; compiled path targets TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import IS, OS, WS


def _kernel_os(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_partial(a_ref, b_ref, o_ref):
    """WS/IS: one K block's partial product, written once."""
    o_ref[0] = jnp.dot(a_ref[...], b_ref[...],
                       preferred_element_type=jnp.float32).astype(o_ref.dtype)


def rsa_gemm_pallas(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int,
                    block_n: int, block_k: int, mode: int = OS,
                    interpret: bool = True) -> jnp.ndarray:
    """a: (M, K), b: (K, N) — M, K, N must be multiples of the blocks
    (ops.rsa_gemm pads arbitrary shapes)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    mt, nt, kt = M // block_m, N // block_n, K // block_k

    if mode == OS:
        grid = (mt, nt, kt)
        return pl.pallas_call(
            functools.partial(_kernel_os, n_k=kt),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
                pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda m, n, k: (m, n)),
            out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(a, b)

    if mode in (WS, IS):
        if mode == WS:            # B tile constant over the M sweep
            grid, mnk = (nt, kt, mt), lambda n, k, m: (m, n, k)
        else:                     # A tile constant over the N sweep
            grid, mnk = (mt, kt, nt), lambda m, k, n: (m, n, k)

        def at(index):            # an index map over (m, n, k), on the grid
            return lambda *g: index(*mnk(*g))

        # a single K block needs no sum: write it in the output dtype
        parts = pl.pallas_call(
            _kernel_partial,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), at(lambda m, n, k: (m, k))),
                pl.BlockSpec((block_k, block_n), at(lambda m, n, k: (k, n))),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   at(lambda m, n, k: (k, m, n))),
            out_shape=jax.ShapeDtypeStruct(
                (kt, M, N), a.dtype if kt == 1 else jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(a, b)
        return parts[0] if kt == 1 else parts.sum(0).astype(a.dtype)

    raise ValueError(f"unknown mode {mode}")


def _kernel_grouped(group, used, layer, a_ref, b_ref, o_ref, acc_ref, *,
                    n_k: int):
    live = pl.program_id(0) < used[0]
    k = pl.program_id(2)

    @pl.when(live & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _acc():
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(live & (k == n_k - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def rsa_grouped_gemm_pallas(a: jnp.ndarray, b: jnp.ndarray,
                            group: jnp.ndarray, used: jnp.ndarray,
                            layer: jnp.ndarray, *,
                            block_m: int, block_n: int, block_k: int,
                            interpret: bool = True) -> jnp.ndarray:
    """The grouped RSA GEMM (OS): rows sorted into groups, each group's
    segment padded to whole row tiles, times a bank of per-group weights
    read in place from a stack of layers' banks.  The group of each row
    tile, the number of tiles in use and the layer ride in scalar
    prefetch, so the weight BlockSpec's index map picks the tile's own
    group in the layer's bank, and the stack is never sliced.  Tiles past
    the used ones compute and write nothing, and map to the last used
    tile's blocks, so nothing is fetched for them.

    a: (R, K) rows, row tile t of group ``group[t]``; b: (L, G, K, N);
    used: (1,) int32 row tiles in use; layer: (1,) int32 the bank of b.
    R, K, N multiples of the blocks.  Returns (R, N); the rows of unused
    tiles are left unwritten."""
    R, K = a.shape
    _, _, K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert R % block_m == 0 and N % block_n == 0 and K % block_k == 0
    mt, nt, kt = R // block_m, N // block_n, K // block_k

    def blk(m, n, k, u):
        # an unused tile repeats the grid's last used step, (u-1, nt-1, kt-1)
        dead = m >= u[0]
        return (jnp.where(dead, jnp.maximum(u[0] - 1, 0), m),
                jnp.where(dead, nt - 1, n), jnp.where(dead, kt - 1, k))

    def a_map(m, n, k, g, u, l):
        mm, _, kk = blk(m, n, k, u)
        return mm, kk

    def b_map(m, n, k, g, u, l):
        mm, nn, kk = blk(m, n, k, u)
        return l[0], g[mm], kk, nn

    def o_map(m, n, k, g, u, l):
        mm, nn, _ = blk(m, n, k, u)
        return mm, nn

    return pl.pallas_call(
        functools.partial(_kernel_grouped, n_k=kt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(mt, nt, kt),
            in_specs=[pl.BlockSpec((block_m, block_k), a_map),
                      pl.BlockSpec((None, 1, block_k, block_n), b_map)],
            out_specs=pl.BlockSpec((block_m, block_n), o_map),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, N), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(group, used, layer, a, b)
