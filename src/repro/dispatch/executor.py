"""The GEMM executor: recommendation -> executed kernel.

``gemm(x, w, site=...)`` is the single seam every dense GEMM in the
model stack goes through.  Shapes are static at trace time, so the
recommendation (a Python-side ``SaraDispatcher.recommend``) and the
backend choice are resolved while tracing and baked into the compiled
executable; the executed configuration is recorded in the active
``SiteRegistry`` under the current scope.

Backends:
  pallas — ``kernels/ops.rsa_gemm`` with the recommended
           block_m/block_n/block_k + residency mode (OS/WS/IS).  Blocks
           are clamped to the 128-aligned operand extent so a 64-wide K
           never pads to a 2048-wide block.  A custom VJP expresses both
           gradient GEMMs (dx = dy @ w^T, dw = x^T @ dy) through the
           same RSA kernel with their own recommended configs, so the
           dispatch layer is load-bearing for training too.
  xla    — ``jnp.einsum`` (+ the recommended mesh-level sharding hint
           when a mesh is active and the policy enables shard_hints).

Expert banks (w of shape (E, K, N) against x (..., E, C, K)) execute as
a vmap of the 2D path over E — the MoE expert GEMMs see the same
recommendation machinery as every other site.

``grouped_gemm(x, w, layout, site=...)`` multiplies rows sorted by expert
(ragged segments, each padded to the row tile) by an expert bank in one
call: ``kernels/ops.moe_grouped_gemm`` on the pallas backend (the grouped
RSA kernel), its twin ``kernels/ref.moe_grouped_gemm_ref`` on xla.  The
bank may be one layer of a stack of layers' banks (``layer=``), which the
kernel reads in place.  The row tile comes from the recommendation at the
mean segment's rows (``group_block_m``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ALIGN = 128                    # MXU tile edge: block clamp granularity


def _round_up(n: int, mult: int) -> int:
    return max(mult, ((int(n) + mult - 1) // mult) * mult)


def _clamped_blocks(cfg, m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Shrink recommended blocks that exceed the 128-aligned operand extent
    (pure padding waste); never grows a block past the recommendation."""
    return (min(cfg.block_m, _round_up(m, ALIGN)),
            min(cfg.block_n, _round_up(n, ALIGN)),
            min(cfg.block_k, _round_up(k, ALIGN)))


def _run_rsa(a, b, tile: Tuple[int, int, int, int],
             interpret: Optional[bool]):
    """tile = (block_m, block_n, block_k, mode)."""
    from repro.kernels import ops
    return ops.rsa_gemm(a, b, block_m=tile[0], block_n=tile[1],
                        block_k=tile[2], mode=tile[3], interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _pallas_gemm2d(x2, w, tile, dx_tile, dw_tile, interpret):
    """(M, K) @ (K, N) through the RSA Pallas kernel, differentiable.
    Each of tile/dx_tile/dw_tile is that GEMM's own recommended
    (block_m, block_n, block_k, mode)."""
    return _run_rsa(x2, w, tile, interpret)


def _pallas_gemm2d_fwd(x2, w, tile, dx_tile, dw_tile, interpret):
    return _run_rsa(x2, w, tile, interpret), (x2, w)


def _pallas_gemm2d_bwd(tile, dx_tile, dw_tile, interpret, res, dy):
    x2, w = res
    dx = _run_rsa(dy, w.T, dx_tile, interpret)
    dw = _run_rsa(x2.T, dy, dw_tile, interpret)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_pallas_gemm2d.defvjp(_pallas_gemm2d_fwd, _pallas_gemm2d_bwd)


def _resolved_tile(policy, m: int, k: int, n: int):
    """(recommended cfg, executed (bm, bn, bk, mode)) for an (m,k,n) GEMM."""
    cfg = policy.dispatcher.recommend(m, k, n)
    return cfg, _clamped_blocks(cfg, m, k, n) + (cfg.mode,)


def _rec_source(policy, m: int, k: int, n: int) -> str:
    """Provenance of the recommendation just resolved ("oracle" for
    dispatchers that don't track sources, e.g. test fixtures)."""
    src = getattr(policy.dispatcher, "source_of", None)
    return src(m, k, n) if src is not None else "oracle"


def _shard_plan_name(policy, M: int, K: int, N: int
                     ) -> Tuple[str, Optional[object]]:
    """Mesh-level recommendation: ("", None) when meshless, else
    (plan name, ShardPlan).  Recorded always; applied under shard_hints."""
    from repro.parallel.hints import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return "", None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data = sizes.get("data", 1) * sizes.get("pod", 1)
    model = sizes.get("model", 1)
    plan = policy.dispatcher.recommend_sharding(M, K, N, data=data,
                                                model=model)
    return plan.name, plan


def gemm(x: jnp.ndarray, w: jnp.ndarray, *, site: str = "dense",
         backend: Optional[str] = None) -> jnp.ndarray:
    """Self-adaptive GEMM.

    w 2D:  (..., M', K) @ (K, N) -> (..., M', N), M = prod of leading dims.
    w 3D:  expert bank — x (..., E, C, K) @ w (E, K, N) -> (..., E, C, N),
           one GEMM per expert, recommended at M = rows-per-expert.

    ``backend`` pins this site regardless of policy ("xla" for sites whose
    downstream decisions must be bit-stable across backends, e.g. the MoE
    router top-k).
    """
    from repro.dispatch.context import active
    policy = active()
    exec_backend = backend or policy.backend()

    if w.ndim == 3:
        return _gemm_experts(x, w, site, exec_backend, policy)
    if w.ndim != 2:
        raise ValueError(f"gemm weight must be 2D or 3D (expert bank), "
                         f"got {w.shape}")

    M = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    K, N = int(x.shape[-1]), int(w.shape[-1])
    cfg, tile = _resolved_tile(policy, M, K, N)
    shard_name, shard_plan = _shard_plan_name(policy, M, K, N)
    if policy.registry is not None:
        policy.registry.record(site, M, K, N, cfg, *tile, exec_backend,
                               shard_name, _rec_source(policy, M, K, N))

    if exec_backend == "pallas":
        # the gradient GEMMs carry their own recommendations: dx is an
        # (M,N)x(N,K) GEMM, dw a (K,M)x(M,N) one
        _, dx_tile = _resolved_tile(policy, M, N, K)
        _, dw_tile = _resolved_tile(policy, K, M, N)
        x2 = x.reshape(M, K)
        out = _pallas_gemm2d(x2, w, tile, dx_tile, dw_tile,
                             policy.interpret)
        return out.reshape(x.shape[:-1] + (N,))

    y = jnp.einsum("...k,kn->...n", x, w)
    if policy.shard_hints and shard_plan is not None:
        from repro.parallel.hints import hint
        axes = [None] * y.ndim
        if y.ndim >= 2:
            axes[0] = shard_plan.out_spec[0]
        axes[-1] = shard_plan.out_spec[1]
        y = hint(y, *axes)
    return y


def _gemm_experts(x, w, site: str, exec_backend: str, policy):
    """x: (..., E, C, K) @ w: (E, K, N) -> (..., E, C, N)."""
    E, K, N = (int(s) for s in w.shape)
    if x.ndim < 3 or x.shape[-3] != E or int(x.shape[-1]) != K:
        raise ValueError(f"expert gemm shape mismatch: x {x.shape} vs "
                         f"w {w.shape}")
    C = int(x.shape[-2])
    lead = x.shape[:-3]
    B = int(np.prod(lead)) if lead else 1
    M = B * C                                # rows per expert GEMM
    cfg, tile = _resolved_tile(policy, M, K, N)
    shard_name, _ = _shard_plan_name(policy, M, K, N)
    if policy.registry is not None:
        policy.registry.record(site, M, K, N, cfg, *tile, exec_backend,
                               shard_name, _rec_source(policy, M, K, N))

    if exec_backend == "pallas":
        _, dx_tile = _resolved_tile(policy, M, N, K)
        _, dw_tile = _resolved_tile(policy, K, M, N)
        xe = jnp.moveaxis(x.reshape((B,) + x.shape[-3:]), 1, 0)  # (E,B,C,K)
        xe = xe.reshape(E, M, K)
        out = jax.vmap(lambda a, b: _pallas_gemm2d(
            a, b, tile, dx_tile, dw_tile,
            policy.interpret))(xe, w)                            # (E,M,N)
        out = jnp.moveaxis(out.reshape(E, B, C, N), 0, 1)        # (B,E,C,N)
        return out.reshape(lead + (E, C, N))
    return jnp.einsum("...eck,ekn->...ecn", x, w)


# ---------------------------------------------------------------------------
# grouped GEMM over ragged expert segments
# ---------------------------------------------------------------------------

SUBLANE = {2: 16, 4: 8}        # rows of a packed (sublane, 128) tile
GROUP_VMEM = 12 << 20          # block budget under v5e's 16 MiB scoped VMEM


class GroupLayout(NamedTuple):
    """Rows sorted by group, each group's segment padded to whole row
    tiles of ``block_m``: ``group`` (R // block_m,) int32 the group of each
    row tile, ``used`` (1,) int32 the tiles that hold rows, ``sizes`` (G,)
    int32 each group's padded rows (in order, from row 0); ``seg_rows``
    the mean segment's rows that the tiles were recommended at."""

    group: jnp.ndarray
    used: jnp.ndarray
    sizes: jnp.ndarray
    block_m: int
    seg_rows: int


def segment_rows(rows: int, groups: int) -> int:
    """Rows of the mean segment of ``rows`` rows over ``groups`` groups."""
    return max(1, -(-int(rows) // int(groups)))


def group_block_m(rows: int, groups: int, k: int, n: int, dtype) -> int:
    """The row tile of a grouped GEMM over ``rows`` routed rows in
    ``groups`` segments: the recommendation's block_m at the mean
    segment's rows, no taller than that segment rounded up to the dtype's
    sublane tile (short segments pad to 16 rows, not 128)."""
    from repro.dispatch.context import active
    m_seg = segment_rows(rows, groups)
    cfg = active().dispatcher.recommend(m_seg, k, n)
    return min(cfg.block_m,
               _round_up(m_seg, SUBLANE[jnp.dtype(dtype).itemsize]))


def _whole_or(block: int, dim: int) -> int:
    """The recommended block where it tiles ``dim`` exactly, else all of
    ``dim``: an expert bank is never padded on the call."""
    return block if block < dim and dim % block == 0 else dim


def _group_blocks(cfg, bm: int, k: int, n: int, itemsize: int
                  ) -> Tuple[int, int]:
    """(block_n, block_k) for a grouped GEMM: the recommendation fitted to
    the bank's dims, block_k then block_n halved while the double-buffered
    blocks and the f32 accumulator exceed ``GROUP_VMEM``."""
    bn, bk = _whole_or(cfg.block_n, n), _whole_or(cfg.block_k, k)

    def vmem(bn, bk):
        return 2 * (bm * bk + bk * bn + bm * bn) * itemsize + 4 * bm * bn

    while vmem(bn, bk) > GROUP_VMEM and bk % 256 == 0:
        bk //= 2
    while vmem(bn, bk) > GROUP_VMEM and bn % 256 == 0:
        bn //= 2
    return bn, bk


def grouped_gemm(x: jnp.ndarray, w: jnp.ndarray, layout: GroupLayout, *,
                 site: str, layer: Optional[jnp.ndarray] = None,
                 backend: Optional[str] = None) -> jnp.ndarray:
    """x (R, K), rows sorted by group as ``layout`` says, @ w (G, K, N) ->
    (R, N): each row by its group's weights.  With ``layer`` (an int32
    scalar), w is a stack (L, G, K, N) of layers' banks and the rows meet
    ``w[layer]``, which the kernel reads in place.  Rows past the used
    tiles are unspecified (zero on xla, unwritten by the kernel)."""
    from repro.dispatch.context import active
    policy = active()
    exec_backend = backend or policy.backend()
    if layer is None:
        w, layer = w[None], 0
    R, K = (int(s) for s in x.shape)
    _, _, K2, N = (int(s) for s in w.shape)
    if K2 != K or R % layout.block_m:
        raise ValueError(f"grouped gemm shape mismatch: x {x.shape}, "
                         f"w {w.shape}, block_m {layout.block_m}")
    bm, m_seg = layout.block_m, layout.seg_rows
    cfg = policy.dispatcher.recommend(m_seg, K, N)
    bn, bk = _group_blocks(cfg, bm, K, N, jnp.dtype(x.dtype).itemsize)
    if policy.registry is not None:
        from repro.core.hw import OS
        policy.registry.record(site, R, K, N, cfg, bm, bn, bk, OS,
                               exec_backend, "",
                               _rec_source(policy, m_seg, K, N))
    if exec_backend == "pallas":
        from repro.kernels import ops
        return ops.moe_grouped_gemm(
            x, w, layout.group, layout.used,
            jnp.asarray(layer, jnp.int32).reshape(1), block_m=bm,
            block_n=bn, block_k=bk, interpret=policy.interpret)
    from repro.kernels.ref import moe_grouped_gemm_ref
    return moe_grouped_gemm_ref(x, w[layer], layout.sizes)
