"""Unified SARA dispatch layer: recommendation -> executed GEMM.

Every dense GEMM site in the model stack calls ``dispatch.gemm(x, w,
site=...)``.  At trace time (shapes are static under jit/vmap) the call:

  1. resolves (M, K, N) -> ``TPUTileConfig`` through the *active*
     ``SaraDispatcher`` (oracle or ADAPTNET mode),
  2. records the site -> executed configuration in the active
     ``SiteRegistry`` (per-trace scope), and
  3. executes through the Pallas RSA kernel (``kernels/ops.rsa_gemm``)
     with the recommended ``block_m/block_n/block_k`` + residency mode,
     or through ``jnp.einsum`` when XLA execution is selected.

Policy is ambient state installed with the ``dispatch.use`` context
manager (this replaces the old mutable ``_GLOBAL`` singleton in
``core/sara.py``)::

    with dispatch.use(dispatcher, execute="pallas"):
        logits = model.logits(params, batch)     # every GEMM -> RSA kernel

``execute="auto"`` (the default policy) compiles the Pallas kernel on TPU
and falls back to XLA elsewhere, so the same call sites run the real
kernel on TPU with no flag plumbing.
"""

from repro.dispatch.context import (DispatchPolicy, active, default_registry,
                                    use)
from repro.dispatch.executor import (GroupLayout, gemm, group_block_m,
                                     grouped_gemm, segment_rows)
from repro.dispatch.registry import SiteRecord, SiteRegistry

__all__ = ["DispatchPolicy", "GroupLayout", "SiteRecord", "SiteRegistry",
           "active", "default_registry", "gemm", "group_block_m",
           "grouped_gemm", "segment_rows", "use"]
