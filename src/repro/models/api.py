"""Public model API: build a model object from an ArchConfig."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import serving, transformer
from repro.models.modules import param_count


def _on(**flags: bool) -> Dict[str, bool]:
    """The step programs' boolean options (``moe_loads``, ``every_row``),
    passed only where they are on (a step function without them keeps the
    plain signature)."""
    return {name: True for name, on in flags.items() if on}


class Model:
    """Functional model wrapper — all methods are pure and jit-friendly."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # ---- params -----------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        return transformer.init_params(key, self.cfg)

    def init_abstract(self) -> Dict[str, Any]:
        """Parameter avals without allocation (for dry-run lowering)."""
        return jax.eval_shape(
            lambda k: transformer.init_params(k, self.cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32))

    def num_params(self, params=None) -> int:
        tree = params if params is not None else self.init_abstract()
        return param_count(tree)

    # ---- training ---------------------------------------------------------
    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        return transformer.loss_fn(params, batch, self.cfg)

    def logits(self, params, batch) -> jnp.ndarray:
        return transformer.logits(params, batch, self.cfg)

    def forward(self, params, batch):
        return transformer.forward(params, batch, self.cfg)

    # ---- serving ----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, src_len: int = 0):
        return serving.init_cache(self.cfg, batch, max_len, src_len)

    def prefill(self, params, batch, cache, length=None):
        return serving.prefill(params, batch, self.cfg, cache, length)

    def decode_step(self, params, tokens, cache):
        return serving.decode_step(params, tokens, self.cfg, cache)

    # ---- paged serving (physical KV arena; serving/kv_pool.py) ------------
    def init_paged_arena(self, num_blocks: int, block_size: int):
        return serving.init_paged_arena(self.cfg, num_blocks, block_size)

    def init_paged_state(self, num_slots: int, src_len: int = 0):
        return serving.init_paged_state(self.cfg, num_slots, src_len)

    def paged_prefill_write(self, arena, layers_cache, block_ids):
        # saralint: ok[cow-gate] pass-through to the bucketed prefill scatter; the engine only hands it freshly alloc'd, never-shared pages
        return serving.paged_prefill_write(arena, layers_cache, block_ids)

    def paged_prefill_step(self, params, tokens, arena, block_tables,
                           kv_lens, chunk_lens, moe_loads=False,
                           every_row=False):
        return serving.paged_prefill_step(params, tokens, self.cfg, arena,
                                          block_tables, kv_lens, chunk_lens,
                                          **_on(moe_loads=moe_loads,
                                                every_row=every_row))

    def paged_decode_step(self, params, tokens, state, arena, block_tables,
                          kv_lens, write_mask, moe_loads=False):
        return serving.paged_decode_step(params, tokens, self.cfg, state,
                                         arena, block_tables, kv_lens,
                                         write_mask,
                                         **_on(moe_loads=moe_loads))


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
