"""NamedSharding rules for params, optimizer state, caches and batches.

Strategy (DESIGN.md §5):
  - TP (Megatron-style) over the `model` axis: attention heads / FFN hidden /
    experts (EP) / vocab.
  - FSDP (ZeRO-3) over the `data` axis (and over `pod`×`data` on the
    multi-pod mesh): the *other* big dimension of every matrix.
  - Optimizer state inherits the parameter sharding.
  - KV caches: batch over `data`(×`pod`), kv-heads over `model` when the head
    count divides the axis (MQA kv=1 replicates over `model` — documented).
  - SSM states: batch over `data`, ssm-heads over `model`.

Rules are matched on the parameter path suffix; any dim whose size does not
divide its assigned axis falls back to replication on that dim (GSPMD would
pad, but even sharding keeps the roofline numbers clean).
"""

from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= _axis_size(mesh, a)
        return n
    return mesh.devices.shape[mesh.axis_names.index(axis)]


def fsdp_axes(mesh: Mesh):
    """FSDP shards over pod×data when a pod axis exists."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


# (path-suffix regex, spec builder).  `F` = fsdp axes, "model" = TP axis.
# Specs are written WITHOUT the leading scan axis; a leading stack dim is
# detected from rank and prepended as None.
def _param_rules(F):
    return [
        # embeddings / unembeddings
        (r"embed$",          lambda: P("model", F)),
        (r"unembed$",        lambda: P(F, "model")),
        # attention
        (r"attn/wq$",        lambda: P(F, "model")),
        (r"attn/wk$",        lambda: P(F, "model")),
        (r"attn/wv$",        lambda: P(F, "model")),
        (r"attn/wo$",        lambda: P("model", F)),
        (r"cross/w[qkv]$",   lambda: P(F, "model")),
        (r"cross/wo$",       lambda: P("model", F)),
        # MLA
        (r"attn/w_q$",       lambda: P(F, "model")),
        (r"attn/w_dq$",      lambda: P(F, None)),
        (r"attn/w_uq$",      lambda: P(None, "model")),
        (r"attn/w_dkv$",     lambda: P(F, None)),
        (r"attn/w_uk$",      lambda: P(None, "model")),
        (r"attn/w_uv$",      lambda: P(None, "model")),
        # dense MLP
        (r"mlp/w_gate$",     lambda: P(F, "model")),
        (r"mlp/w_up$",       lambda: P(F, "model")),
        (r"mlp/w_down$",     lambda: P("model", F)),
        # MoE (EP over model)
        (r"moe/router$",     lambda: P(F, None)),
        (r"moe/e_score_correction_bias$", lambda: P(None)),
        (r"moe/w_gate$",     lambda: P("model", F, None)),
        (r"moe/w_up$",       lambda: P("model", F, None)),
        (r"moe/w_down$",     lambda: P("model", None, F)),
        (r"shared/w_gate$",  lambda: P(F, "model")),
        (r"shared/w_up$",    lambda: P(F, "model")),
        (r"shared/w_down$",  lambda: P("model", F)),
        # RWKV6
        (r"blk/w_[rkvg]$",   lambda: P(F, "model")),
        (r"blk/w_o$",        lambda: P("model", F)),
        (r"blk/w_ck$",       lambda: P(F, "model")),
        (r"blk/w_cv$",       lambda: P("model", F)),
        (r"blk/w_cr$",       lambda: P(F, "model")),
        (r"blk/w_decay_a$",  lambda: P(F, None)),
        (r"blk/w_decay_b$",  lambda: P(None, "model")),
        # Mamba2
        (r"blk/w_in$",       lambda: P(F, "model")),
        (r"blk/w_out$",      lambda: P("model", F)),
        # frontends
        (r"frontend/proj1?$", lambda: P(F, "model")),
        (r"frontend/proj2$", lambda: P("model", F)),
    ]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _fits(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Replicate any dim whose size doesn't divide its axis."""
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is not None and dim % _axis_size(mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


def param_specs(params_aval: Any, cfg: ArchConfig, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching the parameter tree.

    tp_strategy="tp" (default): Megatron TP over `model` + FSDP over `data`.
    tp_strategy="dp_all": no tensor parallelism — pure ZeRO-3: every >=2-D
    parameter shards its largest non-stack dim over data x model (batch also
    runs over both axes via hints layout "dp_all").  The right choice is
    workload-dependent — this is the sharding-class output of the SARA-TPU
    recommender (§Perf lever for small-model cells whose TP collectives
    dominate)."""
    F = fsdp_axes(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_aval)
    specs = []
    if cfg.tp_strategy in ("dp_all", "dp_all_noep"):
        Fall = (F if isinstance(F, tuple) else (F,)) + ("model",)
        ep_rules = [] if cfg.tp_strategy == "dp_all_noep" else \
            [(pat, b) for pat, b in _param_rules(F)
             if pat.startswith(r"moe/")]
        for path, leaf in flat:
            ps = _path_str(path)
            shape = leaf.shape
            if len(shape) < 2:
                specs.append(P())
                continue
            # MoE expert banks keep EP over `model` (tokens all-to-all to
            # the expert shards); ZeRO-gathering every expert per layer
            # would cost E/top_k more gather traffic than EP's dispatch.
            spec = None
            for pat, builder in ep_rules:
                if re.search(pat, ps):
                    spec = builder()
                    if len(shape) == len(spec) + 1:
                        spec = P(*((None,) + tuple(spec)))
                    elif len(shape) != len(spec):
                        spec = None
                    break
            if spec is None:
                big = max(range(len(shape)), key=lambda d: shape[d])
                sp = [None] * len(shape)
                sp[big] = Fall
                spec = P(*sp)
            specs.append(_fits(spec, shape, mesh))
        return jax.tree_util.tree_unflatten(treedef, specs)

    rules = _param_rules(F)
    for path, leaf in flat:
        ps = _path_str(path)
        shape = leaf.shape
        spec = None
        for pat, builder in rules:
            if re.search(pat, ps):
                spec = builder()
                break
        if spec is None:
            spec = P()                       # norms, biases, scalars: replicate
        else:
            # prepend None for a leading stack (layer) axis
            if len(shape) == len(spec) + 1:
                spec = P(*((None,) + tuple(spec)))
            elif len(shape) != len(spec):
                spec = P()
        specs.append(_fits(spec, shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_specs(batch_aval: Any, mesh: Mesh,
                cfg: Optional[ArchConfig] = None) -> Any:
    """Shard the batch dim over pod×data (replicate if indivisible, e.g. B=1).
    Under tp_strategy="dp_all" the batch also shards over `model`."""
    B_axes = batch_axes(mesh)
    if cfg is not None and cfg.tp_strategy.startswith("dp_all"):
        B_axes = (B_axes if isinstance(B_axes, tuple) else (B_axes,)) \
            + ("model",)

    def spec(leaf):
        s = P(*((B_axes,) + (None,) * (len(leaf.shape) - 1)))
        return _fits(s, leaf.shape, mesh)

    return jax.tree_util.tree_map(spec, batch_aval)


def cache_specs_tree(cache_aval: Any, cfg: ArchConfig, mesh: Mesh) -> Any:
    """Shard decode caches: (L, B, S, heads, ...) -> B on data, heads on model."""
    B_axes = batch_axes(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_aval)
    specs = []
    for path, leaf in flat:
        ps = _path_str(path)
        shape = leaf.shape
        if ps.endswith("pos") or leaf.ndim == 0:
            specs.append(P())
            continue
        if ps.endswith("length"):
            specs.append(_fits(P(None), shape, mesh))
            continue
        if leaf.ndim >= 4:
            # (L, B, S, KVH[, hd]) or states (L, B, H, ...)
            if "wkv" in ps or ("ssm" in ps and "layers" in ps):
                spec = P(None, B_axes, "model")
            elif leaf.ndim == 5:
                spec = P(None, B_axes, None, "model", None)
            else:
                spec = P(None, B_axes, None, None)
        elif leaf.ndim == 3:
            spec = P(None, B_axes, None)
        elif leaf.ndim == 2:
            spec = P(None, B_axes)
        else:
            spec = P(None)
        specs.append(_fits(spec, shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def to_named(specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
