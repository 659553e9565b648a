"""Ambient dispatch policy (dispatcher + execution backend + registry).

The policy is an explicit stack manipulated by the ``use`` context
manager; ``active()`` returns the top of the stack (or a lazily-built
default: oracle dispatcher, ``execute="auto"``, process-wide registry).
This replaces the old mutable ``_GLOBAL`` dispatcher singleton in
``core/sara.py`` — the policy is scoped, explicit, and restorable.

The policy is consulted at *trace* time: a jitted function bakes in
whatever policy was active when it first traced.  Enter ``use(...)``
around the call that triggers compilation (the serving engine and the
launchers do this for you).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, replace
from typing import List, Optional

import jax

from repro.dispatch.registry import SiteRegistry

EXECUTE_MODES = ("auto", "pallas", "xla")


@dataclass(frozen=True)
class DispatchPolicy:
    dispatcher: "SaraDispatcher"       # noqa: F821 (resolved lazily)
    execute: str = "auto"              # "pallas" | "xla" | "auto"
    registry: SiteRegistry = None
    interpret: Optional[bool] = None   # None -> backend-aware (kernels/ops)
    shard_hints: bool = False          # apply ShardPlan hints on xla outputs

    def backend(self) -> str:
        """Resolve 'auto' at trace time: compiled Pallas on TPU, XLA off
        TPU and under a multi-device mesh — the compiler cannot partition a
        Pallas kernel across devices, and XLA's dot it can."""
        if self.execute == "auto":
            from repro.parallel.hints import current_mesh
            mesh = current_mesh()
            sharded = mesh is not None and mesh.devices.size > 1
            return ("pallas" if jax.default_backend() == "tpu"
                    and not sharded else "xla")
        return self.execute


_DEFAULT_REGISTRY = SiteRegistry()
_STACK: List[DispatchPolicy] = []
_DEFAULT: Optional[DispatchPolicy] = None


def default_registry() -> SiteRegistry:
    return _DEFAULT_REGISTRY


def active() -> DispatchPolicy:
    """The innermost policy, or the lazily-built process default."""
    if _STACK:
        return _STACK[-1]
    global _DEFAULT
    if _DEFAULT is None:
        from repro.core.sara import SaraDispatcher
        _DEFAULT = DispatchPolicy(dispatcher=SaraDispatcher(),
                                  registry=_DEFAULT_REGISTRY)
    return _DEFAULT


@contextlib.contextmanager
def use(dispatcher=None, execute: Optional[str] = None,
        registry: Optional[SiteRegistry] = None,
        interpret: Optional[bool] = None,
        shard_hints: Optional[bool] = None):
    """Install a dispatch policy; unset fields inherit from the active one.

        with dispatch.use(my_dispatcher, execute="pallas"):
            engine.step()
    """
    if execute is not None and execute not in EXECUTE_MODES:
        raise ValueError(f"execute must be one of {EXECUTE_MODES}, "
                         f"got {execute!r}")
    base = active()
    pol = replace(
        base,
        dispatcher=dispatcher if dispatcher is not None else base.dispatcher,
        execute=execute if execute is not None else base.execute,
        registry=registry if registry is not None else base.registry,
        interpret=interpret if interpret is not None else base.interpret,
        shard_hints=(shard_hints if shard_hints is not None
                     else base.shard_hints))
    _STACK.append(pol)
    try:
        yield pol
    finally:
        _STACK.pop()
