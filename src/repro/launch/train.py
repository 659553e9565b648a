"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --preset reduced --steps 50 --batch 8 --seq 128 --data-axis 1

Uses the full substrate: synthetic pipeline, AdamW, sharded train_step
(pjit over whatever devices exist), fault-tolerant driver with periodic
async checkpoints + restart, straggler monitor.  The e2e ~100M-param run of
deliverable (b) is ``examples/train_lm.py`` which drives this module.
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np


def build_trainer(cfg, data_axis: int = 1, model_axis: int = 1, *,
                  global_batch: int, seq_len: int, lr: float = 1e-3,
                  seed: int = 0, execute: str = "auto", registry=None):
    """Sharded initial state and the jitted train step on a
    ``(data_axis, model_axis)`` mesh over this host's first devices.
    Training keeps params, activations and optimizer state in float32.

    Returns ``(params, opt_state, train_step, batch_sharding)``;
    ``train_step(params, opt_state, batch) -> (params, opt_state, metrics)``
    donates its state arguments and traces under the mesh and the dispatch
    policy (recorded in ``registry`` under the ``train_step`` scope)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import (_dispatch_ctx, build_train_step,
                                    make_optimizer)
    from repro.configs.shapes import input_specs, ShapeSpec
    from repro.parallel.hints import use_mesh
    from repro.parallel.sharding import batch_specs, to_named

    cfg = cfg.replace(param_dtype="float32", compute_dtype="float32",
                      opt_state_dtype="float32")
    mesh = make_host_mesh(data_axis, model_axis)
    model, step_fn, _, (p_sh, o_sh) = build_train_step(cfg, mesh, lr=lr)
    params = jax.device_put(model.init(jax.random.PRNGKey(seed)), p_sh)
    opt_state = jax.device_put(make_optimizer(cfg, lr).init(params), o_sh)

    shape = ShapeSpec("train", seq_len, global_batch, "train")
    b_sh = to_named(batch_specs(input_specs(cfg, shape), mesh), mesh)
    jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))

    def train_step(params, opt_state, batch):
        with use_mesh(mesh, cfg.tp_strategy), mesh, \
                _dispatch_ctx("train_step", execute, registry):
            return jitted(params, opt_state, batch)

    return params, opt_state, train_step, b_sh


def train_main(arch: str = "llama3.2-1b", preset: str = "reduced",
               steps: int = 50, global_batch: int = 8, seq_len: int = 128,
               data_axis: int = 1, model_axis: int = 1,
               checkpoint_dir: str = "/tmp/repro_ckpt",
               checkpoint_every: int = 25, lr: float = 1e-3,
               log_every: int = 10, seed: int = 0,
               execute: str = "auto",
               override_cfg=None, fail_injector=None,
               d_model: Optional[int] = None,
               num_layers: Optional[int] = None):
    from repro import dispatch
    from repro.configs.registry import get_arch
    from repro.data.pipeline import make_loader
    from repro.runtime.driver import DriverConfig, TrainDriver

    cfg = override_cfg if override_cfg is not None else get_arch(arch)
    if preset == "reduced":
        cfg = cfg.reduced()
    if d_model:
        cfg = cfg.replace(d_model=d_model,
                          head_dim=d_model // cfg.num_heads,
                          d_ff=4 * d_model)
    if num_layers:
        cfg = cfg.replace(num_layers=num_layers)

    # the dispatch policy is consulted at trace time (first train_step
    # call), so every training GEMM — fwd and the custom-VJP bwd pair —
    # executes with the SARA-recommended configuration
    registry = dispatch.SiteRegistry()
    params, opt_state, train_step, b_sh = build_trainer(
        cfg, data_axis, model_axis, global_batch=global_batch,
        seq_len=seq_len, lr=lr, seed=seed, execute=execute,
        registry=registry)

    loader = make_loader(cfg.vocab_size, seq_len, global_batch, seed=seed)
    batches = {}

    def make_batch(step: int):
        # pull from the prefetching loader; memoize for restart replay
        while loader.step <= step and step not in batches:
            b = next(loader)
            batches[loader.step - 1] = b
            for s in list(batches):
                if s < step - 2:
                    del batches[s]
        arr = batches.get(step) or next(loader)
        return jax.device_put({"tokens": arr["tokens"]}, b_sh)

    driver = TrainDriver(
        DriverConfig(checkpoint_dir=checkpoint_dir,
                     checkpoint_every=checkpoint_every),
        train_step=train_step, make_batch=make_batch,
        fail_injector=fail_injector)

    t0 = time.time()
    params, opt_state, history = driver.run(params, opt_state,
                                            start_step=0, num_steps=steps)
    dt = time.time() - t0
    losses = [h["loss"] for h in history]
    if log_every:
        for h in history[::log_every] + history[-1:]:
            print(f"  step {h['step']:5d} loss {h['loss']:.4f} "
                  f"grad_norm {h.get('grad_norm', 0):.3f}")
    tok_s = steps * global_batch * seq_len / dt
    print(f"train done: {steps} steps in {dt:.1f}s ({tok_s:,.0f} tok/s), "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"{driver.straggler_report()}")
    plan = registry.plan("train_step")
    if plan:
        print(f"  dispatch: {len(plan)} GEMM sites executed "
              f"({dict(registry.backends('train_step'))})")
    loader.close()
    return params, history, driver


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--preset", default="reduced",
                    choices=["reduced", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--execute", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="GEMM backend for the dispatch layer")
    a = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    train_main(arch=a.arch, preset=a.preset, steps=a.steps,
               global_batch=a.batch, seq_len=a.seq, data_axis=a.data_axis,
               model_axis=a.model_axis, lr=a.lr, checkpoint_dir=a.ckpt,
               execute=a.execute)


if __name__ == "__main__":
    main()
