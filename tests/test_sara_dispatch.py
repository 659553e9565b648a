"""SARA dispatcher: recommendations are feasible + execution is exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adaptnet as A
from repro.core import tpu_costmodel as tcm
from repro.core.hw import TPU_V5E
from repro.core.sara import SaraDispatcher


def _logbucket_params(max_dim=4096, num_buckets=32, seed=0):
    return A.init_params(jax.random.PRNGKey(seed), A.AdaptNetConfig(
        num_classes=tcm.NUM_TILE_CLASSES, encoding="logbucket",
        num_buckets=num_buckets, max_dim=max_dim))


def test_tile_space_enumeration():
    assert tcm.NUM_TILE_CLASSES == len(tcm.TILE_CONFIGS) == 3 * 3 * 5 * 3


def test_recommendations_feasible():
    d = SaraDispatcher()
    for M, K, N in [(128, 128, 128), (4096, 4096, 4096), (37, 9000, 222)]:
        cfg = d.recommend(M, K, N)
        vmem = (cfg.block_m * cfg.block_k + cfg.block_k * cfg.block_n
                + cfg.block_m * cfg.block_n) * 2 * tcm.DTYPE_BYTES
        assert vmem <= TPU_V5E.vmem_bytes


def test_recommendation_cached_constant_time():
    d = SaraDispatcher()
    c1 = d.recommend(512, 512, 512)
    c2 = d.recommend(512, 512, 512)
    assert c1 is c2


def test_oracle_beats_fixed_config_on_average():
    rng = np.random.default_rng(0)
    M, K, N = (rng.integers(64, 8192, 200) for _ in range(3))
    costs = tcm.tile_cost_seconds(M, K, N)
    best = costs.min(-1)
    fixed = costs[:, 0]
    assert np.mean(best / fixed) < 1.0


def test_dispatcher_gemm_matches_einsum():
    d = SaraDispatcher(use_pallas=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 96, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 48))
    np.testing.assert_allclose(np.asarray(d.gemm(x, w)),
                               np.asarray(jnp.einsum("bmk,kn->bmn", x, w)),
                               rtol=1e-5, atol=1e-5)


def test_dispatcher_gemm_pallas_path():
    d = SaraDispatcher(use_pallas=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (160, 192))
    w = jax.random.normal(jax.random.PRNGKey(1), (192, 130))
    np.testing.assert_allclose(np.asarray(d.gemm(x, w)),
                               np.asarray(x @ w), rtol=2e-4, atol=2e-4)


def test_auto_backend_resolves_from_platform_and_mesh(monkeypatch):
    """execute="auto" runs the compiled RSA kernel only where it can: on a
    TPU, and not under a multi-device mesh (the compiler cannot partition
    a Pallas kernel across devices)."""
    from types import SimpleNamespace

    from repro.dispatch.context import DispatchPolicy
    from repro.parallel.hints import use_mesh
    pol = DispatchPolicy(dispatcher=SaraDispatcher())
    assert pol.backend() == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pol.backend() == "pallas"
    for devices, want in ((1, "pallas"), (4, "xla")):
        with use_mesh(SimpleNamespace(devices=np.empty((devices, 1)))):
            assert pol.backend() == want


def test_sharding_planner_sensible():
    # huge square GEMM -> use the whole mesh (2d)
    assert tcm.plan_gemm_sharding(8192, 8192, 8192).name in ("2d",)
    # tiny GEMM -> replicated beats paying collectives
    assert tcm.plan_gemm_sharding(64, 64, 64).name in ("replicated", "row_dp")
    # M indivisible by data -> no row sharding chosen
    p = tcm.plan_gemm_sharding(63, 4096, 4096)
    assert p.x_spec[0] != "data"


def test_cache_invalidated_on_mode_or_params_change():
    """Regression: flipping ``mode`` or installing ``adaptnet_params`` on a
    live dispatcher used to keep serving stale cached recommendations from
    the previous source."""
    d = SaraDispatcher()
    d.recommend(512, 512, 512)
    assert d.cache_info()["size"] == 1
    assert d.source_of(512, 512, 512) == "oracle"

    d.mode = "adaptnet"
    d.adaptnet_params = _logbucket_params()
    assert d.cache_info()["size"] == 0         # stale oracle recs dropped
    d.recommend(512, 512, 512)
    assert d.source_of(512, 512, 512) == "adaptnet"
    assert d.cache_info()["hits"] == 0         # re-decided, not replayed

    d.mode = "oracle"
    assert d.cache_info()["size"] == 0
    d.recommend(512, 512, 512)
    assert d.source_of(512, 512, 512) == "oracle"


def test_out_of_range_falls_back_to_oracle():
    """Legacy raw-encoding params clip every dim > 10^4 to one embedding
    row, so lm_head-scale shapes must take the explicit oracle path, never
    the aliased lookup."""
    raw = A.init_params(jax.random.PRNGKey(0), A.AdaptNetConfig(
        num_classes=tcm.NUM_TILE_CLASSES))          # raw: vocab 10001
    d = SaraDispatcher(mode="adaptnet", adaptnet_params=raw)
    assert not d.in_trained_range(64, 2048, 128256)
    cfg = d.recommend(64, 2048, 128256)             # gemma/llama lm_head
    assert d.source_of(64, 2048, 128256) == "oracle_fallback"
    assert cfg is tcm.TILE_CONFIGS[int(tcm.best_tile_config(64, 2048,
                                                            128256))]
    d.recommend(100, 200, 300)                      # within [1, 10^4]
    assert d.source_of(100, 200, 300) == "adaptnet"
    assert d.source_info() == {"adaptnet": 1, "oracle": 0,
                               "oracle_fallback": 1}
    # logbucket params carry their coverage bound instead
    d2 = SaraDispatcher(mode="adaptnet",
                        adaptnet_params=_logbucket_params(max_dim=4096))
    assert d2.in_trained_range(64, 2048, 4096)
    assert not d2.in_trained_range(64, 2048, 4097)


def test_recommend_batch_matches_scalar():
    shapes = [(64, 2048, 128256), (1, 64, 128), (1, 64, 128),
              (512, 512, 512), (300_000, 1, 1)]
    d_batch = SaraDispatcher(mode="adaptnet",
                             adaptnet_params=_logbucket_params(
                                 max_dim=A.MAX_DIM_SERVING))
    d_one = SaraDispatcher(mode="adaptnet",
                           adaptnet_params=d_batch.adaptnet_params)
    batch = d_batch.recommend_batch(shapes)
    singles = [d_one.recommend(*s) for s in shapes]
    assert batch == singles
    for s in shapes:
        assert d_batch.source_of(*s) == d_one.source_of(*s)
    assert d_batch.source_of(300_000, 1, 1) == "oracle_fallback"
    # second pass is pure cache hits
    info = d_batch.cache_info()
    assert d_batch.recommend_batch(shapes) == batch
    assert d_batch.cache_info()["hits"] == info["hits"] + len(shapes)
    # oracle mode batches through the vectorized cost-model sweep
    d_orc = SaraDispatcher()
    assert d_orc.recommend_batch(shapes) == \
        [SaraDispatcher().recommend(*s) for s in shapes]


def test_adaptnet_tpu_learns_tile_space():
    """Scaled-down training run on the (harder, 135-class) TPU tile space;
    the full-scale numbers live in benchmarks/bench_sara_tpu."""
    from repro.core.sara import train_adaptnet_tpu
    params, acc, geo = train_adaptnet_tpu(n_samples=40_000, epochs=8)
    assert acc >= 0.5
    assert geo <= 1.15
    d = SaraDispatcher(mode="adaptnet", adaptnet_params=params)
    cfg = d.recommend(1024, 1024, 1024)
    assert cfg in tcm.TILE_CONFIGS
