"""The DeepSeek-V3 family's module and what the Moonlight cell adds: the
counts pinned by hand at Moonlight-16B-A3B's published widths, the three
new readers on hand-made traces, and the cell's files found by name (the
new cell through ``bench/models/deepseek_v3.py``, the old cells still
through ``llama.py``)."""

import json

import numpy as np
import pytest

import engine_trace as et
import harness
import trace_reduce as tr
from conftest import TINY_CONF

CELL = "moonlight.longgen-batch"
NEW = ("moe_row_use", "moe_gemm_roofline", "mla_attn_roofline")


def _conf():
    return json.loads((harness.ROOT / "bench/configs/moonlight-16b-a3b.json")
                      .read_text())


@pytest.fixture(scope="module")
def ds():
    return harness.model_module("deepseek_v3")


@pytest.fixture(scope="module")
def cfg(ds):
    return ds.arch_config(_conf())


def test_cell_loads_through_its_model_module(ds):
    cell = harness.load_cell(harness.ROOT, CELL)
    assert cell.model.__file__ == ds.__file__
    assert cell.conf["model_type"] == "deepseek_v3"
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == list(NEW)
    # the mean gap decides: the widest gap of the program and of the
    # float8 control overlap (the limits file's readings)
    r = cell.limits["readings"]
    assert r["mean_logit_gap"]["lower"] < cell.limits["mean_logit_gap"] \
        < r["mean_logit_gap"]["upper"]
    assert cell.limits.get("max_logit_gap") is None
    for old in ("yi34b.chat", "dsk7b.longdoc-batch"):
        other = harness.load_cell(harness.ROOT, old)
        assert other.model.__name__ == "bench_model_llama"
        assert not set(NEW) & {m["name"] for m in other.per_layer}


def test_arch_config_maps_the_published_block(cfg):
    assert (cfg.family, cfg.attention_type) == ("moe", "mla")
    assert cfg.mla.q_lora_rank is None
    assert (cfg.mla.kv_lora_rank, cfg.mla.qk_nope_head_dim,
            cfg.mla.qk_rope_head_dim, cfg.mla.v_head_dim) == (512, 128, 64,
                                                               128)
    m = cfg.moe
    assert (m.num_experts, m.experts_per_token, m.num_shared_experts,
            m.d_ff_expert) == (64, 6, 2, 1408)
    assert (m.scoring_func, m.topk_method, m.norm_topk_prob,
            m.routed_scaling_factor) == ("sigmoid", "noaux_tc", True, 2.446)
    assert (cfg.first_dense_layers, cfg.num_layers, cfg.d_ff) == (1, 7,
                                                                 11264)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.vocab_size) == (50000.0, 1e-5,
                                                              163840)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("rope_scaling", {"factor": 40}),
    ("scoring_func", "softmax")])
def test_arch_config_refuses_what_is_not_mapped(ds, key, value):
    with pytest.raises(ValueError):
        ds.arch_config(dict(_conf(), **{key: value}))


def test_counts_pinned_by_hand(ds, cfg):
    # MLA 13.76M + 6 routed experts x 8.65M + 2 shared x 8.65M
    assert ds.attn_params(cfg) == 13_762_560
    assert ds.expert_params(cfg) == 8_650_752
    assert round(ds.active_params(cfg, True) / 1e6, 1) == 83.0
    assert round(ds.active_params(cfg, False) / 1e6, 1) == 83.0
    # the cut: the dense layer, 6 MoE layers, embedding and head
    assert round(ds.total_params(cfg) / 1e9, 2) == 4.26
    assert ds.latent_row_bytes(cfg, 2) == 1152
    assert ds.attn_layers(cfg) == 7
    assert [ds.site_layers(cfg, s) for s in (
        "layer.mla.q_proj", "layer.mlp.gate", "moe.expert.gate",
        "moe.shared.down", "moe.router", "unembed")] == [7, 1, 6, 6, 6, 1]


def test_attention_counts(ds, cfg):
    lanes = [(10, 1), (0, 0), (100, 4)]
    keys = 11 + (4 * 100 + 10)
    assert ds.attn_flops(cfg, lanes) == 16 * (2 * 576 + 2 * 512) * keys
    assert ds.attn_bytes(cfg, lanes, 2) == (
        (11 + 104) * 1152 + 5 * 16 * (2 * 512 + 64) * 2)
    flops = ds.model_flops(cfg, lanes, 2)
    weights = (ds.active_params(cfg, False)
               + 6 * (ds.active_params(cfg, True) + 2048 * 64))
    assert flops == 2.0 * weights * 5 + 7 * ds.attn_flops(cfg, lanes) \
        + 2.0 * 2048 * 163840 * 2


def _op(name, start_ms, end_ms):
    return tr.Op(f"{name}.1 = bf16[8,8]{{1,0}} custom-call(%a, %b)",
                 start_ms * 1e6, end_ms * 1e6)


def _run(cfg, ds, ops):
    cell = harness.load_cell(harness.ROOT, CELL)
    run = harness.RunData(cell, cfg, harness.peaks_for("TPU v5 lite"))
    run.trace = tr.Trace(ops={0: ops})
    run.trace_window = (0.0, 1e9)
    run.device = 0
    run.calls = [harness.StepCall("prefill_chunk", np.array([0, 64]),
                                  np.array([128, 64])),
                 harness.StepCall("decode", np.array([128, 128]),
                                  np.array([1, 1]))]
    return run


def _span(name, start, end, **args):
    return et.Span(f"engine.{name}", start * 1e6, end * 1e6, args)


def test_new_readers_on_a_hand_made_trace(ds, cfg, monkeypatch):
    """A chunk step and a decode step (times in ms), each program's MoE
    stats on the sync after it, and the kernels' device events."""
    found = [
        _span("step", 0, 100, step_num=0),
        _span("prefill_chunk", 6, 10, lanes=2, live_rows=192, rows=256),
        _span("sync", 10, 50, moe_routed_rows=6912, moe_gemm_rows=8192,
              moe_experts_hit=384),
        _span("paged_decode", 55, 57, lanes=2, width=16, kv_rows=256),
        _span("sync", 57, 80, moe_routed_rows=72, moe_gemm_rows=1024,
              moe_experts_hit=64),
    ]
    monkeypatch.setattr(et, "spans", lambda run: found)
    ops = [_op("%moe_grouped_gemm", 20, 45), _op("%moe_grouped_gemm", 60, 70),
           _op("%mla_paged_prefill_attention", 12, 19),
           _op("%mla_paged_attention", 58, 59), _op("%rsa_gemm", 45, 48)]
    run = _run(cfg, ds, ops)
    read = {n: harness.reader(n)(run) for n in NEW}
    assert read["moe_row_use"] == pytest.approx(100.0 * 6984 / 9216)
    d, f, peaks = 2048, 1408, run.peaks

    def least(rows, hit):
        return max(6.0 * d * f * rows / peaks["bf16_flops_per_s"],
                   (hit * 3 * d * f + rows * 3 * (d + f)) * 2
                   / peaks["hbm_bytes_per_s"])
    assert read["moe_gemm_roofline"] == pytest.approx(
        100.0 * (least(6912, 384) + least(72, 64)) / 0.035)
    assert read["mla_attn_roofline"] == pytest.approx(
        harness.reader("paged_attn_roofline")(run))
    for name, v in read.items():
        assert 0 < v <= 100, (name, v)


def test_new_readers_read_nothing_without_their_sources(ds, cfg,
                                                        monkeypatch):
    """A program without the grouped kernel, the MLA kernels or the MoE
    stats (the parent's, or a Llama cell's): nothing to read, no error."""
    monkeypatch.setattr(et, "spans", lambda run: [
        _span("paged_decode", 55, 57, lanes=2, width=16, kv_rows=256),
        _span("sync", 57, 80)])
    run = _run(cfg, ds, [_op("%rsa_gemm", 20, 45),
                         _op("%paged_attention", 50, 52)])
    assert all(harness.reader(n)(run) is None for n in NEW)


def test_tiny_same_family_run_is_correct(tiny_cell):
    """The whole timed path on the CPU for a tiny DeepSeek-V3-family
    configuration (d 64, 8 experts top-2 + 1 shared, 2 heads, r 32, rd 16,
    1 dense + 2 MoE layers): every served token is the float32
    reference's best."""
    import time
    conf = {k: v for k, v in TINY_CONF.items() if k not in (
        "num_hidden_layers", "model_type", "name")}
    conf.update(
        name="tiny-ds", model_type="deepseek_v3", num_hidden_layers=3,
        num_attention_heads=2, num_key_value_heads=2,
        first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, q_lora_rank=None,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        moe_intermediate_size=32, scoring_func="sigmoid",
        topk_method="noaux_tc", n_group=1, topk_group=1,
        norm_topk_prob=True, routed_scaling_factor=2.446)
    tiny_cell.conf = conf
    tiny_cell.model = harness.model_module("deepseek_v3")
    out = harness.run_cell(tiny_cell, 2147483999, 3.0, False,
                           time.perf_counter(),
                           {"platform": "cpu", "kind": "cpu", "count": 1},
                           log=lambda m: None)
    assert out["failed"] == 0 and out["attempted"] > 5
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"], gap
    assert out["correct"] is True
