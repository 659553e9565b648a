"""Busy, idle and kernel time from device ops and host spans."""

import os

import pytest

import harness
import trace_reduce as tr
from trace_reduce import Op

RSA_GEMM = ("%rsa_gemm",)
PAGED_ATTN = harness.model_module("llama").PAGED_ATTN_KERNELS


def _trace():
    ops = [Op("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 10),
           Op("%rsa_gemm.3 = bf16[8,8]{1,0} custom-call(%a, %b)", 5, 20),
           Op("%copy.7 = s32[4]{0} copy(%c)", 30, 35),
           Op("%while.2 = (s32[]) while(%t)", 28, 95),
           Op("%paged_attention = bf16[4,8]{1,0} custom-call(%q)", 50, 70),
           Op("%fusion.2 = bf16[8]{0} fusion(%d)", 90, 120)]
    spans = [("bench.window", 0, 100), ("bench.step", 0, 40),
             ("bench.wait", 40, 48), ("bench.step", 48, 100)]
    return tr.Trace({0: ops}, spans)


def test_union_busy_and_gaps():
    t = _trace()
    lo, hi = t.window()
    assert (lo, hi) == (0, 100)
    busy = tr.busy(t.ops[0], lo, hi)
    assert busy == [(0, 20), (30, 35), (50, 70), (90, 100)]
    assert tr.covered(busy) == 55
    assert tr.gaps(busy, lo, hi) == [(20, 30), (35, 50), (70, 90)]


def test_idle_within_step_spans():
    t = _trace()
    busy = tr.busy(t.ops[0], 0, 100)
    steps = [(s, e) for n, s, e in t.spans if n == "bench.step"]
    total, idle = tr.idle_within(busy, steps)
    # steps cover 0-40 and 48-100 (92 ns); busy there: 20 + 5 + 20 + 10
    assert total == pytest.approx(92e-9)
    assert idle == pytest.approx(37e-9)


def test_kernel_seconds_and_breakdown():
    t = _trace()
    s, n = tr.op_seconds(t.ops[0], RSA_GEMM, 0, 100)
    assert (s, n) == (pytest.approx(15e-9), 1)
    s, n = tr.op_seconds(t.ops[0], PAGED_ATTN, 60, 100)
    assert (s, n) == (pytest.approx(10e-9), 1)
    # a fusion that merely runs inside the kernel's wrapper is no kernel
    assert not tr.matches(Op("%rsa_gemm_pad.1 = bf16[8]{0} pad(%a)", 0, 1),
                          RSA_GEMM)
    top = tr.top_ops(t.ops[0], 0, 100, n=2)
    assert [k for k, _ in top] == ["fusion bf16[8]", "paged_attention bf16[4,8]"]
    assert top[0][1] == pytest.approx(20e-9)
    gaps = tr.gaps(tr.busy(t.ops[0], 0, 100), 0, 100)
    labels = tr.label_gaps(gaps, t.spans, n=2)
    assert labels == [["bench.step", pytest.approx(20e-9)],
                      ["bench.wait", pytest.approx(15e-9)]]


def test_op_kind_strips_instance_numbers():
    op = Op("%rsa_gemm.62 = bf16[4096,20480]{1,0:T(8,128)(2,1)} "
            "custom-call(%a, %b)", 0, 1)
    assert tr.op_kind(op) == "rsa_gemm bf16[4096,20480]"
    assert tr.op_kind(Op("%copy-done.1 = s32[4]{0} copy-done(%c)", 0, 1)) \
        == "copy-done s32[4]"
    assert tr.is_container(Op("%while.4 = (s32[]) while(%t)", 0, 1))
    assert not tr.is_container(Op("%while_fusion.1 = f32[2]{0} fusion()",
                                  0, 1))


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: three steps of an RSA GEMM
    (256x512x256) and a paged decode (4 lanes), each in a bench.step.  The
    device's timestamps run 1-2 ms ahead of the host spans in it: each
    step's kernels start up to 2 ms before the span that launched them."""
    t = tr.load(os.path.join(os.path.dirname(__file__), "data"))
    assert list(t.ops) == [0]
    lo, hi = t.window()
    steps = [(s, e) for n, s, e in t.spans if n == "bench.step"]
    assert len(steps) == 3 and all(lo <= s < e <= hi for s, e in steps)
    early = lo - 2.5e6
    rsa_s, rsa_n = tr.op_seconds(t.ops[0], RSA_GEMM, early, hi)
    pa_s, pa_n = tr.op_seconds(t.ops[0], PAGED_ATTN, early, hi)
    assert (rsa_n, pa_n) == (3, 3) and rsa_s > 0 and pa_s > 0
    busy = tr.covered(tr.busy(t.ops[0], early, hi)) * 1e-9
    assert rsa_s + pa_s <= busy < (hi - early) * 1e-9
    assert [k for k, _ in tr.top_ops(t.ops[0], early, hi, n=2)] == \
        ["paged_attention bf16[4,2,4,128]", "rsa_gemm bf16[256,256]"]
