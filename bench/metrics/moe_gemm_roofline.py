"""Least time of the window's grouped expert GEMMs over the device time of
the grouped kernel's events (grouped expert GEMM layer, ``kernels/rsa_gemm.py``
``rsa_grouped_gemm_pallas``).  Per step program, from the stats the engine
puts on the ``engine.sync`` span that ends it (summed over the MoE
layers): 2 x 3 x d x f operations per routed row (gate, up and down), and
the bytes of the weights of ``moe_experts_hit`` experts (3 x d x f each)
plus each routed row read and written once by each of the three GEMMs, in
bfloat16.  A step's MoE layers are bounded together, which can only make
the least time shorter."""

import costs
import engine_trace as et
import trace_reduce as tr

KERNELS = ("%moe_grouped_gemm",)
ITEMSIZE = 2


def read(run):
    lo, hi = run.trace_window
    kernel_s, n = tr.op_seconds(run.trace.ops[run.device], KERNELS, lo, hi)
    if n == 0 or kernel_s <= 0:
        return None
    d, f = run.cfg.d_model, run.cfg.moe.d_ff_expert
    least = 0.0
    for s in et.spans(run):
        if s.name != et.SYNC or "moe_routed_rows" not in s.args:
            continue
        rows = int(s.args["moe_routed_rows"])
        hit = int(s.args["moe_experts_hit"])
        nbytes = (hit * 3 * d * f + rows * 3 * (d + f)) * ITEMSIZE
        least += costs.least_seconds(6.0 * d * f * rows, nbytes, run.peaks)
    return 100.0 * least / kernel_s if least else None
