"""Least time of the RSA GEMM calls in the traced window, from each executed
site's unpadded M, K, N (2MKN operations; A + B + C bytes), each site
counted once for every layer that runs it (the model module's
``site_layers``), over the device time of the RSA kernel's events
(dispatch + RSA kernel layer, ``dispatch/``, ``kernels/rsa_gemm.py``)."""

import costs
import trace_reduce as tr

KERNELS = ("%rsa_gemm",)     # every GEMM of the program, whatever the model


def read(run):
    lo, hi = run.trace_window
    kernel_s, n = tr.op_seconds(run.trace.ops[run.device], KERNELS, lo, hi)
    if n == 0 or kernel_s <= 0:
        return None
    per_call = {}
    for scope, sites in run.sites.items():
        t = 0.0
        for name, m, k, n_ in sites:
            mult = run.model.site_layers(run.cfg, name)
            out = 4 if name == "unembed" else 2
            t += mult * costs.least_seconds(
                costs.gemm_flops(m, k, n_),
                costs.gemm_bytes(m, k, n_, 2, out), run.peaks)
        per_call[scope] = t
    least = sum(per_call.get(c.kind, 0.0) for c in run.calls)
    return 100.0 * least / kernel_s
