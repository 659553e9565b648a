"""Share of the rows the window's grouped expert GEMMs computed that carried
a routed token: the ``moe_routed_rows`` (live tokens x experts per token,
summed over the MoE layers) over the ``moe_gemm_rows`` (each expert's
segment padded to the row tile) of every step program in the window (MoE
dispatch layer, ``models/moe.py`` ``moe_serve``).  The engine puts both on
the ``engine.sync`` span that ends each step program; a program that
writes neither gives nothing here."""

import engine_trace as et


def read(run):
    routed = rows = 0
    for s in et.spans(run):
        if s.name == et.SYNC and "moe_gemm_rows" in s.args:
            routed += int(s.args["moe_routed_rows"])
            rows += int(s.args["moe_gemm_rows"])
    return 100.0 * routed / rows if rows else None
