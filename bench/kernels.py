"""How the program's kernels are named in a device trace.  A device op's
name is its HLO instruction, ``%<name>.<n> = <type> <opcode>(...)``; a
Pallas kernel is a ``custom-call`` named after the jitted wrapper that
launches it (``kernels/ops.py``)."""

RSA_GEMM = ("%rsa_gemm",)
PAGED_ATTN = ("%paged_attention", "%paged_prefill_attention")
