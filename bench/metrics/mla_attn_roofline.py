"""``paged_attn_roofline``'s reading for a model whose paged kernels are the
absorbed-MLA ones (MLA paged kernels layer, ``kernels/paged_attn.py``
``paged_mla_decode_pallas``, ``paged_mla_prefill_pallas``): the model
module names the kernels (``PAGED_ATTN_KERNELS``) and counts their
operations and bytes over 576-wide latent rows (``attn_flops``,
``attn_bytes``)."""

import harness


def read(run):
    return harness.reader("paged_attn_roofline", run.cell.bench_dir)(run)
