"""Model operations of the live tokens of every step in the traced window
(each layer's weights per token, causal attention over its context, the
unembedding per live lane: the model module's ``model_flops``) over the
summed wall time of ``engine.step()`` and the chip's bf16 peak (model step
layer, ``models/serving.py``)."""


def read(run):
    wall = sum(run.step_walls)
    if not run.calls or wall <= 0:
        return None
    flops = 0.0
    for c in run.calls:
        lanes = [(int(s), int(n)) for s, n in zip(c.starts, c.rows) if n > 0]
        flops += run.model.model_flops(run.cfg, lanes, len(lanes))
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])
