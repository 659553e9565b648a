"""Share of the prompt tokens of the window's admitted requests that came
from cached pages instead of being prefilled (KV pool / prefix cache
layer, ``serving/prefix_cache.py``)."""


def read(run):
    admitted = [r.req for r in run.recs if r.req.t_admit >= 0]
    prompt = sum(q.prompt_len for q in admitted)
    if not prompt:
        return None
    return 100.0 * sum(q.cached_prefix_tokens for q in admitted) / prompt
