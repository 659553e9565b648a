"""The one traffic generator: a mix file of parameters plus a seed give the
requests of a run and the times they are due.

Every seed gets the same multiset of sizes and inter-arrival gaps (the
quantiles of the mix's distributions), in an order and with token ids drawn
from the seed.  So runs with different seeds do the same amount of work,
and their spread measures the system rather than the draw.

Mix file keys (``bench/traffic/<mix>.json``; other keys, such as
``source``, are notes for the reader):

- ``arrivals``, one of
  - ``{"kind": "poisson", "rate_per_s": r}``: ``round(r * seconds)``
    requests over the window with exponential gaps;
  - ``{"kind": "backlog", "requests": n}``: all ``n`` due at the start;
  - ``{"kind": "phases", "cycle": [{"seconds": s, "rate_per_s": r}, ...]}``:
    the cycle repeats from the open until the window closes; each phase
    holds ``round(r * s)`` requests (of its part inside the window) with
    exponential gaps, so on/off bursts are a cycle with a zero-rate phase.
- ``prompt_tokens`` / ``output_tokens``: lognormal ``median`` and ``sigma``,
  clipped to ``[min, max]``.
- ``shared_prefix`` (optional): ``prompts`` system prompts of ``tokens``
  tokens each, chosen with Zipf exponent ``zipf_s``; ``prompt_tokens`` is
  then the unique turn that follows the system prompt.
- ``classes`` (optional): a list of populations in one queue, each with a
  ``share`` of the requests and its own ``prompt_tokens``,
  ``output_tokens`` and optional ``shared_prefix``; without it the mix is
  one population.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass
class Planned:
    """One request of the schedule: due ``due_s`` seconds after the window
    opens."""

    index: int
    due_s: float
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int
    prefix_id: int = -1         # which shared system prompt, -1 for none
    prefix_len: int = 0         # its tokens at the head of ``prompt``


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of the clipped
    lognormal ``spec``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(raw), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps at the quantiles of an exponential of rate ``rate``."""
    q = (np.arange(n) + 0.5) / max(n, 1)
    return -np.log1p(-q) / rate


def zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests pick each of ``k`` items under Zipf(s),
    by largest remainder, so every seed gets the same counts."""
    return share_counts(1.0 / np.arange(1, k + 1) ** s, n)


def share_counts(weights, n: int) -> np.ndarray:
    """``n`` split in proportion to ``weights`` by largest remainder."""
    w = np.asarray(weights, np.float64)
    share = w / w.sum() * n
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts


def classes(mix: Dict) -> List[Dict]:
    return mix.get("classes") or [mix]


def phases(arr: Dict, seconds: float) -> List[tuple]:
    """``(start, length, requests)`` of each phase inside the window."""
    out, t = [], 0.0
    period = sum(p["seconds"] for p in arr["cycle"])
    if period <= 0:
        raise ValueError("a phases cycle needs a positive length")
    while t < seconds:
        for p in arr["cycle"]:
            length = min(p["seconds"], seconds - t)
            if length > 0:
                out.append((t, length, int(round(p["rate_per_s"] * length))))
            t += p["seconds"]
    return out


def request_count(mix: Dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    if arr["kind"] == "phases":
        return max(1, sum(n for _, _, n in phases(arr, seconds)))
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def due_times(arr: Dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    if arr["kind"] == "poisson":
        gaps = rng.permutation(exp_gaps(arr["rate_per_s"], n - 1))
        return np.concatenate([[0.0], np.cumsum(gaps)])   # first at the open
    if arr["kind"] == "backlog":
        return np.zeros(n)
    out = []
    for start, length, k in phases(arr, seconds):
        if k == 0:
            continue
        # the first at the phase's start, the longest gap closes it
        gaps = exp_gaps(k / length, k)
        inner = np.concatenate([[0.0], rng.permutation(gaps[:-1])])
        out.append(start + np.cumsum(inner) * length / gaps.sum())
    return np.concatenate(out) if out else np.zeros(1)


def generate(mix: Dict, seed: int, seconds: float,
             vocab: int) -> List[Planned]:
    """The schedule of a run, sorted by due time."""
    rng = np.random.default_rng(seed)
    n = request_count(mix, seconds)
    pops = classes(mix)
    counts = share_counts([c.get("share", 1.0) for c in pops], n)
    cls = (rng.permutation(np.repeat(np.arange(len(pops)), counts))
           if len(pops) > 1 else np.zeros(n, np.int64))
    prompt_lens = np.zeros(n, np.int64)
    out_lens = np.zeros(n, np.int64)
    for c, pop in enumerate(pops):
        at = cls == c
        prompt_lens[at] = rng.permutation(
            quantile_lengths(pop["prompt_tokens"], int(at.sum())))
        out_lens[at] = rng.permutation(
            quantile_lengths(pop["output_tokens"], int(at.sum())))
    due = due_times(mix["arrivals"], n, seconds, rng)
    prefixes: List[np.ndarray] = []
    prefix_of = np.full(n, -1)
    for c, pop in enumerate(pops):
        shared = pop.get("shared_prefix")
        if not shared:
            continue
        at = np.flatnonzero(cls == c)
        first = len(prefixes)
        prefixes += [rng.integers(0, vocab, shared["tokens"], dtype=np.int32)
                     for _ in range(shared["prompts"])]
        picks = zipf_counts(shared["prompts"], shared["zipf_s"], len(at))
        prefix_of[at] = first + rng.permutation(
            np.repeat(np.arange(len(picks)), picks))
    plan = []
    for i in range(n):
        turn = rng.integers(0, vocab, int(prompt_lens[i]), dtype=np.int32)
        head = 0
        if prefix_of[i] >= 0:
            head = len(prefixes[prefix_of[i]])
            turn = np.concatenate([prefixes[prefix_of[i]], turn])
        plan.append(Planned(i, float(due[i]), turn, int(out_lens[i]),
                            int(prefix_of[i]), head))
    return plan


def _prefix_tokens(pop: Dict) -> int:
    return (pop.get("shared_prefix") or {"tokens": 0})["tokens"]


def max_context(mix: Dict) -> int:
    """Longest prompt plus output the mix can make."""
    return max(_prefix_tokens(p) + p["prompt_tokens"]["max"]
               + p["output_tokens"]["max"] for p in classes(mix))


def min_prompt(mix: Dict) -> int:
    return min(_prefix_tokens(p) + p["prompt_tokens"]["min"]
               for p in classes(mix))
