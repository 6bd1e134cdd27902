"""The one generator of requests, driven by a traffic file's parameters.

A traffic file (``traffic/<mix>.json``) gives the prompt and output length
ranges and the kind of arrivals.  Lengths are log-uniform and stratified:
each block of ``block`` requests holds the same ``block`` quantiles of the
distribution, and the seed chooses only their order, the pairing of prompt
and output lengths and the token ids.  So every seed asks for the same
work, in another order.

Arrivals: ``"backlog"`` is the only kind; its requests have no due times
(the harness keeps a queue full).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

ARRIVALS = ("backlog",)


@dataclasses.dataclass
class Spec:
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def _loguniform(rng, lo: int, hi: int, block: int) -> np.ndarray:
    q = rng.permutation(_quantiles(block))
    return np.rint(np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))).astype(int)


def generate(mix: dict, seed: int, vocab: int, count: int) -> List[Spec]:
    """``count`` requests from ``seed``."""
    if mix["arrivals"] not in ARRIVALS:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}; expected one of {ARRIVALS}")
    rng = np.random.default_rng(seed)
    block = mix["block"]
    out: List[Spec] = []
    while len(out) < count:
        plens = _loguniform(rng, mix["prompt_len"]["lo"], mix["prompt_len"]["hi"], block)
        olens = _loguniform(rng, mix["output_len"]["lo"], mix["output_len"]["hi"], block)
        for i in range(block):
            prompt = rng.integers(0, vocab, size=int(plens[i]), dtype=np.int32)
            out.append(Spec(prompt, int(olens[i])))
    return out[:count]
