"""Open loop: independent users. Arrivals are a Poisson process at the
cell's fixed rate, conditioned on its count: given how many arrive in a
stretch of time, Poisson arrivals are uniform order statistics over it, so
a run offers ``round(rate * seconds)`` requests inside the window (and
``round(rate * ramp_s)`` in the ramp before it) at instants drawn
independently and sorted. Bursts and lulls of every length up to the
window's are there; only the luck of the total count is not. Prompt and
output lengths come from the distributions in the traffic file (lognormal
or uniform, clipped) as ``lengths.stratified``: the same multiset of lengths
in every run, in an order the seed draws. Prompts are random letters: no
two share a first block of the cache, so the prefix cache never hits.

``--seed`` draws all of it: the arrival instants, the order of the lengths
and the prompts' bytes. Two runs with one seed offer the same requests at
the same instants; two seeds offer the same work at other instants.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

import numpy as np

from .. import lengths
from ..spec import text_of


def prompt_lengths(params: Dict[str, Any]) -> Iterable[int]:
    return lengths.bounds(params["prompt"])


def generate(params: Dict[str, Any], rate_rps: float, seed: int,
             seconds: float) -> Dict[str, Any]:
    if not rate_rps or rate_rps <= 0:
        raise ValueError("an open-loop cell needs a positive rate_rps")
    rng = np.random.default_rng([int(seed), 0x0BE7])
    ramp_s = float(params.get("ramp_s", 0.0))
    n_ramp = int(round(rate_rps * ramp_s))
    n_win = max(1, int(round(rate_rps * seconds)))
    due = np.concatenate([
        np.sort(rng.uniform(0.0, ramp_s, n_ramp)),
        ramp_s + np.sort(rng.uniform(0.0, seconds, n_win)),
    ])
    prompts = lengths.stratified(params["prompt"], n_ramp, rng) \
        + lengths.stratified(params["prompt"], n_win, rng)
    outputs = lengths.stratified(params["output"], n_ramp, rng) \
        + lengths.stratified(params["output"], n_win, rng)
    requests = [
        {"id": f"r{j:05d}", "due_s": float(due[j]),
         "prompt": text_of(prompts[j], rng), "prompt_tokens": prompts[j],
         "max_tokens": outputs[j]}
        for j in range(len(due))
    ]
    return {"loop": "open", "ramp_s": ramp_s, "requests": requests}
