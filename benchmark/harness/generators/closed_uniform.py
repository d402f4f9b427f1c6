"""Closed loop: ``clients`` callers that each wait for their reply before
they send the next request. All start together at the start of the ramp.
Lengths are strata over the whole pool of requests, dealt so that the k-th
requests of all clients together hold short and long ones in the
proportions of the whole (``lengths.blocked`` with a block per round of
requests); the seed decides which client gets which. Each client holds more
requests than it can finish; the run stops sending at the end of the window.
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
    rng = np.random.default_rng([int(seed), 0xC105ED])
    clients = int(params["clients"])
    ramp_s = float(params.get("ramp_s", 0.0))
    # a request takes at least min_request_s (a traffic parameter, from the
    # fastest decode the chip could do), so this many are never used up
    per_client = int((ramp_s + seconds) / float(params["min_request_s"])) + 2
    n = clients * per_client
    prompts = lengths.blocked(params["prompt"], n, clients, rng)
    outputs = lengths.blocked(params["output"], n, clients, rng)
    plan = []
    for c in range(clients):
        plan.append([
            {"id": f"c{c:02d}r{j:03d}",
             "prompt": text_of(prompts[j * clients + c], rng),
             "prompt_tokens": prompts[j * clients + c],
             "max_tokens": outputs[j * clients + c]}
            for j in range(per_client)
        ])
    return {"loop": "closed", "ramp_s": ramp_s, "clients": plan}
