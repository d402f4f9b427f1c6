"""Closed loop over sessions: ``clients`` callers that each hold ONE long
document and ask one question after another about it, each waiting for its
reply before it sends the next request. A request is the client's document
followed by a fresh question, so from a client's second request on the
document is a prefix the cache holds (its length is a multiple of
``document.multiple``, the cache's block, so a hit covers all of it), and
what is prefilled is the question.

The documents' lengths are the distribution's ``clients`` strata, the same
multiset under every seed: the seed deals them to the clients and draws the
bytes. Questions and outputs are strata over the whole pool of requests,
dealt as ``closed_uniform`` deals them. All clients start together at the
start of the ramp, where the first requests prefill their documents.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List

import numpy as np

from .. import lengths
from ..spec import text_of


def document_lengths(params: Dict[str, Any]) -> List[int]:
    """The ``clients`` documents' lengths, ascending: one from each stratum,
    rounded down to a whole number of blocks."""
    spec, clients = params["document"], int(params["clients"])
    step = int(spec.get("multiple", 1))
    return [lengths.quantile(spec, (j + 0.5) / clients) // step * step
            for j in range(clients)]


def prompt_lengths(params: Dict[str, Any]) -> Iterable[int]:
    """What a prefix hit leaves to prefill (a question) and the whole
    prompts (a document and a question), so that the rounds of both are
    warmed."""
    q = params["question"]
    yield from lengths.bounds(q)
    for doc in document_lengths(params):
        yield from range(doc + int(q["min"]), doc + int(q["max"]) + 1)


def generate(params: Dict[str, Any], rate_rps: float, seed: int,
             seconds: float) -> Dict[str, Any]:
    rng = np.random.default_rng([int(seed), 0x5E5510])
    clients = int(params["clients"])
    ramp_s = float(params.get("ramp_s", 0.0))
    per_client = int((ramp_s + seconds) / float(params["min_request_s"])) + 2
    n = clients * per_client
    docs = document_lengths(params)
    docs = [docs[j] for j in rng.permutation(clients)]
    questions = lengths.blocked(params["question"], n, clients, rng)
    outputs = lengths.blocked(params["output"], n, clients, rng)
    limit = int(params["max_seq_len"])
    plan = []
    for c in range(clients):
        document = text_of(docs[c], rng)
        rows = []
        for j in range(per_client):
            q, out = questions[j * clients + c], outputs[j * clients + c]
            if docs[c] + q + out > limit:
                raise ValueError(
                    f"document {docs[c]} + question {q} + output {out} "
                    f"exceeds max_seq_len {limit}")
            rows.append({"id": f"c{c:02d}r{j:03d}",
                         "prompt": document + text_of(q, rng),
                         "prompt_tokens": docs[c] + q, "max_tokens": out})
        plan.append(rows)
    return {"loop": "closed", "ramp_s": ramp_s, "clients": plan}
