"""``harness/generators/closed_sessions.py``: one document a client, shared
to the byte by all of the client's requests and by no other client's; the
documents' lengths the distribution's strata and whole blocks, the same
multiset under every seed; ``prompt_lengths`` covering what a prefix hit
leaves (a question) and the whole prompts."""

import json

import pytest

from harness import spec
from harness.generators import closed_sessions

PARAMS = json.loads((spec.BENCH / "traffic" / "doc-sessions.json")
                    .read_text())["params"]
SMALL = {"clients": 4, "document": {"dist": "uniform", "min": 64, "max": 128,
                                    "multiple": 16},
         "question": {"dist": "uniform", "min": 8, "max": 24},
         "output": {"dist": "uniform", "min": 4, "max": 8},
         "ramp_s": 2.0, "min_request_s": 1.0, "max_seq_len": 256}


def test_the_cells_traffic_file_is_what_the_issue_fixed():
    assert PARAMS["clients"] == 8
    assert (PARAMS["document"]["min"], PARAMS["document"]["max"],
            PARAMS["document"]["multiple"]) == (16384, 21504, 16)
    assert (PARAMS["question"]["min"], PARAMS["question"]["max"]) == (128, 512)
    assert (PARAMS["ramp_s"], PARAMS["min_request_s"],
            PARAMS["max_seq_len"]) == (30.0, 2.0, 24576)
    docs = closed_sessions.document_lengths(PARAMS)
    assert len(docs) == 8 and docs == sorted(docs)
    assert all(d % 16 == 0 and 16384 <= d <= 21504 for d in docs)
    # the longest session fits a row and the eight fit the default pool
    worst = max(docs) + 512 + PARAMS["output"]["max"]
    assert worst <= 24576 and 8 * worst < 294912


@pytest.mark.parametrize("seed", [1, 2, 4_000_000_123])
def test_one_document_a_client_shared_by_its_requests_alone(seed):
    plan = closed_sessions.generate(SMALL, None, seed, 6.0)
    assert plan["loop"] == "closed" and plan["ramp_s"] == 2.0
    docs = closed_sessions.document_lengths(SMALL)
    assert all(d % 16 == 0 for d in docs)
    heads = []
    for rows in plan["clients"]:
        assert len(rows) == int((2.0 + 6.0) / 1.0) + 2
        doc_len = next(d for d in docs if all(
            8 <= r["prompt_tokens"] - d <= 24 for r in rows))
        head = rows[0]["prompt"][:doc_len]
        for r in rows:
            assert r["prompt"][:doc_len] == head          # to the byte
            assert len(r["prompt"]) == r["prompt_tokens"]
            assert 4 <= r["max_tokens"] <= 8
            assert r["prompt_tokens"] + r["max_tokens"] <= 256
        # the questions differ
        assert len({r["prompt"][doc_len:] for r in rows}) == len(rows)
        heads.append((doc_len, head))
    # every stratum dealt once, and no two clients share a first block
    assert sorted(d for d, _ in heads) == docs
    assert len({h[:16] for _, h in heads}) == len(heads)


def test_the_same_multiset_under_every_seed_and_a_seeded_deal():
    docs = closed_sessions.document_lengths(SMALL)

    def work(seed):
        """(documents, questions, outputs) as sorted lengths, and the
        clients' first blocks."""
        plan = closed_sessions.generate(SMALL, None, seed, 6.0)
        held, questions, outputs = [], [], []
        for rows in plan["clients"]:
            doc = next(d for d in docs if all(
                8 <= r["prompt_tokens"] - d <= 24 for r in rows))
            held.append(doc)
            questions += [r["prompt_tokens"] - doc for r in rows]
            outputs += [r["max_tokens"] for r in rows]
        return (sorted(held), sorted(questions), sorted(outputs)), \
            [rows[0]["prompt"][:16] for rows in plan["clients"]]

    a, heads_a = work(7)
    b, heads_b = work(8)
    assert a == b and a[0] == docs
    assert heads_a != heads_b
    assert work(7) == (a, heads_a)


def test_prompt_lengths_cover_questions_and_whole_prompts():
    got = set(closed_sessions.prompt_lengths(SMALL))
    docs = closed_sessions.document_lengths(SMALL)
    assert set(range(8, 25)) <= got
    for d in docs:
        assert {d + 8, d + 24} <= got
    plan = closed_sessions.generate(SMALL, None, 3, 6.0)
    assert {r["prompt_tokens"] for rows in plan["clients"] for r in rows} <= got


def test_a_session_past_the_row_is_refused():
    with pytest.raises(ValueError, match="max_seq_len"):
        closed_sessions.generate(dict(SMALL, max_seq_len=100), None, 1, 6.0)
