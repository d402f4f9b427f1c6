"""Generators: the same bytes for a seed, the same work for every seed."""

import hashlib
import json

import pytest

from harness import generators, lengths, spec
from harness.session import piece_widths, ragged_widths

MIXES = sorted((spec.BENCH / "traffic").glob("*.json")) \
    + sorted((spec.TESTDATA / "traffic").glob("*.json"))


def digest(plan):
    return hashlib.sha256(
        json.dumps(plan, sort_keys=True).encode()).hexdigest()


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_byte_stable_and_same_work_across_seeds(path):
    tr = spec.load_traffic(path)
    gen = generators.load(tr["generator"])
    a = gen.generate(tr["params"], 3.0, 7, 20.0)
    assert digest(a) == digest(gen.generate(tr["params"], 3.0, 7, 20.0))
    b = gen.generate(tr["params"], 3.0, 8, 20.0)
    assert digest(a) != digest(b)

    def work(plan):
        reqs = plan["requests"] if plan["loop"] == "open" \
            else [r for c in plan["clients"] for r in c]
        if plan["loop"] == "open":
            reqs = [r for r in reqs if r["due_s"] >= plan["ramp_s"]]
        return (sorted(r["prompt_tokens"] for r in reqs),
                sorted(r["max_tokens"] for r in reqs))
    assert work(a) == work(b)
    reach = set(gen.prompt_lengths(tr["params"]))
    for plan in (a, b):
        reqs = plan["requests"] if plan["loop"] == "open" \
            else [r for c in plan["clients"] for r in c]
        assert all(len(r["prompt"]) == r["prompt_tokens"] for r in reqs)
        assert all(r["prompt_tokens"] in reach for r in reqs)
        firsts = [r["prompt"][:16] for r in reqs]
        assert len(set(firsts)) == len(firsts)      # nothing shared


def test_open_loop_seed_draws_the_arrivals_and_the_count_is_fixed():
    tr = spec.load_traffic(spec.BENCH / "traffic" / "chat-short.json")
    gen = generators.load(tr["generator"])
    schedules = set()
    for seed in range(4):
        plan = gen.generate(tr["params"], 2.5, seed, 40.0)
        inside = [r for r in plan["requests"] if r["due_s"] >= plan["ramp_s"]]
        assert len(inside) == 100
        assert all(r["due_s"] < plan["ramp_s"] + 40.0 for r in inside)
        due = [r["due_s"] for r in plan["requests"]]
        assert due == sorted(due)
        schedules.add((tuple(due), tuple(r["prompt_tokens"] for r in inside)))
        # Poisson given its count: bunches and lulls are there, so some
        # four-second stretch holds well over or under its ten requests
        per_4s = [sum(1 for r in inside
                      if k <= (r["due_s"] - plan["ramp_s"]) / 4.0 < k + 1)
                  for k in range(10)]
        assert max(per_4s) - min(per_4s) >= 4
    assert len(schedules) == 4          # another seed, another schedule


def test_strata_keep_the_tails_and_the_bounds():
    spec_ = {"dist": "lognormal", "median": 96, "sigma": 0.8,
             "min": 16, "max": 1024}
    import numpy as np

    xs = lengths.stratified(spec_, 200, np.random.default_rng(0))
    assert min(xs) == 16 and max(xs) > 600 and max(xs) <= 1024
    assert abs(sorted(xs)[100] - 96) <= 2
    assert lengths.quantile({"dist": "uniform", "min": 10, "max": 20}, 0.5) == 15


def test_round_widths_from_prompt_lengths():
    buckets = [16, 32, 64, 128, 256, 512]
    assert piece_widths(12, 256, buckets) == [16]
    assert piece_widths(256, 256, buckets) == [256]
    assert piece_widths(300, 256, buckets) == [64, 256]      # 256 + 44
    assert piece_widths(512, 256, buckets) == [256]
    assert list(ragged_widths(range(32, 129), 256, buckets)) == [32, 64, 128]
    reach = ragged_widths(range(512, 1921), 256, buckets)
    assert list(reach) == [16, 32, 64, 128, 256]
    assert reach[16] == 513 and reach[256] == 512


def test_closed_loop_rounds_carry_even_work_whatever_the_seed():
    import numpy as np

    spec_ = {"dist": "lognormal", "median": 96, "sigma": 0.8,
             "min": 16, "max": 1024}
    sizes = lengths.block_sizes(102, 8)
    assert sizes == [8] * 11 + [7, 7] and sum(sizes) == 102
    sums = []
    for seed in (0, 1):
        xs = lengths.blocked(spec_, 102, 8, np.random.default_rng(seed))
        assert sorted(xs) == sorted(
            lengths.stratified(spec_, 102, np.random.default_rng(9)))
        at, per_block = 0, []
        for size in sizes:
            per_block.append(sum(xs[at:at + size]))
            at += size
        sums.append(per_block)
    assert sums[0] == sums[1]                   # the seed moves no work
    # within a half of each other (the two blocks of seven hold the two
    # longest prompts and lack a short one)
    assert max(sums[0]) < 1.5 * min(sums[0])
    # undealt, a block can hold the eight longest: 2.5 times the heaviest
    plain = sorted(lengths.stratified(spec_, 102, np.random.default_rng(0)))
    assert sum(plain[-8:]) > 2.5 * max(sums[0])
