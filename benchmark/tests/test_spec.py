"""BENCHMARK.json against the files it names, and the harness against the
rule that it names no cell, no model and no mix."""

import json
import re

import pytest

from harness import generators, layers, metrics, spec
from harness.session import check_spec
from harness.window import slice_start

MANIFESTS = [spec.CHECKOUT / "BENCHMARK.json",
             spec.TESTDATA / "BENCHMARK.json"]


@pytest.mark.parametrize("path", MANIFESTS, ids=["real", "stand-in"])
def test_every_cell_has_its_files_and_reports_what_its_metrics_move(path):
    manifest = json.loads(path.read_text())
    assert {c["name"] for c in manifest["configs"]} \
        == {w["config"] for w in manifest["workloads"]}
    for w in manifest["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell["_stand_in"] == (path.parent == spec.TESTDATA)
        check_spec(cell)                    # readers found, moves reported
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        assert set(cell["end_to_end"]) <= set(metrics.END_TO_END) | {"setup_s"}
        generators.load(cell["_traffic"]["generator"])
        assert cell["_golden"].is_file()
        assert cell["_config"]["name"] == w["config"]
        assert ("rate_rps" in cell) == ("limits" in cell)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) \
            <= {w["name"] for w in manifest["workloads"]}, m["name"]


def test_a_metric_that_moves_what_the_cell_lacks_is_refused():
    cell = spec.load_cell("tiny-mistral.decode")
    cell["per_layer"] = cell["per_layer"] + [
        {"name": "batcher.queue_wait_p90_ms", "unit": "ms",
         "moves": "ttft_p90_ms"}]
    with pytest.raises(spec.SpecError, match="does not report"):
        layers.readers(cell)


def test_limits_of_the_contract():
    manifest = json.loads(MANIFESTS[0].read_text())
    assert len(json.dumps(manifest)) < 64 * 1024
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert all(len(w["why"]) <= 200 for w in manifest["workloads"])
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_the_harness_names_no_cell_model_or_mix():
    names = set()
    for path in MANIFESTS:
        manifest = json.loads(path.read_text())
        for w in manifest["workloads"]:
            names |= {w["name"], w["config"], w["traffic"]}
            names.add(spec.load_cell(w["name"])["_config"]["registry_model"])
    pattern = re.compile("|".join(re.escape(n) for n in sorted(names)))
    code = sorted((spec.BENCH / "harness").rglob("*.py")) + [
        spec.BENCH / "run.py", spec.BENCH / "sweep.py",
        spec.BENCH / "spread.py",
        spec.BENCH / "make_golden.py", spec.BENCH / "compile_only.py"]
    for path in code:
        body = path.read_text()
        # prose may give examples; code may not
        body = re.sub(r'"""(.|\n)*?"""', "", body)
        body = re.sub(r"#.*", "", body)
        assert not pattern.search(body), path.name


def test_the_traced_slice_opens_at_a_request():
    closed = {"loop": "closed", "ramp_s": 6.0, "clients": []}
    assert slice_start(closed, 51.0, 5.0) == 23.0
    plan = {"loop": "open", "ramp_s": 4.0, "requests": [
        {"due_s": 4.0 + d} for d in (1.0, 20.0, 27.5, 30.0)]}
    assert slice_start(plan, 51.0, 5.0) == 27.5     # first due after 23.0
    plan["requests"] = [{"due_s": 4.0 + d} for d in (1.0, 20.0, 49.0)]
    assert slice_start(plan, 51.0, 5.0) == 46.0     # never past the end
    plan["requests"] = [{"due_s": 4.0 + 1.0}]
    assert slice_start(plan, 51.0, 5.0) == 23.0     # none later: the middle
