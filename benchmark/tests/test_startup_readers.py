"""The six `startup.*` readers on counters made by hand: a window whose
opening holds the program's `startup` (values, the slowest graph's name and
the table in `notes`), the parent's program, which has `compiles` /
`compile_s` and no `startup` (nothing to read, in any of the six), a start
that lowered no graph; and that the manifest lists the six, for every cell,
as metrics of the engine that move `setup_s`."""

import json

import pytest

from harness import layers, spec

CELL = {"name": "c", "end_to_end": {"setup_s": {}}}
GRAPHS = {
    "decode_multi[T=4]": {"trace_s": 1.5, "lower_s": 2.0, "backend_s": 0.0,
                          "wall_s": 3.6},
    "ragged_round[Tp=264]": {"trace_s": 2.25, "lower_s": 6.5,
                             "backend_s": 0.0, "wall_s": 8.9},
    "chain_sched": {"trace_s": 0.01, "lower_s": 0.02, "backend_s": 0.25,
                    "wall_s": 0.3},
}
STARTUP = {
    "at": {"init": 100.0, "params": 100.1}, "graphs": GRAPHS,
    "init_s": 7.25, "params_s": 6.0, "kv_pools_s": 1.0, "jit_fns_s": 0.125,
    "load_model_s": 7.5, "worker_ready_s": 8.0, "graphs_trace_s": 3.76,
    "graphs_lower_s": 8.52, "graphs_backend_s": 0.25, "compile_misses": 0,
}
WITH = {"compiles": 31, "compile_s": 4.75, "startup": STARTUP}
PARENT = {"compiles": 31, "compile_s": 4.75}
NO_GRAPH = {"compiles": 3, "compile_s": 0.5,
            "startup": dict(STARTUP, graphs={}, graphs_trace_s=0,
                            graphs_lower_s=0, graphs_backend_s=0,
                            compile_misses=3)}
NAMES = ("engine_init_s", "graphs_trace_s", "graphs_lower_s",
         "compile_backend_s", "slowest_graph_s", "cache_miss_compiles")


def read(name, engine0):
    entry = {"name": "startup." + name, "moves": "setup_s"}
    reader = layers.readers(dict(CELL, per_layer=[entry]))[0][1]
    # the window's closing counters have moved on: a reader takes the opening's
    later = dict(engine0, compiles=99, compile_s=99.0)
    run = {"win": {"c0": {"engine": engine0}, "c1": {"engine": later}},
           "notes": {}}
    return reader(run), run["notes"]


@pytest.mark.parametrize("name,want", zip(NAMES, (
    7.25, 3.76, 8.52, 4.75, 8.75, 0)))
def test_a_program_that_times_its_start_is_read(name, want):
    got, _ = read(name, WITH)
    assert got == pytest.approx(want) and float(got) == got


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_program_gives_nothing_to_read(name):
    got, notes = read(name, PARENT)
    assert got is None and notes == {}


@pytest.mark.parametrize("name,want", zip(NAMES, (
    7.25, 0, 0, 0.5, None, 3)))
def test_a_start_that_lowered_no_graph(name, want):
    got, notes = read(name, NO_GRAPH)
    assert got == want and notes == {}


def test_the_slowest_graph_is_named_in_the_notes_with_the_table():
    got, notes = read("slowest_graph_s", WITH)
    assert got == 8.75
    assert notes["startup"]["slowest_graph"] == "ragged_round[Tp=264]"
    assert notes["startup"]["graphs"] == GRAPHS
    assert notes["startup"]["at"] == STARTUP["at"]
    assert notes["startup"]["init_s"] == 7.25
    json.dumps(notes)                   # it goes into the detail file


@pytest.mark.parametrize("name,unit,source", zip(NAMES, (
    "s", "s", "s", "s", "s", "compiles"), (
    "program_span", "program_counter", "program_counter", "program_counter",
    "program_span", "program_counter")))
def test_the_manifest_lists_it_for_every_cell(name, unit, source):
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "startup." + name]
    assert entry["workloads"] == [w["name"] for w in manifest["workloads"]]
    assert (entry["unit"], entry["better"], entry["moves"], entry["source"],
            entry["layer"]) == (unit, "lower", "setup_s", source, "engine")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_startup_entry_has_a_reader_of_its_own():
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    listed = [m["name"] for m in manifest["per_layer"]
              if m["name"].startswith("startup.")]
    assert listed == ["startup." + n for n in NAMES]
    assert [layers.reader_path(name).stem for name in listed] \
        == ["startup_" + n for n in NAMES]
