"""Start-up measured inside the program (docs/observability.md, "Start-up"):
the compile log's three stages driven by hand, the phases of a tiny
engine's load and the row a graph ``lower_serving_graphs`` leaves in
``get_stats()["startup"]``, a sleep planted in one graph's traced function,
a request that compiles after the start, the worker's way to READY, and the
heartbeat's route to ``/metrics``."""

import threading
import time

import pytest

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.runtime import flight
from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.server.observability import MetricsCollector
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
    TpuTopology,
    WorkerState,
)
from distributed_gpu_inference_tpu.utils.device import (
    CompileLog,
    compile_log,
    roomy_stack,
)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
ENGINE_PHASES = ("init", "params", "kv_pools", "jit_fns")
STAGES = ("trace_s", "lower_s", "backend_s")


# --------------------------------------------------------------------- #
# (a) the compile log, its handlers driven by hand
# --------------------------------------------------------------------- #

@pytest.fixture()
def log(monkeypatch):
    """A compile log of the test's own, that JAX never calls."""
    from jax import monitoring

    for name in ("register_event_listener", "register_scalar_listener",
                 "register_event_duration_secs_listener"):
        monkeypatch.setattr(monitoring, name, lambda listener: None)
    return CompileLog()


def stage(log, event, secs, fn, inside=()):
    """One stage event as JAX reports it: its start, whatever ran inside
    it, its end."""
    log._on_start(event, 0.0, fun_name=fn)
    for inner in inside:
        stage(log, *inner)
    log._on_duration(event, secs, fun_name=fn)


def by_stage(log, key="fn"):
    return [(r["stage"], r[key]) for r in log.rows]


def test_a_trace_inside_a_trace_is_counted_once(log):
    # f.lower() of a sin and a matmul: sin, matmul, then f, which holds both
    stage(log, TRACE, 1.0, "f", inside=[
        (TRACE, 0.25, "inner", [(TRACE, 0.125, "sin")]),
        (TRACE, 0.5, "matmul")])
    stage(log, LOWER, 2.0, "jit(f)")
    assert (log.trace_s, log.lower_s) == (1.0, 2.0)
    assert by_stage(log) == [("trace", "f"), ("lower", "jit(f)")]
    assert (log.count, log.seconds, log.misses) == (0, 0.0, 0)


def test_a_trace_inside_a_lowering_belongs_to_the_lowering(log):
    # a lowering rule that traces a jitted function (a kernel's body)
    stage(log, LOWER, 3.0, "jit(round)", inside=[(TRACE, 1.0, "where")])
    assert (log.trace_s, log.lower_s) == (0.0, 3.0)
    assert by_stage(log) == [("lower", "jit(round)")]


@pytest.mark.parametrize("outcome,cache,misses", [
    (MISS, "miss", 1), (HIT, "hit", 0), (None, "uncached", 0)])
def test_a_backend_request_carries_what_the_cache_did(log, outcome, cache,
                                                      misses):
    log._on_start(BACKEND, 0.0, fun_name="jit(f)")
    if outcome:
        log._on_event(outcome)
    log._on_duration(BACKEND, 4.0, fun_name="jit(f)")
    assert (log.count, log.seconds, log.misses) == (1, 4.0, misses)
    assert log.rows == [{"fn": "jit(f)", "secs": 4.0, "stage": "backend",
                         "graph": "jit(f)", "cache": cache}]


def test_a_miss_then_a_hit_count_one_miss_and_both_requests(log):
    log._on_event(MISS)
    stage(log, BACKEND, 4.0, "jit(f)")
    log._on_event(HIT)
    stage(log, BACKEND, 0.5, "jit(g)")
    assert (log.count, log.seconds, log.misses) == (2, 4.5, 1)
    assert [r["cache"] for r in log.rows] == ["miss", "hit"]


def test_a_backend_request_inside_a_trace_counts_as_a_request_only(log):
    # a concrete call met while tracing: the request is the process's, its
    # seconds are inside the outer trace's and not the label's
    row = log.label("outer[T=4]")
    stage(log, TRACE, 2.0, "outer", inside=[(BACKEND, 0.5, "jit(add)")])
    log.unlabel()
    assert (log.count, log.seconds, log.trace_s) == (1, 0.5, 2.0)
    assert row == {"trace_s": 2.0, "lower_s": 0.0, "backend_s": 0.0}


def test_a_label_takes_its_threads_events_and_no_other_threads(log):
    rows = {}

    def lower(graph, trace_s):
        rows[graph] = log.label(graph)
        log._on_start(TRACE, 0.0, fun_name="round")
        time.sleep(0.02)            # both threads inside their trace
        log._on_duration(TRACE, trace_s, fun_name="round")
        stage(log, LOWER, 2 * trace_s, "jit(round)")
        log._on_event(MISS)
        stage(log, BACKEND, 4 * trace_s, "jit(round)")
        log.unlabel()
        stage(log, TRACE, 8 * trace_s, "later")     # no label any more

    threads = [threading.Thread(target=lower, args=a)
               for a in (("a[T=1]", 1.0), ("b[T=4]", 0.25))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rows == {
        "a[T=1]": {"trace_s": 1.0, "lower_s": 2.0, "backend_s": 4.0},
        "b[T=4]": {"trace_s": 0.25, "lower_s": 0.5, "backend_s": 1.0}}
    assert log.trace_s == 1.25 + 8 * 1.25 and log.lower_s == 2.5
    assert (log.count, log.seconds, log.misses) == (2, 5.0, 2)
    labelled = sorted((r["graph"], r["stage"]) for r in log.rows
                      if r["fn"] != "later")
    assert labelled == sorted((g, s) for g in rows
                              for s in ("trace", "lower", "backend"))
    assert {r["graph"] for r in log.rows if r["fn"] == "later"} == {"later"}


def test_rows_stop_growing_and_the_totals_do_not(log, monkeypatch):
    monkeypatch.setattr(CompileLog, "ROWS_KEPT", 3)
    for _ in range(5):
        stage(log, TRACE, 1.0, "f")
    assert len(log.rows) == 3 and log.trace_s == 5.0


def test_a_phase_keeps_its_seconds_and_its_start():
    startup = {}
    before = time.monotonic()
    with flight.phase("dgi.test.load", startup, "load", model="m"):
        with flight.phase("dgi.test.load.part", startup, "part"):
            time.sleep(0.01)
    assert before <= startup["at"]["load"] <= startup["at"]["part"]
    assert 0.009 < startup["part_s"] <= startup["load_s"]
    outer = {"at": {"ready": 1.5}, "ready_s": 2.0}
    flight.adopt_phases(startup, outer)
    assert startup["ready_s"] == 2.0 and startup["at"]["ready"] == 1.5
    assert set(startup["at"]) == {"load", "part", "ready"}


# --------------------------------------------------------------------- #
# (a2) the roomy frame the graphs are traced and lowered beneath
# --------------------------------------------------------------------- #

def _frames_between(call):
    """How many frames ``call`` finds between itself and this one."""
    import sys

    here = sys._getframe()

    def count():
        frame, n = sys._getframe(1), 0
        while frame is not here:
            frame, n = frame.f_back, n + 1
        return n

    return call(count)


def test_a_roomy_stack_is_one_frame_of_half_a_mib():
    """One frame more than a plain call, and that frame's locals alone
    are past CPython's 16 KiB chunk thirty times over: what is called
    beneath it lies in the room its chunk leaves."""
    assert _frames_between(roomy_stack) == _frames_between(lambda f: f()) + 1
    seen = {}

    def look():
        import sys

        code = sys._getframe(1).f_code
        seen.update(name=code.co_name, slots=code.co_nlocals)

    roomy_stack(look)
    assert seen["name"] == "roomy_stack" and seen["slots"] * 8 >= 1 << 19


@pytest.mark.parametrize("where", ["main", "thread", "nested"])
def test_a_roomy_stack_returns_and_raises_what_the_call_does(where):
    def run(out):
        out.append(roomy_stack(lambda: 7))
        with pytest.raises(ZeroDivisionError):
            roomy_stack(lambda: 1 // 0)

    out = []
    if where == "thread":
        thread = threading.Thread(target=run, args=(out,))
        thread.start()
        thread.join()
    elif where == "nested":
        roomy_stack(lambda: run(out))
    else:
        run(out)
    assert out == [7]


# --------------------------------------------------------------------- #
# (b) a tiny engine's start
# --------------------------------------------------------------------- #

CFG = dict(max_batch_size=4, max_seq_len=128, prefill_buckets=(16, 32, 64),
           multi_step=4)


def build(params=None):
    engine = TPUEngine("llama3-tiny", EngineConfig(**CFG), params=params)
    return engine, engine.lower_serving_graphs([1, 4], [16])


@pytest.fixture(scope="module")
def started():
    """An engine built and its round graphs lowered: what a start leaves.
    The process has one log and its rows stop growing at ``ROWS_KEPT``,
    which a worker process that ran many files before this one may have
    passed: the rows it kept are dropped first, so this module's are
    there to be read whatever ran before."""
    log = compile_log()
    with log._lock:
        del log.rows[:]
    engine, graphs = build()
    return {"engine": engine, "graphs": graphs,
            "startup": engine.get_stats()["startup"]}


@pytest.mark.parametrize("phase", ENGINE_PHASES)
def test_every_phase_of_the_load_has_seconds_and_a_start(started, phase):
    st = started["startup"]
    assert st[phase + "_s"] > 0.0
    assert st["at"]["init"] <= st["at"][phase] <= time.monotonic()
    assert st[phase + "_s"] <= st["init_s"]


def test_the_phases_of_the_load_lie_inside_it_in_order(started):
    st = started["startup"]
    assert st["params_s"] + st["kv_pools_s"] + st["jit_fns_s"] <= st["init_s"]
    at = st["at"]
    assert at["init"] <= at["params"] <= at["kv_pools"] <= at["jit_fns"]
    assert at["params"] + st["params_s"] <= at["kv_pools"] + 1e-3


def test_one_row_a_returned_graph_and_its_stages_inside_its_wall(started):
    table = started["startup"]["graphs"]
    assert list(table) == list(started["graphs"])
    assert {"decode_multi[T=1]", "decode_multi[T=4]", "chain_sched",
            "ragged_round[Tp=16]", "chain_round[Tp=16]",
            "merge_core"} <= set(table)
    for name, row in table.items():
        assert row["trace_s"] > 0.0 and row["lower_s"] > 0.0, name
        assert sum(row[s] for s in STAGES) <= row["wall_s"] + 1e-3, name
        # a round graph is lowered and left to its caller to compile; a
        # small program is run once, and that compiles it
        ran = not name.startswith(("decode_multi", "ragged_round"))
        assert (row["backend_s"] > 0.0) == ran, name


@pytest.mark.parametrize("stage_s", STAGES)
def test_the_sums_are_the_rows(started, stage_s):
    st = started["startup"]
    assert st["graphs_" + stage_s] == pytest.approx(
        sum(row[stage_s] for row in st["graphs"].values()))
    assert st["graphs_" + stage_s] > 0.0


def test_the_process_totals_are_the_compile_logs(started):
    stats = started["engine"].get_stats()
    log = compile_log()
    assert stats["compiles"] == log.count > 0
    assert stats["compile_s"] == log.seconds > 0.0
    assert stats["compile_trace_s"] == log.trace_s > 0.0
    assert stats["compile_lower_s"] == log.lower_s > 0.0
    assert stats["compile_misses"] == log.misses \
        == stats["startup"]["compile_misses"]
    # the labelled graphs are among the process's stage events
    assert stats["startup"]["graphs_trace_s"] <= log.trace_s
    assert stats["startup"]["graphs_lower_s"] <= log.lower_s
    labelled = {r["graph"] for r in log.rows} & set(started["graphs"])
    assert labelled == set(started["graphs"])


def test_get_stats_hands_out_a_copy(started):
    engine = started["engine"]
    first = engine.get_stats()["startup"]
    first["graphs"]["merge_core"]["trace_s"] = -1.0
    first["at"]["init"] = -1.0
    again = engine.get_stats()["startup"]
    assert again["graphs"]["merge_core"]["trace_s"] > 0.0
    assert again["at"]["init"] > 0.0


# the sleep is a quarter of a second where the issue says a fifth: a row
# that moved by the sleep exactly reads "at least 0.2" one time in two
SLEEP_S, MOVED_S, STILL_S = 0.25, 0.2, 0.05
SLOW = "ragged_round[Tp=32]"
READINGS = 6


def test_a_sleep_in_one_graphs_trace_lands_on_that_graph(started,
                                                         monkeypatch):
    """The lowest of several readings a side, each on an engine of its own
    over the first one's weights (a graph is traced once an engine), the
    sides in turn. Two readings a side where the machine is quiet; beside
    five other test processes a reading's rows swing by more than the 0.05
    asked of them, so more are taken, up to READINGS, until the lowest
    settle."""
    params = started["engine"].params
    real = llama.forward_chunk

    def slow_at_32(cfg, params, token_ids, *a, **kw):
        if token_ids.shape[-1] == 32:
            time.sleep(SLEEP_S)
        return real(cfg, params, token_ids, *a, **kw)

    def table():
        return build(params)[0].get_stats()["startup"]["graphs"]

    def lowest(tables):
        return {(g, s): min(t[g][s] for t in tables)
                for g in tables[0] for s in STAGES}

    plain, planted = [], []
    for _ in range(READINGS):
        plain.append(table())
        with monkeypatch.context() as patched:
            patched.setattr(llama, "forward_chunk", slow_at_32)
            planted.append(table())
        before, after = lowest(plain), lowest(planted)
        moved = {k: after[k] - before[k] for k in before}
        slow = moved.pop((SLOW, "trace_s"))
        still = max(moved, key=lambda k: abs(moved[k]))
        if len(plain) > 1 and slow >= MOVED_S \
                and abs(moved[still]) <= STILL_S:
            break
    assert slow >= MOVED_S, (slow, len(plain))
    assert abs(moved[still]) <= STILL_S, (still, moved[still], len(plain))


def test_a_compile_inside_a_request_moves_no_row_of_the_start(started):
    engine = started["engine"]
    before = engine.get_stats()
    # 40 tokens: the 64-wide prefill, which nothing lowered
    engine.generate([InferenceRequest(
        prompt_token_ids=list(range(5, 45)),
        sampling=SamplingParams(max_new_tokens=2))])
    after = engine.get_stats()
    assert after["compiles"] > before["compiles"]
    assert after["compile_trace_s"] > before["compile_trace_s"]
    assert after["compile_lower_s"] > before["compile_lower_s"]
    for st in (before["startup"], after["startup"]):
        st.pop("compile_misses")         # the process's, as it stands
    assert after["startup"] == before["startup"]
    # the request's compiles are in the rows, under their functions' names
    late = [r for r in compile_log().rows
            if r["stage"] == "backend" and r["graph"] == r["fn"]]
    assert late


# --------------------------------------------------------------------- #
# (c) the worker's way to READY, and the heartbeat's to /metrics
# --------------------------------------------------------------------- #

class QuietPlane:
    """The plane's client as a worker's start drives it."""

    worker_id, auth_token = "w-1", "tok"
    refresh_token = signing_secret = "s"

    def verify_credentials(self):
        return True

    def fetch_remote_config(self):
        return {"version": 0}

    def heartbeat(self, **kw):
        return {}

    def going_offline(self):
        pass


@pytest.fixture(scope="module")
def worker():
    from distributed_gpu_inference_tpu.utils.config import WorkerConfig
    from distributed_gpu_inference_tpu.worker.main import Worker

    cfg = WorkerConfig.model_validate({
        "name": "start", "task_types": ["llm"],
        "server": {"url": "http://127.0.0.1:1"},
        "engines": {"llm": {"model": "llama3-tiny", "max_batch_size": 4,
                            "serving": {"mode": "batcher"},
                            "extra": {"max_seq_len": 128}}},
        "heartbeat_interval_s": 600.0,
    })
    w = Worker(cfg, api=QuietPlane(),
               topology=TpuTopology(chip_type="cpu", num_chips=1,
                                    hbm_gb_per_chip=4.0))
    w.start(install_signal_handlers=False, block=False)
    yield w
    w.request_shutdown()


@pytest.mark.parametrize("phase", [
    "load_model", "worker_ready", "worker_register", "worker_load_engines"])
def test_the_worker_leaves_its_phases_beside_the_engines(worker, phase):
    assert worker.state == WorkerState.IDLE
    st = worker.engines["llm"].engine.get_stats()["startup"]
    assert st[phase + "_s"] > 0.0 and phase in st["at"]
    assert st["at"]["worker_ready"] <= st["at"][phase]
    assert st["init_s"] <= st["load_model_s"] \
        <= st["worker_load_engines_s"] <= st["worker_ready_s"]


def test_the_heartbeat_carries_the_start_and_the_plane_shows_it(worker):
    sent = worker._batcher_stats()
    st = worker.engines["llm"].engine.get_stats()["startup"]
    for key, label in flight.STARTUP_PHASES.items():
        assert sent[f"startup_{label}_s"] == round(st[key + "_s"], 3)
    assert sent["compile_trace_s"] > 0.0 and sent["compile_lower_s"] > 0.0
    assert sent["compile_misses"] == compile_log().misses

    mc = MetricsCollector()
    sent = dict(sent, startup_graphs_backend_s=21.5, compile_s=30.0,
                compile_trace_s=4.0, compile_lower_s=9.0, compile_misses=12)
    mc.record_batcher_engine("w1", sent)
    # a second beat: the gauges stand, the counters take the rise
    mc.record_batcher_engine("w1", dict(sent, compile_lower_s=9.5,
                                        compile_misses=13,
                                        startup_ready_s="garbage"))
    text = mc.metrics.render().decode()
    if "worker_startup_seconds" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    for label in flight.STARTUP_PHASES.values():
        assert f'worker_startup_seconds{{phase="{label}",worker="w1"}}' \
            in text
    assert ('worker_startup_seconds{phase="graphs_backend",worker="w1"} 21.5'
            in text)
    assert ('worker_startup_seconds{phase="ready",worker="w1"} '
            f'{float(sent["startup_ready_s"])}') in text
    for stage_, secs in (("backend", 30.0), ("trace", 4.0), ("lower", 9.5)):
        assert ('worker_compile_seconds_total{stage="%s",worker="w1"} %s'
                % (stage_, secs)) in text
    assert 'worker_compile_misses_total{worker="w1"} 13.0' in text


def test_the_graphs_are_lowered_beneath_a_roomy_frame(monkeypatch):
    """``lower_serving_graphs`` traces and lowers inside ``roomy_stack``:
    the engine's jitted functions are asked to lower with that frame
    above them."""
    import sys

    engine, _ = build()
    seen = []
    lower = engine._decode_multi_fn.lower

    class Spy:
        def lower(self, *args, **kwargs):
            frame = sys._getframe()
            while frame is not None:
                seen.append(frame.f_code.co_name)
                frame = frame.f_back
            return lower(*args, **kwargs)

    monkeypatch.setattr(engine, "_decode_multi_fn", Spy())
    graphs = engine.lower_serving_graphs([1], [])
    assert set(graphs) == {"decode_multi[T=1]", "chain_sched"}
    assert "roomy_stack" in seen
