"""Worker runtime: registration, engines, heartbeat, load control, drain.

Parity target: reference worker boot/poll behavior (SURVEY §3.1) — tested
hermetically with a fake API client and a stub engine, like the reference's
worker tests (no network, no model).
"""

import threading
import time
from typing import Any, Dict, List, Optional

import pytest

from distributed_gpu_inference_tpu.utils.config import (
    EngineModelConfig,
    WorkerConfig,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    TpuTopology,
    WorkerState,
)
from distributed_gpu_inference_tpu.worker.api_client import APIError
from distributed_gpu_inference_tpu.worker.engines import register_engine
from distributed_gpu_inference_tpu.worker.engines.base import BaseEngine
from distributed_gpu_inference_tpu.worker.main import Worker, probe_topology


class StubEngine(BaseEngine):
    def __init__(self, config=None):
        super().__init__(config)
        self.loaded = False
        self.unloaded = False

    def load_model(self):
        self.loaded = True

    def inference(self, params):
        if params.get("boom"):
            raise RuntimeError("engine exploded")
        return {"echo": params}

    def unload(self):
        self.unloaded = True


class FakeAPI:
    """Implements the APIClient surface the Worker drives."""

    def __init__(self, jobs: Optional[List[Dict[str, Any]]] = None,
                 creds_valid: bool = False):
        self.worker_id = "w-1" if creds_valid else None
        self.auth_token = "tok" if creds_valid else None
        self.refresh_token = "ref" if creds_valid else None
        self.signing_secret = "sig" if creds_valid else None
        self.jobs = list(jobs or [])
        self.creds_valid = creds_valid
        self.completed: List[Dict[str, Any]] = []
        self.calls: List[str] = []
        self.heartbeat_response: Dict[str, Any] = {}
        self.remote_config: Dict[str, Any] = {"version": 0}

    def verify_credentials(self):
        self.calls.append("verify")
        return self.creds_valid

    def register(self, info):
        self.calls.append("register")
        self.registered_info = info
        self.worker_id = "w-new"
        self.auth_token = "tok2"
        self.refresh_token = "ref2"
        self.signing_secret = "sig2"
        return {
            "worker_id": "w-new", "auth_token": "tok2",
            "refresh_token": "ref2", "signing_secret": "sig2",
        }

    def refresh_credentials(self):
        self.calls.append("refresh")
        return {}

    def fetch_remote_config(self):
        self.calls.append("fetch_config")
        return self.remote_config

    def heartbeat(self, **kw):
        self.calls.append("heartbeat")
        self.last_heartbeat = kw
        return dict(self.heartbeat_response)

    def fetch_next_job(self):
        self.calls.append("poll")
        return self.jobs.pop(0) if self.jobs else None

    def complete_job(self, job_id, success, result=None, error=None):
        self.completed.append(
            {"job_id": job_id, "success": success, "result": result,
             "error": error}
        )
        return {"ok": True}

    def release_job(self, job_id):
        self.released = getattr(self, "released", [])
        self.released.append(job_id)

    def going_offline(self):
        self.calls.append("going_offline")

    def offline(self):
        self.calls.append("offline")
        return []

    def close(self):
        self.calls.append("close")


@pytest.fixture(autouse=True)
def stub_llm_engine():
    register_engine("llm", StubEngine)
    yield
    from distributed_gpu_inference_tpu.worker.engines import _OVERRIDES

    _OVERRIDES.pop("llm", None)


def _config(**kw) -> WorkerConfig:
    cfg = WorkerConfig(
        task_types=["llm"],
        engines={"llm": EngineModelConfig(engine="echo", model="llama3-tiny")},
        poll_interval_s=0.01,
        heartbeat_interval_s=30.0,
        **kw,
    )
    return cfg


def _worker(api: FakeAPI, **cfg_kw) -> Worker:
    return Worker(_config(**cfg_kw), api=api)


def test_register_new_worker_persists_credentials():
    api = FakeAPI()
    saved = {}
    w = Worker(_config(), api=api, on_credentials=saved.update)
    w.register()
    assert "register" in api.calls
    assert saved["worker_id"] == "w-new"
    assert api.registered_info["supported_types"] == ["llm"]
    assert "topology" in api.registered_info
    assert "fetch_config" in api.calls


def test_register_reuses_valid_credentials():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.register()
    assert "register" not in api.calls
    assert "verify" in api.calls


def test_remote_config_overrides_load_control():
    api = FakeAPI(creds_valid=True)
    api.remote_config = {
        "version": 7,
        "load_control": {"acceptance_rate": 0.5, "max_jobs_per_hour": 10,
                         "working_hours": [9, 17]},
    }
    w = _worker(api)
    w.register()
    assert w.config.config_version == 7
    assert w.config.load_control.acceptance_rate == 0.5
    assert w.config.load_control.max_jobs_per_hour == 10
    assert w.config.load_control.working_hours == (9, 17)


def test_load_engines_drops_broken_type():
    class Broken(StubEngine):
        def load_model(self):
            from distributed_gpu_inference_tpu.worker.engines.base import (
                EngineLoadError,
            )

            raise EngineLoadError("no deps")

    register_engine("embedding", Broken)
    try:
        api = FakeAPI(creds_valid=True)
        cfg = _config()
        cfg.task_types = ["llm", "embedding"]
        w = Worker(cfg, api=api)
        w.load_engines()
        assert w.config.task_types == ["llm"]
        assert "llm" in w.engines and "embedding" not in w.engines
    finally:
        from distributed_gpu_inference_tpu.worker.engines import _OVERRIDES

        _OVERRIDES.pop("embedding", None)


def test_heartbeat_config_changed_triggers_refetch():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.load_engines()
    api.heartbeat_response = {"config_changed": True}
    w._heartbeat_once()
    assert api.calls.count("fetch_config") == 1
    assert w.stats["heartbeats"] == 1


def test_heartbeat_401_refreshes_token():
    api = FakeAPI(creds_valid=True)

    def bad_heartbeat(**kw):
        api.calls.append("heartbeat")
        raise APIError(401, "expired")

    api.heartbeat = bad_heartbeat
    w = _worker(api)
    w._heartbeat_once()
    assert "refresh" in api.calls


def test_process_job_success_and_failure():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.load_engines()
    w.state = WorkerState.IDLE
    assert w.try_begin_job()
    w.process_job({"id": "j1", "type": "llm", "params": {"x": 1}})
    assert api.completed[0]["success"] is True
    assert api.completed[0]["result"] == {"echo": {"x": 1}}
    assert w.stats["jobs_completed"] == 1
    assert w.state == WorkerState.IDLE

    assert w.try_begin_job()
    w.process_job({"id": "j2", "type": "llm", "params": {"boom": True}})
    assert api.completed[1]["success"] is False
    assert "exploded" in api.completed[1]["error"]
    assert w.stats["jobs_failed"] == 1


def test_try_begin_job_excludes_concurrent_work():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.state = WorkerState.IDLE
    assert w.try_begin_job()
    assert not w.try_begin_job()        # second claim refused while BUSY
    w.end_job()
    assert w.try_begin_job()


def test_process_job_unknown_type_fails_cleanly():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.load_engines()
    w.state = WorkerState.IDLE
    assert w.try_begin_job()
    w.process_job({"id": "j3", "type": "vision", "params": {}})
    assert api.completed[0]["success"] is False


def test_load_control_acceptance_rate_zero_rejects():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.config.load_control.acceptance_rate = 0.0
    assert w.should_accept_job({"type": "llm"}) is False


def test_load_control_hourly_cap():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.config.load_control.max_jobs_per_hour = 2
    now = time.time()
    w._hour_window = [now - 10, now - 20]
    assert w.should_accept_job({"type": "llm"}, now=now) is False
    # stale entries roll out of the window
    w._hour_window = [now - 4000, now - 20]
    assert w.should_accept_job({"type": "llm"}, now=now) is True


def test_load_control_cooldown():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    w.config.load_control.cooldown_seconds = 30.0
    w._last_job_done_at = time.time() - 5
    assert w.should_accept_job({"type": "llm"}) is False
    w._last_job_done_at = time.time() - 60
    assert w.should_accept_job({"type": "llm"}) is True


def test_load_control_working_hours():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    hour = time.localtime().tm_hour
    w.config.load_control.working_hours = ((hour + 1) % 24, (hour + 2) % 24)
    assert w.should_accept_job({"type": "llm"}) is False
    w.config.load_control.working_hours = (hour, (hour + 1) % 24)
    assert w.should_accept_job({"type": "llm"}) is True


def test_gated_worker_never_claims():
    """Job-independent gates are checked BEFORE fetching, so a gated worker
    doesn't claim-and-release head-of-queue work."""
    api = FakeAPI(creds_valid=True,
                  jobs=[{"id": "jx", "type": "llm", "params": {}}])
    w = _worker(api)
    w.load_engines()
    w.state = WorkerState.IDLE
    w.config.load_control.acceptance_rate = 0.0
    assert w._poll_once() is False
    assert "poll" not in api.calls          # never even fetched
    assert api.jobs                          # job still queued


def test_type_weight_release_is_one_shot():
    """A job released once by the probabilistic type throttle is ACCEPTED on
    re-encounter — no release/re-claim ping-pong starvation."""
    api = FakeAPI(creds_valid=True,
                  jobs=[{"id": "jw", "type": "llm", "params": {}},
                        {"id": "jw", "type": "llm", "params": {}}])
    w = _worker(api)
    w.load_engines()
    w.state = WorkerState.IDLE
    w.config.load_control.job_type_weights = {"llm": 0.0}  # always throttle
    assert w._poll_once() is False
    assert api.released == ["jw"]
    # the same job comes back: taken this time
    assert w._poll_once() is True
    assert api.completed[0]["job_id"] == "jw"


def test_rejected_job_released_not_failed():
    api = FakeAPI(creds_valid=True,
                  jobs=[{"id": "jr", "type": "llm", "params": {}}])
    w = _worker(api)
    w.load_engines()
    w.state = WorkerState.IDLE
    w.config.load_control.job_type_weights = {"llm": 0.0}
    assert w._poll_once() is False
    assert w.stats["jobs_rejected"] == 1
    # requeued for other workers — NOT completed as failed
    assert api.completed == []
    assert api.released == ["jr"]
    assert w.state == WorkerState.IDLE


def test_full_lifecycle_processes_jobs_then_drains():
    api = FakeAPI(
        creds_valid=True,
        jobs=[
            {"id": "a", "type": "llm", "params": {"n": 1}},
            {"id": "b", "type": "llm", "params": {"n": 2}},
        ],
    )
    w = _worker(api)
    t = threading.Thread(
        target=lambda: w.start(install_signal_handlers=False, block=True)
    )
    t.start()
    deadline = time.time() + 10
    while len(api.completed) < 2 and time.time() < deadline:
        time.sleep(0.01)
    w.request_shutdown()
    t.join(timeout=10)
    assert not t.is_alive()
    assert [c["job_id"] for c in api.completed] == ["a", "b"]
    assert "going_offline" in api.calls
    assert "offline" in api.calls
    assert "close" in api.calls
    assert w.state == WorkerState.OFFLINE
    assert w.engines["llm"].unloaded


def test_probe_topology_returns_valid():
    topo = probe_topology()
    assert topo.num_chips >= 1
    assert topo.chip_type in ("cpu", "v4", "v5e", "v5p", "v6e")


def test_get_status_shape():
    api = FakeAPI(creds_valid=True)
    w = _worker(api)
    st = w.get_status()
    assert st["state"] == "initializing"
    assert st["task_types"] == ["llm"]
    assert "topology" in st and "stats" in st


# -- TPU-aware onboarding probe (faked environments) -------------------------


def test_probe_tpu_runtime_reads_env(monkeypatch):
    from distributed_gpu_inference_tpu.worker.main import probe_tpu_runtime

    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-16")
    monkeypatch.setenv("TPU_WORKER_ID", "3")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
    monkeypatch.setenv("TPU_LIBRARY_PATH", "/opt/libtpu.so")
    r = probe_tpu_runtime()
    assert r["libtpu"] is True
    assert r["accelerator_type"] == "v5litepod-16"
    assert r["worker_id"] == "3"
    assert r["hosts"] == ["h0", "h1"]


def test_probe_topology_mesh_from_coords(monkeypatch):
    import distributed_gpu_inference_tpu.worker.main as wm

    class FakeDev:
        def __init__(self, coords):
            self.device_kind = "TPU v5e"
            self.coords = coords

    class FakeJax:
        @staticmethod
        def devices():
            # a 2x4 slice: coords span (2, 4, 1)
            return [FakeDev((x, y, 0)) for x in range(2) for y in range(4)]

    monkeypatch.setattr(wm, "probe_tpu_runtime", lambda: {
        "libtpu": True, "accel_devices": [], "accelerator_type": "",
        "worker_id": "", "hosts": [],
    })
    import sys
    monkeypatch.setitem(sys.modules, "jax", FakeJax())
    t = wm.probe_topology()
    assert t.chip_type == "v5e"
    assert t.num_chips == 8
    assert t.mesh_shape == (2, 4)
    assert t.peak_bf16_tflops == 197.0


def test_probe_topology_env_fallback_without_jax(monkeypatch):
    """Broken driver: jax raises, but libtpu + accelerator type declare a
    TPU host — register what the platform says, not 'cpu'."""
    import distributed_gpu_inference_tpu.worker.main as wm

    class Boom:
        def devices(self):
            raise RuntimeError("no backend")

        def __getattr__(self, k):
            raise RuntimeError("no backend")

    monkeypatch.setattr(wm, "probe_tpu_runtime", lambda: {
        "libtpu": True, "accel_devices": ["/dev/accel0"],
        "accelerator_type": "v5litepod-8", "worker_id": "", "hosts": [],
    })
    import sys
    monkeypatch.setitem(sys.modules, "jax", Boom())
    t = wm.probe_topology()
    assert t.chip_type == "v5e"
    assert t.num_chips == 8
    assert t.hbm_gb_per_chip == 16.0


def test_probe_topology_unknown_chip_is_an_error(monkeypatch):
    """A chip the published table does not know has no figures — never
    another chip's."""
    import distributed_gpu_inference_tpu.worker.main as wm

    class FakeDev:
        device_kind = "TPU v9 hyper"

    class FakeJax:
        @staticmethod
        def devices():
            return [FakeDev()]

    monkeypatch.setattr(wm, "probe_tpu_runtime", lambda: {
        "libtpu": True, "accel_devices": [], "accelerator_type": "",
        "worker_id": "", "hosts": [],
    })
    import sys
    monkeypatch.setitem(sys.modules, "jax", FakeJax())
    with pytest.raises(RuntimeError, match="unknown accelerator"):
        wm.probe_topology()


def test_probe_topology_cpu_has_no_device_figures():
    topo = probe_topology()         # the suite runs on the CPU
    assert topo.chip_type == "cpu"
    assert topo.hbm_gb_per_chip == topo.peak_bf16_tflops == 0.0
    assert topo.ici_bandwidth_gbps == 0.0


def test_register_sends_the_row_columns():
    """The plane's worker row has chip columns of its own (worker list,
    remote-config HBM caps) that default to one 16 GB chip."""
    api = FakeAPI(creds_valid=False)
    w = _worker(api)
    w.topology = TpuTopology(chip_type="v5e", num_chips=4,
                             hbm_gb_per_chip=16.0, mesh_shape=(2, 2))
    w.register()
    info = api.registered_info
    assert info["chip_generation"] == "v5e"
    assert info["num_chips"] == 4
    assert info["mesh_shape"] == [2, 2]
    assert info["topology"]["num_chips"] == 4


def test_wizard_reports_runtime(monkeypatch):
    from distributed_gpu_inference_tpu.worker.cli import ConfigWizard
    import distributed_gpu_inference_tpu.worker.main as wm

    monkeypatch.setattr(wm, "probe_tpu_runtime", lambda: {
        "libtpu": True, "accel_devices": ["/dev/accel0"],
        "accelerator_type": "v5litepod-4", "worker_id": "", "hosts": [],
    })
    lines = []
    wiz = ConfigWizard(input_fn=lambda p: "", print_fn=lines.append)
    cfg = wiz.run()
    assert cfg is not None
    joined = "\n".join(lines)
    assert "libtpu=found" in joined
    assert "type=v5litepod-4" in joined
