"""Worker CLI: setup wizard + start/status/set/show commands.

Behavioral parity with the reference's ``worker/cli.py`` (877 LoC):
- Interactive setup wizard — server/region/accelerator probe/task types/
  load control/direct endpoint (:298-651) — writing ``config.yaml``.
- ``start`` boots the worker (:706), ``status`` shows local + server state
  (:736), ``set k.v value`` does dotted config updates (:790).

TPU re-design: the accelerator probe reads ``jax.devices()``
(:class:`worker.main.probe_topology`) instead of nvidia-smi (:77), and
there is no CUDA-version → torch-index-url dance (:110-133) — jax is baked
into the image/venv by the launcher.

Every prompt has a default so the wizard is scriptable:
``yes "" | tpu-worker setup`` produces a valid config (hermetic tests drive
it with a ``input_fn``).
"""

from __future__ import annotations

import argparse
import builtins
import json
import sys
from pathlib import Path
from typing import Any, Callable, List, Optional

from ..utils.config import (
    WorkerConfig,
    load_worker_config,
    save_worker_config,
    set_dotted,
)

DEFAULT_CONFIG_PATH = "config.yaml"
REGIONS = ("us-west", "us-east", "eu-west", "eu-central", "asia-east",
           "asia-southeast")
TASK_TYPES = ("llm", "embedding", "image_gen", "vision", "whisper")


class ConfigWizard:
    """Interactive setup (reference ConfigWizard:298). ``input_fn``/``print_fn``
    are injectable for tests."""

    def __init__(self, input_fn: Optional[Callable[[str], str]] = None,
                 print_fn: Callable[[str], None] = print) -> None:
        # resolve builtins.input lazily so monkeypatched/test inputs work
        self._input = input_fn or (lambda prompt: builtins.input(prompt))
        self._print = print_fn

    def _ask(self, prompt: str, default: str) -> str:
        try:
            raw = self._input(f"{prompt} [{default}]: ").strip()
        except (EOFError, StopIteration):
            raw = ""
        return raw or default

    def _ask_bool(self, prompt: str, default: bool) -> bool:
        raw = self._ask(prompt + " (y/n)", "y" if default else "n").lower()
        return raw in ("y", "yes", "true", "1")

    def _ask_number(self, prompt: str, default, cast):
        """Re-prompt on a bad numeric answer instead of crashing the whole
        wizard (a typo must never discard every prior answer)."""
        for _ in range(3):
            raw = self._ask(prompt, str(default))
            try:
                return cast(raw)
            except ValueError:
                self._print(f"  not a valid number: {raw!r}")
        self._print(f"  using default {default}")
        return cast(str(default))

    def run(self, base: Optional[WorkerConfig] = None) -> WorkerConfig:
        from .main import probe_topology, probe_tpu_runtime

        cfg = base or WorkerConfig()
        self._print("== TPU worker setup ==")

        runtime = probe_tpu_runtime()
        if runtime["libtpu"] or runtime["accel_devices"]:
            self._print(
                "tpu runtime: libtpu="
                + ("found" if runtime["libtpu"] else "MISSING")
                + (f", devices={len(runtime['accel_devices'])}"
                   if runtime["accel_devices"] else "")
                + (f", type={runtime['accelerator_type']}"
                   if runtime["accelerator_type"] else "")
            )
        topo = probe_topology()
        self._print(
            f"detected accelerator: {topo.chip_type} x{topo.num_chips} "
            f"({topo.hbm_gb_per_chip:.0f} GB HBM/chip, mesh "
            f"{'x'.join(map(str, topo.mesh_shape))}, "
            f"{topo.peak_bf16_tflops:.0f} bf16 TFLOP/s/chip)"
        )

        cfg.name = self._ask("worker name", cfg.name)
        cfg.server.url = self._ask("control-plane URL", cfg.server.url)
        region = self._ask(
            f"region {list(REGIONS)}", cfg.region
        )
        cfg.region = region

        types = self._ask(
            f"task types (comma-sep of {list(TASK_TYPES)})",
            ",".join(cfg.task_types),
        )
        cfg.task_types = [t.strip() for t in types.split(",") if t.strip()]

        # load control (reference wizard load-control section)
        if self._ask_bool("configure load control", False):
            lc = cfg.load_control
            lc.acceptance_rate = self._ask_number(
                "acceptance rate 0..1", lc.acceptance_rate, float
            )
            lc.max_jobs_per_hour = self._ask_number(
                "max jobs/hour (0 = unlimited)", lc.max_jobs_per_hour, int
            )
            lc.cooldown_seconds = self._ask_number(
                "cooldown seconds between jobs", lc.cooldown_seconds, float
            )
            hours = self._ask("working hours start-end (e.g. 9-17, empty=all)",
                              "")
            if hours and "-" in hours:
                a, _, b = hours.partition("-")
                try:
                    lc.working_hours = (int(a), int(b))
                except ValueError:
                    self._print(f"  ignoring invalid hours: {hours!r}")

        # direct endpoint (reference wizard direct section)
        if self._ask_bool("enable direct inference endpoint", False):
            cfg.direct.enabled = True
            cfg.direct.port = self._ask_number(
                "direct port", cfg.direct.port, int
            )
            cfg.direct.public_url = self._ask(
                "public URL clients reach this worker at",
                cfg.direct.public_url or f"http://localhost:{cfg.direct.port}",
            ) or None
        return cfg


# ---------------------------------------------------------------------------
# commands
# ---------------------------------------------------------------------------


def cmd_setup(args: argparse.Namespace) -> int:
    base = None
    path = Path(args.config)
    if path.exists():
        base = load_worker_config(path)
    cfg = ConfigWizard().run(base)
    save_worker_config(cfg, path)
    print(f"wrote {path}")
    return 0


def cmd_start(args: argparse.Namespace) -> int:
    import logging

    from ..utils.device import enable_compile_cache, require_backend
    from .main import Worker

    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    # a worker serves from the chip or from a CPU it was told to use —
    # never from the CPU JAX falls back to when it finds no chip
    backend = require_backend()
    cache_dir = enable_compile_cache()
    logging.getLogger("tpu_worker").info(
        "backend %s, compile cache %s", backend, cache_dir
    )
    cfg = load_worker_config(args.config, missing_ok=True)
    path = Path(args.config)

    def persist(creds):
        cfg.server.worker_id = creds["worker_id"]
        cfg.server.auth_token = creds["auth_token"]
        cfg.server.refresh_token = creds["refresh_token"]
        cfg.server.signing_secret = creds["signing_secret"]
        save_worker_config(cfg, path)

    Worker(cfg, on_credentials=persist).start()
    return 0


def cmd_status(args: argparse.Namespace) -> int:
    cfg = load_worker_config(args.config, missing_ok=True)
    out = {
        "config": str(Path(args.config).resolve()),
        "name": cfg.name,
        "region": cfg.region,
        "task_types": cfg.task_types,
        "server_url": cfg.server.url,
        "registered": bool(cfg.server.worker_id),
        "worker_id": cfg.server.worker_id,
        "direct_enabled": cfg.direct.enabled,
    }
    if cfg.server.worker_id and not args.local:
        try:
            import httpx

            headers = {}
            if cfg.server.api_key:
                headers["X-API-Key"] = cfg.server.api_key
            if cfg.server.auth_token:
                headers["Authorization"] = f"Bearer {cfg.server.auth_token}"
            resp = httpx.get(
                f"{cfg.server.url.rstrip('/')}/api/v1/workers/"
                f"{cfg.server.worker_id}",
                headers=headers,
                timeout=5.0,
            )
            if resp.status_code == 200:
                remote = resp.json()
                out["server_status"] = remote.get("status")
                out["reliability_score"] = remote.get("reliability_score")
                out["last_heartbeat"] = remote.get("last_heartbeat")
            else:
                out["server_status"] = f"HTTP {resp.status_code}"
        except Exception as exc:  # noqa: BLE001 - status must never crash
            out["server_status"] = f"unreachable: {exc}"
    print(json.dumps(out, indent=2))
    return 0


def cmd_set(args: argparse.Namespace) -> int:
    cfg = load_worker_config(args.config, missing_ok=True)
    value: Any = args.value
    # parse JSON-ish scalars so `set load_control.acceptance_rate 0.5` works
    try:
        value = json.loads(args.value)
    except ValueError:
        pass
    try:
        cfg = set_dotted(cfg, args.key, value)
    except KeyError:
        print(f"error: unknown config key {args.key!r}", file=sys.stderr)
        return 1
    except Exception as exc:  # pydantic ValidationError etc.
        print(f"error: invalid value for {args.key!r}: {exc}",
              file=sys.stderr)
        return 1
    save_worker_config(cfg, args.config)
    print(f"{args.key} = {value!r}")
    return 0


def cmd_show(args: argparse.Namespace) -> int:
    cfg = load_worker_config(args.config, missing_ok=True)
    data = cfg.model_dump(mode="json")
    # never print secrets
    for k in ("auth_token", "refresh_token", "signing_secret", "api_key"):
        if data.get("server", {}).get(k):
            data["server"][k] = "***"
    print(json.dumps(data, indent=2))
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="tpu-worker",
        description="TPU inference worker (reference: gpu-worker CLI)",
    )
    ap.add_argument("--config", default=DEFAULT_CONFIG_PATH)
    sub = ap.add_subparsers(dest="command", required=True)

    sub.add_parser("setup", help="interactive configuration wizard")
    p_start = sub.add_parser("start", help="run the worker")
    p_start.add_argument("--log-level", default="INFO")
    p_status = sub.add_parser("status", help="local + server-side status")
    p_status.add_argument("--local", action="store_true",
                          help="skip the server round trip")
    p_set = sub.add_parser("set", help="dotted config update, e.g. "
                           "load_control.acceptance_rate 0.5")
    p_set.add_argument("key")
    p_set.add_argument("value")
    sub.add_parser("show", help="print config (secrets masked)")
    return ap


_COMMANDS = {
    "setup": cmd_setup,
    "start": cmd_start,
    "status": cmd_status,
    "set": cmd_set,
    "show": cmd_show,
}


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    return _COMMANDS[args.command](args)


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
