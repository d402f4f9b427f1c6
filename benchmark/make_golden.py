#!/usr/bin/env python3
"""Make ``golden/<config>.json``: what the plain reference says the first
token of each probe prompt is.

    python3 benchmark/make_golden.py --config <name> [--config <name> ...]

Run once per configuration, on the chip, never during runs. For each probe
prompt of the configuration file it stores the reference's five largest
logits at the last prompt position, with their token ids. ``run.py`` holds
the served first token of each probe to the file's rule: it is the
reference's argmax, or one of the five whose logit is within ``margin`` of
the maximum. Margin and reason stay as they are when the file is made anew,
unless ``--margin`` gives another.

The weights are the program's streamed init regenerated from
``weights_seed`` one layer at a time (``reference.SeedStream``), so a model
that needs four chips to serve is checked on one. ``--from-engine`` loads
the configuration's engine the way the worker does and slices its tree
instead: for the stand-in configurations, whose engines build their weights
by the program's other init.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

from harness import reference, spec  # noqa: E402

PROVISIONAL_MARGIN = 0.5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", action="append", required=True)
    ap.add_argument("--from-engine", action="store_true")
    ap.add_argument("--margin", type=float, default=None)
    ap.add_argument("--reason", default=None)
    ap.add_argument("--copy-to", default=None,
                    help="also write each file into this directory (a chip "
                         "call brings back only its output directory)")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    asked = (jax.config.jax_platforms or "").split(",")
    for name in args.config:
        base = next((b for b in (spec.BENCH, spec.TESTDATA)
                     if (b / "configs" / f"{name}.json").is_file()), None)
        if base is None:
            raise SystemExit(f"no configs/{name}.json under benchmark/")
        if dev.platform != "tpu" and not (
                dev.platform == "cpu" and "cpu" in asked
                and base == spec.TESTDATA):
            raise SystemExit(
                f"platform {dev.platform!r}: golden files of real "
                "configurations are made on the chip"
            )
        cfg = spec.load_config(base / "configs" / f"{name}.json")
        probes = spec.probe_prompts(cfg)
        t0 = time.monotonic()
        if args.from_engine:
            from distributed_gpu_inference_tpu.worker.engines import (
                create_engine,
            )

            llm = create_engine("llm", dict(cfg["worker_engine"]))
            llm.load_model()
            weights = reference.FromTree(llm.engine.params)
        else:
            weights = reference.SeedStream(cfg, cfg["weights_seed"])
        logits = reference.last_logits(
            cfg, weights, [p["token_ids"] for p in probes]
        )
        path = base / "golden" / f"{name}.json"
        old = json.loads(path.read_text()) if path.is_file() else {}
        golden = {
            "config": name, "weights_seed": cfg["weights_seed"],
            "weights_from": "engine tree" if args.from_engine
            else "reference.SeedStream",
            "made_on": {"platform": dev.platform, "kind": dev.device_kind},
            "margin": args.margin if args.margin is not None
            else old.get("margin", PROVISIONAL_MARGIN),
            "margin_reason": args.reason or old.get(
                "margin_reason", "provisional: not yet set from a chip run"),
            "rule": "the served first token is the reference's argmax, or "
                    "one of its top five whose logit is within margin of "
                    "the maximum",
            "probes": [
                {"name": p["name"], "tokens": len(p["token_ids"]),
                 "top": reference.top(lg)}
                for p, lg in zip(probes, logits)
            ],
        }
        path.parent.mkdir(exist_ok=True)
        path.write_text(json.dumps(golden, indent=1) + "\n")
        if args.copy_to:
            Path(args.copy_to).mkdir(parents=True, exist_ok=True)
            (Path(args.copy_to) / f"{name}.json").write_text(
                json.dumps(golden, indent=1) + "\n")
        print(f"{name}: {len(probes)} probes in "
              f"{time.monotonic() - t0:.1f}s -> {path}", flush=True)
        for g in golden["probes"]:
            gaps = [round(g["top"]["logits"][0] - x, 3)
                    for x in g["top"]["logits"]]
            print(f"  {g['name']:10s} top ids {g['top']['ids']} "
                  f"deficits {gaps}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
