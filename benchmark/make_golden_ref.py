#!/usr/bin/env python3
"""Make ``golden/<config>.json`` for a configuration whose file names its
own plain reference (``"reference": "<module under harness/>"``).

    python3 benchmark/make_golden_ref.py --config <name> [--margin M --reason R]

``make_golden.py`` knows the Llama-recipe reference alone. A configuration
whose block that reference does not describe brings a reference module of
its own (``SeedStream`` and ``last_logits`` of the same form) and names it
in its file; this script reads the name from there. The file it writes has
the form ``make_golden.py`` writes and ``run.py``'s probe check reads: per
probe prompt the reference's five largest logits at the last prompt
position with their token ids, and the margin with its reason. Run once per
configuration, on the chip, never during runs.
"""

from __future__ import annotations

import argparse
import importlib
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--margin", type=float, default=None)
    ap.add_argument("--reason", default=None)
    ap.add_argument("--copy-to", default=None,
                    help="also write the file into this directory (a chip "
                         "call brings back only its output directory)")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"platform {dev.platform!r}: golden files of real "
                         "configurations are made on the chip")
    cfg = spec.load_config(spec.BENCH / "configs" / f"{args.config}.json")
    if "reference" not in cfg:
        raise SystemExit(f"{args.config}.json names no reference module: "
                         "use make_golden.py")
    ref = importlib.import_module(f"harness.{cfg['reference']}")
    probes = spec.probe_prompts(cfg)
    t0 = time.monotonic()
    logits = ref.last_logits(
        cfg, ref.SeedStream(cfg, cfg["weights_seed"]),
        [p["token_ids"] for p in probes],
    )
    path = spec.BENCH / "golden" / f"{args.config}.json"
    old = json.loads(path.read_text()) if path.is_file() else {}
    golden = {
        "config": args.config, "weights_seed": cfg["weights_seed"],
        "weights_from": f"{cfg['reference']}.SeedStream",
        "made_on": {"platform": dev.platform, "kind": dev.device_kind},
        "margin": args.margin if args.margin is not None
        else old.get("margin", PROVISIONAL_MARGIN),
        "margin_reason": args.reason or old.get(
            "margin_reason", "provisional: not yet set from a chip run"),
        "rule": "the served first token is the reference's argmax, or one "
                "of its top five whose logit is within margin of the maximum",
        "probes": [
            {"name": p["name"], "tokens": len(p["token_ids"]),
             "top": reference.top(lg)}
            for p, lg in zip(probes, logits)
        ],
    }
    text = json.dumps(golden, indent=1) + "\n"
    path.write_text(text)
    if args.copy_to:
        Path(args.copy_to).mkdir(parents=True, exist_ok=True)
        (Path(args.copy_to) / path.name).write_text(text)
    print(f"{args.config}: {len(probes)} probes in "
          f"{time.monotonic() - t0:.1f}s -> {path}", flush=True)
    for g in golden["probes"]:
        gaps = [round(g["top"]["logits"][0] - x, 3)
                for x in g["top"]["logits"]]
        print(f"  {g['name']:10s} top ids {g['top']['ids']} deficits {gaps}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
