"""`kernels.moe_held_gmm_roofline` in the scans of a latent-attention model
of two attention kinds: the same reader (the held experts that received a
row, each pair's row in and out, over the seconds a step of
`dgi_moe_gmm_step`), read where the engine keeps pages per layer kind
beside an indexer (`attn_row_steps_scan` and `index_layers_scored`): the
expert layer is shared code, and the share says whether the attention
beside it moved it. Elsewhere it gives nothing to read."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "kernels_moe_held_gmm_roofline",
    Path(__file__).with_name("kernels_moe_held_gmm_roofline.py"))
_held = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_held)


def read(run):
    counters = run["win"]["c1"]["engine"]
    if "attn_row_steps_scan" not in counters \
            or "index_layers_scored" not in counters:
        return None
    value = _held.read(run)
    note = run["notes"].pop("kernels.moe_held_gmm_roofline", None)
    if note is not None:
        run["notes"]["kernels.moe_held_gmm_roofline.mixed_latent"] = note
    return value
