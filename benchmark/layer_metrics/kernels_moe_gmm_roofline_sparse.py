"""`kernels.moe_gmm_roofline` for a configuration that names its experts'
width `moe_intermediate_size` (128 experts of 3 x 2048 x 768 here): the
same arithmetic (`shapes_moe.routed_layer_bytes` over the HBM peak against
the seconds of `dgi_moe_gmm_step.<n>` a step, the window's experts a call
carried down to the slice's occupancy), read from the configuration through
`shapes_sparse_attn.moe_config`."""

import importlib.util
from pathlib import Path

from harness import shapes_sparse_attn

_spec = importlib.util.spec_from_file_location(
    "kernels_moe_gmm_roofline",
    Path(__file__).with_name("kernels_moe_gmm_roofline.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


def read(run):
    if "sa_config" not in run["config"]:
        return None
    notes = {}
    value = _base.read({**run, "notes": notes,
                        "config": shapes_sparse_attn.moe_config(run["config"])})
    if "kernels.moe_gmm_roofline" in notes:
        run["notes"]["kernels.moe_gmm_roofline.sparse"] = \
            notes["kernels.moe_gmm_roofline"]
    return value
