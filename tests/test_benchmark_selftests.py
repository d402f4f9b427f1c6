"""The benchmark's self-tests that hold its judged statistics, its knee and
spread rules and its round readers (``benchmark/tests/test_metrics.py``,
``test_sweep.py``, ``test_spread.py``, ``test_round_readers.py``, of
PR 31 ``test_mla_readers.py``, of PR 39 ``test_egress_readers.py``, of PR 40
``test_admit_ahead_reader.py``, of PR 42 ``test_kda_readers.py`` and of
PR 43 ``test_step_form_reader.py``, of PR 45
``test_sparse_attn_readers.py`` and ``test_closed_sessions.py``, of PR 49
``test_window_readers.py``, of PR 52 ``test_mla_sparse_readers.py``, of
PR 59 ``test_mla_window_readers.py``), inside
tier-1: they need no chip and no JAX, and a later change to ``harness/`` or
a reader should not wait for someone to run ``benchmark/tests`` by hand.
Imported from their files, as ``tests/test_model_olmoe.py`` imports
``harness``; each case keeps its name behind its module's."""

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:      # as benchmark/tests/conftest.py does
    sys.path.insert(0, str(BENCH))

for _stem in ("metrics", "sweep", "spread", "round_readers", "mla_readers",
              "egress_readers", "admit_ahead_reader", "kda_readers",
              "step_form_reader", "sparse_attn_readers", "closed_sessions",
              "window_readers", "mla_sparse_readers", "mla_window_readers"):
    _spec = importlib.util.spec_from_file_location(
        f"benchmark_selftests_{_stem}", BENCH / "tests" / f"test_{_stem}.py")
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    for _name, _obj in vars(_mod).items():
        if _name.startswith("test_") and callable(_obj):
            globals()[f"test_{_stem}__{_name[5:]}"] = _obj
