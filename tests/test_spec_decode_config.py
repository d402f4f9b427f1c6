"""Speculative config validation + observability export (fast, no jit).

- SpecDecodeConfig (engine-integrated chain mode) rejects draft depths
  whose worst-case per-step block growth exceeds max_blocks_per_seq, with
  the limiting field named.
- What selected or shaped the standalone tree decoder (gone) still loads,
  as the plain engine, and nothing can attach a second decoder.
- MetricsCollector.record_spec_engine exports per-worker accept-rate and
  tokens-per-step counters for /metrics.
"""

import pytest

from distributed_gpu_inference_tpu.runtime.engine import EngineConfig
from distributed_gpu_inference_tpu.runtime.speculative import (
    SpecDecodeConfig,
)


def test_spec_decode_config_accepts_sane_depth():
    cfg = EngineConfig(max_batch_size=2, max_seq_len=128, block_size=16)
    SpecDecodeConfig(num_draft_tokens=4).validate(cfg)
    SpecDecodeConfig(num_draft_tokens=7).validate(cfg)


def test_spec_decode_config_rejects_zero_depth():
    cfg = EngineConfig(max_batch_size=2, max_seq_len=128, block_size=16)
    with pytest.raises(ValueError, match="num_draft_tokens"):
        SpecDecodeConfig(num_draft_tokens=0).validate(cfg)


def test_spec_decode_config_accepts_depth_beyond_old_small_q_cap():
    # the pre-round-6 small-q path capped K+1 at 8 queries (pages re-staged
    # per query); the ragged kernel stages pages per query TILE, so deeper
    # verify windows are valid — bounded only by block growth / max_seq_len
    cfg = EngineConfig(max_batch_size=2, max_seq_len=128, block_size=16)
    SpecDecodeConfig(num_draft_tokens=8).validate(cfg)
    SpecDecodeConfig(num_draft_tokens=16).validate(cfg)


def test_spec_decode_config_rejects_block_growth_overflow():
    # max_seq_len 8 / block 2 -> 4 blocks per sequence; a 7-token draft
    # window could touch ceil(9/2)+1 = 6 blocks per step
    cfg = EngineConfig(max_batch_size=2, max_seq_len=8, block_size=2)
    with pytest.raises(ValueError) as ei:
        SpecDecodeConfig(num_draft_tokens=7).validate(cfg)
    msg = str(ei.value)
    assert "num_draft_tokens" in msg          # the limiting field, by name
    assert "max_blocks_per_seq" in msg


def test_spec_decode_config_rejects_window_beyond_context():
    cfg = EngineConfig(max_batch_size=2, max_seq_len=8, block_size=4)
    with pytest.raises(ValueError, match="num_draft_tokens"):
        SpecDecodeConfig(num_draft_tokens=7).validate(cfg)


def test_engine_ctor_validates_spec_config():
    from distributed_gpu_inference_tpu.runtime.engine import TPUEngine

    with pytest.raises(ValueError, match="num_draft_tokens"):
        TPUEngine(
            "llama3-tiny",
            EngineConfig(max_batch_size=1, max_seq_len=32, block_size=16,
                         prefill_buckets=(16,),
                         speculative=SpecDecodeConfig(num_draft_tokens=40)),
        )


@pytest.mark.parametrize("surface", ["yaml", "engine dict"])
@pytest.mark.parametrize("key, value", [
    ("engine", "jax-speculative"), ("engine", "speculative"),
    ("spec_widths", "4,2,2"),
])
def test_tree_decoder_engine_keys_load_the_plain_engine(
        surface, key, value, tmp_path, monkeypatch, caplog):
    """The standalone tree decoder is gone; a saved worker YAML or engine
    dict that selected it (``engine: jax-speculative`` / ``speculative``)
    or shaped its tree (``spec_widths``) keeps loading — as the plain
    ``jax`` engine — and says once a process which decoder exists. On the
    configuration layer: nothing is loaded."""
    import logging

    from distributed_gpu_inference_tpu.utils import config as config_mod
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    monkeypatch.setattr(config_mod, "_retired_engine_warned", set())
    caplog.set_level(logging.WARNING)

    def load():
        if surface == "yaml":
            yml = tmp_path / "config.yaml"
            yml.write_text(
                "engines:\n  llm:\n    model: llama3-tiny\n"
                f"    {key}: {value!r}\n")
            cfg = config_mod.load_worker_config(yml, environ={})
            return cfg.engines["llm"].model_dump()
        return TPULLMEngine({"model": "llama3-tiny", key: value}).config

    for _ in range(3):
        got = load()
    assert got.get("engine", "jax") == "jax" and "spec_widths" not in got
    assert got["model"] == "llama3-tiny"
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith(f"{key}: ")]
    assert len(said) == 1 and "speculative_decode: true" in said[0]


def test_no_second_decoder_can_be_attached_to_the_batcher():
    """One admission path: the batcher takes an engine and a config and
    nothing that decodes beside the engine's rounds."""
    import dataclasses

    from distributed_gpu_inference_tpu.runtime.batcher import (
        BatcherConfig,
        BatcherServing,
        ContinuousBatcher,
    )

    class Engine:
        cfg = EngineConfig()

    with pytest.raises(TypeError, match="spec"):
        ContinuousBatcher(Engine(), BatcherConfig(), spec=object())
    with pytest.raises(TypeError, match="spec"):
        BatcherServing(Engine(), BatcherConfig(), spec=object())
    assert not [f.name for f in dataclasses.fields(BatcherConfig)
                if f.name.startswith("spec")]


def test_record_spec_engine_exports_per_worker():
    from distributed_gpu_inference_tpu.server.observability import (
        HAVE_PROMETHEUS,
        MetricsCollector,
    )

    mc = MetricsCollector()
    stats = {
        "spec_accepted": 30, "spec_drafted": 40, "spec_slot_steps": 10,
        "spec_accept_rate": 0.75, "spec_tokens_per_step": 4.0,
    }
    mc.record_spec_engine("worker-a", stats)
    # totals advance by deltas across scrapes, and a restart re-anchors
    stats2 = dict(stats, spec_accepted=50, spec_drafted=70,
                  spec_slot_steps=17)
    mc.record_spec_engine("worker-a", stats2)
    mc.record_spec_engine("worker-a", {"spec_accepted": 5, "spec_drafted": 6,
                                       "spec_slot_steps": 2,
                                       "spec_accept_rate": 0.8,
                                       "spec_tokens_per_step": 3.5})
    text = mc.render().decode()
    if HAVE_PROMETHEUS:
        assert 'speculative_accepted_tokens_total{worker="worker-a"} 50.0' \
            in text
        assert 'speculative_drafted_tokens_total{worker="worker-a"} 70.0' \
            in text
        assert 'speculative_worker_accept_rate{worker="worker-a"} 0.8' \
            in text
        assert 'speculative_worker_tokens_per_step{worker="worker-a"} 3.5' \
            in text


def test_worker_llm_engine_wires_spec_config():
    from distributed_gpu_inference_tpu.worker.engines.base import (
        EngineLoadError,
    )
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    eng = TPULLMEngine({
        "model": "llama3-tiny", "max_batch_size": 2, "max_seq_len": 64,
        "speculative_decode": True, "spec_num_draft_tokens": 3,
    })
    eng.load_model()
    assert eng.engine.cfg.speculative is not None
    assert eng.engine.cfg.speculative.num_draft_tokens == 3
    assert "spec_accept_rate" in eng.engine.get_stats()
    eng.unload()

    bad = TPULLMEngine({
        "model": "llama3-tiny", "max_batch_size": 2, "max_seq_len": 64,
        "speculative_decode": True, "spec_num_draft_tokens": 0,
    })
    with pytest.raises(EngineLoadError, match="speculative_decode"):
        bad.load_model()
