"""TPULLMEngine end-to-end: load, generate, chat templating, TP wiring.

(Regression: load_model used to pass checkpoint_path to an engine that
didn't accept it — nothing drove this path end-to-end.)
"""

import pytest

# compile-heavy (jit/scan graphs): excluded from the fast CI gate
pytestmark = pytest.mark.slow

from distributed_gpu_inference_tpu.worker.engines.base import EngineLoadError
from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine


@pytest.fixture(scope="module")
def engine():
    e = TPULLMEngine({
        "model": "llama3-tiny", "max_batch_size": 2, "max_seq_len": 96,
    })
    e.load_model()
    return e


def test_load_and_generate(engine):
    out = engine.inference({"prompt": "hello world", "max_new_tokens": 6})
    assert isinstance(out["text"], str)
    assert out["usage"]["completion_tokens"] <= 6
    assert out["usage"]["prompt_tokens"] > 0
    assert engine.loaded


def test_chat_messages_path(engine):
    out = engine.inference({
        "messages": [
            {"role": "system", "content": "be brief"},
            {"role": "user", "content": "hi"},
        ],
        "max_new_tokens": 4,
    })
    assert isinstance(out["text"], str)


def test_deterministic_greedy(engine):
    a = engine.inference({"prompt": "abc", "max_new_tokens": 6})
    b = engine.inference({"prompt": "abc", "max_new_tokens": 6})
    assert a["text"] == b["text"]


def test_tp_size_wiring():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    e = TPULLMEngine({
        "model": "llama3-tiny", "max_batch_size": 1, "max_seq_len": 64,
        "tp_size": 2,
    })
    e.load_model()
    assert e.engine.mesh is not None
    assert "model" in str(e.engine.params["layers"]["wq"].sharding.spec)
    out = e.inference({"prompt": "tp", "max_new_tokens": 4})
    assert isinstance(out["text"], str)


def test_a_saved_tree_decoder_config_loads_and_serves_the_plain_engine():
    """What selected and shaped the standalone tree decoder (gone) is
    accepted and ignored: the worker loads the plain engine behind the
    batcher and serves greedy and sampled requests through it."""
    e = TPULLMEngine({
        "model": "llama3-tiny", "engine": "jax-speculative",
        "max_batch_size": 2, "max_seq_len": 96, "spec_widths": "banana",
        "serving": {"spec_max_batch": 4, "spec_max_active": 0},
    })
    e.load_model()
    assert e.config["engine"] == "jax" and "spec_widths" not in e.config
    assert e.engine.cfg.speculative is None and e.serving.active
    greedy = e.inference({"prompt": "abcdef", "max_new_tokens": 6})
    assert 0 < greedy["usage"]["completion_tokens"] <= 6
    sampled = e.inference({"prompt": "abcdef", "max_new_tokens": 6,
                           "temperature": 0.8})
    assert isinstance(sampled["text"], str)
    assert e.serving.get_stats()["completed"] == 2
    e.unload()


def test_tp_size_too_large_is_load_error():
    e = TPULLMEngine({"model": "llama3-tiny", "tp_size": 999})
    with pytest.raises(EngineLoadError, match="tp_size"):
        e.load_model()
