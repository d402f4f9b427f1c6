"""Streamed + device-path KV handoff (VERDICT r3 #3).

Two migration paths beyond the round-3 one-shot blob:

- **Streamed** (`StreamedExport` / `HandoffReceiver`): begin/piece/commit
  messages; pages cross the wire while the donor's chunked prefill is still
  computing. Invariant: decode continued on the receiver is bit-exact vs a
  single-engine oracle.
- **Device** (`migrate_kv_device`): same-device engine pairs move pages
  pool→pool in one jitted gather-scatter — zero host bytes (the intra-slice
  PD path: no page crosses the host, so this is the path that scales
  on-slice).

Ref anchor: the per-layer KV transfer contract the reference defines but
never wires (/root/reference/proto/inference.proto:121-127).
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow

from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.runtime.kv_handoff import (
    HandoffReceiver,
    StreamedExport,
    abort_message,
    export_slot_kv,
    is_stream_message,
    migrate_kv_device,
    serialize_handoff,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

MODEL = "llama3-tiny"
TOTAL_NEW = 10
# long enough to span several 16-token prefill chunks (buckets=(16,))
PROMPT = [(i * 29 + 3) % 500 for i in range(50)]


def _cfg(**kw):
    base = dict(
        max_batch_size=2, max_seq_len=96, block_size=16,
        prefill_buckets=(16,), dtype="float32",
    )
    base.update(kw)
    return EngineConfig(**base)


def _req(prompt=None, max_new=TOTAL_NEW, seed=None, temperature=0.0):
    return InferenceRequest(
        prompt_token_ids=list(prompt if prompt is not None else PROMPT),
        sampling=SamplingParams(max_new_tokens=max_new,
                                temperature=temperature, seed=seed),
    )


@pytest.fixture(scope="module")
def shared_params():
    return TPUEngine(MODEL, _cfg(), seed=0).params


@pytest.fixture(scope="module")
def reference_tokens(shared_params):
    eng = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    resp = eng.generate([_req()])[0]
    assert len(resp.token_ids) == TOTAL_NEW
    return resp.token_ids


def _stream(donor, recv, req, piece_blocks=2):
    """Drive a full streamed handoff donor→recv; returns (exp, slot)."""
    rx = HandoffReceiver(recv)
    exp = StreamedExport(donor, req, key="s1", piece_blocks=piece_blocks)
    result = None
    for msg in exp.messages():
        assert is_stream_message(msg)
        result = rx.handle(msg)
    assert result["state"] == "committed"
    return exp, result["slot"]


def _decode_all(eng, slot):
    while eng.slots[slot] is not None and \
            eng.slots[slot].finish_reason is None:
        eng.decode_step()
    return eng.finish_slot(slot)


def test_streamed_handoff_bit_exact(shared_params, reference_tokens):
    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    exp, slot = _stream(donor, recv, _req())
    # donor slot freed by the generator
    assert donor.num_active == 0
    assert exp.first_token is not None
    assert exp.pieces_sent >= 2, "multi-chunk prompt must stream >1 piece"
    assert exp.bytes_before_first_token > 0, \
        "pieces must cross the wire BEFORE prefill finishes"
    resp = _decode_all(recv, slot)
    assert [exp.first_token] + resp.token_ids[1:] == reference_tokens
    assert resp.token_ids == reference_tokens
    assert resp.finish_reason == "length"


def test_streamed_handoff_seeded_sampling_continues_stream(shared_params):
    """A seeded sampled generation keeps its exact random stream across the
    streamed migration (slot_key rides the commit)."""
    oracle = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    want = oracle.generate([_req(seed=7, temperature=0.8)])[0]

    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    _, slot = _stream(donor, recv, _req(seed=7, temperature=0.8))
    resp = _decode_all(recv, slot)
    assert resp.token_ids == want.token_ids


def test_streamed_receiver_prefix_hit_skips_uploads(shared_params):
    """Pages already resident via the receiver's prefix cache are never
    re-uploaded (the begin allocation is prefix-cache aware)."""
    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    # warm the receiver's radix with the same prompt
    warm = recv.submit(_req(max_new=1))
    recv.decode_step()
    recv.finish_slot(warm, cache=True)

    rx = HandoffReceiver(recv)
    exp = StreamedExport(donor, _req(), key="s2", piece_blocks=2)
    staged = 0
    result = None
    for msg in exp.messages():
        result = rx.handle(msg)
        if result.get("state") == "staged":
            staged += result["blocks"]
    sess_cached = result and result.get("state") == "committed"
    assert sess_cached
    # whole prompt cached → only the pending-token block could stage
    assert staged <= 1
    resp = _decode_all(recv, result["slot"])
    oracle = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    want = oracle.generate([_req()])[0]
    assert resp.token_ids == want.token_ids


def test_streamed_messages_without_begin_rejected(shared_params):
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    rx = HandoffReceiver(recv)
    with pytest.raises(ValueError, match="no streamed handoff session"):
        rx.handle(abort_message("nope") .replace(b"\x03", b"\x01", 1))
    # abort for an unknown session is a no-op, not an error
    assert rx.handle(abort_message("nope"))["state"] == "aborted"


def test_streamed_abort_frees_receiver_blocks(shared_params):
    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    free0 = recv.manager.num_free
    rx = HandoffReceiver(recv)
    exp = StreamedExport(donor, _req(), key="s3", piece_blocks=2)
    gen = exp.messages()
    rx.handle(next(gen))            # begin → receiver allocates
    rx.handle(next(gen))            # one piece staged
    assert recv.manager.num_free < free0
    gen.close()                     # donor gives up (failed POST path)
    assert donor.num_active == 0    # donor slot freed on GeneratorExit
    rx.handle(abort_message("s3"))
    assert recv.manager.num_free == free0
    assert not recv.manager.pending.uploads


def test_streamed_rejects_sliding_window(shared_params):
    donor = TPUEngine("mistral-tiny", EngineConfig(
        max_batch_size=2, max_seq_len=96, prefill_buckets=(16, 32)))
    with pytest.raises(ValueError, match="sliding-window"):
        StreamedExport(donor, _req(), key="x")


def test_streamed_legacy_blob_still_handled(shared_params):
    """One receiver callable serves both wire modes."""
    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    slot = donor.submit(_req())
    raw = serialize_handoff(export_slot_kv(donor, slot))
    donor.finish_slot(slot, cache=False)
    assert not is_stream_message(raw)
    result = HandoffReceiver(recv).handle(raw)
    assert result["streamed"] is False
    resp = _decode_all(recv, result["slot"])
    oracle = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    assert resp.token_ids == oracle.generate([_req()])[0].token_ids


# ---------------------------------------------------------------------------
# Device-path migration (same-device pools: no host bytes)
# ---------------------------------------------------------------------------


def test_device_migration_bit_exact(shared_params, reference_tokens):
    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    slot = donor.submit(_req())
    for _ in range(3):
        donor.decode_step()
    dslot = migrate_kv_device(donor, recv, slot)
    donor.finish_slot(slot, cache=False)
    resp = _decode_all(recv, dslot)
    assert resp.token_ids == reference_tokens
    assert resp.finish_reason == "length"


def test_device_migration_right_after_prefill(shared_params,
                                              reference_tokens):
    """The PD shape: migrate immediately after the first token samples."""
    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    slot = donor.submit(_req())
    dslot = migrate_kv_device(donor, recv, slot)
    donor.finish_slot(slot, cache=False)
    resp = _decode_all(recv, dslot)
    assert resp.token_ids == reference_tokens


def test_device_migration_window_state(shared_params):
    """Sliding-window donors migrate release state without uploading the
    released (garbage) pages."""
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=96,
                        prefill_buckets=(16, 32), multi_step=4)
    prompt = [(i * 13) % 500 for i in range(30)]
    ref = TPUEngine("mistral-tiny", ecfg)
    want = ref.generate([_req(prompt, 24)])[0]

    donor = TPUEngine("mistral-tiny", ecfg, params=ref.params)
    recv = TPUEngine("mistral-tiny", ecfg, params=ref.params)
    slot = donor.submit(_req(prompt, 24))
    for _ in range(10):
        donor.decode_step()
    wf = donor.manager.seq_window_front[donor.slots[slot].seq_id]
    assert wf > 0
    dslot = migrate_kv_device(donor, recv, slot)
    seq_id = recv.slots[dslot].seq_id
    assert all(b == 0 for b in recv.manager.seq_blocks[seq_id][:wf])
    donor.finish_slot(slot, cache=False)
    resp = _decode_all(recv, dslot)
    assert resp.token_ids == want.token_ids


@pytest.mark.parametrize("path", ["oneshot", "streamed", "device"])
def test_first_token_stop_does_not_decode_on_recipient(shared_params, path):
    """A donor whose FIRST sampled token hits a stop id finishes with
    generated=[] and a stale last_token; every migration path must carry
    finish_reason so the recipient reports the stop instead of decoding
    garbage for max_new_tokens."""
    from distributed_gpu_inference_tpu.runtime.kv_handoff import (
        adopt_kv,
        deserialize_handoff,
    )

    oracle = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    first = oracle.generate([_req()])[0].token_ids[0]

    def stop_req():
        r = _req()
        r.sampling.stop_token_ids = (first,)
        return r

    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    recv = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    if path == "streamed":
        rx = HandoffReceiver(recv)
        exp = StreamedExport(donor, stop_req(), key="fs")
        result = None
        for msg in exp.messages():
            result = rx.handle(msg)
        slot = result["slot"]
    elif path == "device":
        s = donor.submit(stop_req())
        assert donor.slots[s].finish_reason == "stop"
        slot = migrate_kv_device(donor, recv, s)
        donor.finish_slot(s, cache=False)
    else:
        s = donor.submit(stop_req())
        h = export_slot_kv(donor, s)
        assert h.finish_reason == "stop"
        donor.finish_slot(s, cache=False)
        slot = adopt_kv(recv, deserialize_handoff(serialize_handoff(h)))
    assert recv.slots[slot].finish_reason == "stop"
    recv.decode_step()      # must NOT advance the finished slot
    resp = recv.finish_slot(slot)
    assert resp.token_ids == []
    assert resp.finish_reason == "stop"


def test_combined_seq_sharded_prefill_streams_to_tp_decode(shared_params):
    """COMBINED regime (VERDICT r3 #10): kv_seq_sharded prefill engine on a
    seq submesh chunk-prefills a long prompt through 1/seq pools and
    STREAMS the handoff to a decode engine on a disjoint model-TP submesh;
    continuation bit-exact vs the single-chip oracle."""
    import jax

    from distributed_gpu_inference_tpu.parallel.mesh import MeshPlan, make_mesh

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    seq_mesh = make_mesh(MeshPlan(seq=2), devs[:2], keep_trivial_axes=False)
    tp_mesh = make_mesh(MeshPlan(model=2), devs[2:4],
                        keep_trivial_axes=False)

    oracle = TPUEngine(MODEL, _cfg(max_seq_len=256), params=shared_params,
                       seed=0)
    want = oracle.generate([_req()])[0]

    pre = TPUEngine(MODEL, _cfg(max_seq_len=256, kv_seq_sharded=True),
                    params=shared_params, mesh=seq_mesh)
    dec = TPUEngine(MODEL, _cfg(max_seq_len=256), params=shared_params,
                    mesh=tp_mesh)
    rx = HandoffReceiver(dec)
    exp = StreamedExport(pre, _req(), key="combo", piece_blocks=2)
    result = None
    for msg in exp.messages():
        result = rx.handle(msg)
    assert result["state"] == "committed"
    assert exp.bytes_before_first_token > 0
    resp = _decode_all(dec, result["slot"])
    assert resp.token_ids == want.token_ids


def test_device_migration_rejects_mismatch(shared_params):
    donor = TPUEngine(MODEL, _cfg(), params=shared_params, seed=0)
    slot = donor.submit(_req())
    other = TPUEngine(MODEL, _cfg(block_size=32), params=shared_params,
                      seed=0)
    with pytest.raises(ValueError, match="block_size mismatch"):
        migrate_kv_device(donor, other, slot)
