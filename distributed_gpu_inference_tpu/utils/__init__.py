"""Substrate: typed data structures, tensor wire framing, configuration.

TPU-native re-design of the reference's ``common/`` package
(``common/data_structures.py``, ``common/serialization.py``) and the worker
config system (``worker/config.py``).
"""

from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: F401
    BlockRange,
    InferenceRequest,
    InferenceResponse,
    InferenceState,
    JobStatus,
    JobType,
    KVBlockMeta,
    ModelShardConfig,
    SessionConfig,
    WorkerInfo,
    WorkerRole,
    WorkerState,
    block_prefix_hashes,
    compute_prefix_hash,
    estimate_kv_cache_bytes,
)
from distributed_gpu_inference_tpu.utils.serialization import (  # noqa: F401
    StreamingTensorBuffer,
    TensorSerializer,
    deserialize_tensor_dict,
    serialize_tensor_dict,
)
