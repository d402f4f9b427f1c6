"""Share of the places in the chunk form's chunks (`mamba_chunk_size`
tokens) that held no token: 1 - `ssd_tokens_ragged` / (chunk x
`ssd_chunks_ragged`), window delta. A decoding row beside a piece is a
segment of one token in a whole chunk, and a piece's last chunk is as full
as the piece leaves it; the kernel's operands and matmuls cover the whole
chunk. A program without the counters gives nothing to read."""

from harness import shapes_ssd
from harness.window import delta


def read(run):
    chunks = delta(run["win"], "engine", "ssd_chunks_ragged")
    if not chunks:
        return None
    tokens = delta(run["win"], "engine", "ssd_tokens_ragged")
    return 100.0 * (1.0 - tokens / (shapes_ssd.dims(run["config"])["Q"]
                                    * chunks))
