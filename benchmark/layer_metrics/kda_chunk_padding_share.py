"""Share of the places in the chunk form's 64-token chunks that held no
token: 1 - `kda_tokens_ragged` / (64 x `kda_chunks_ragged`), window delta.
A decoding row beside a piece is a segment of one token in a chunk of 64,
and a piece's last chunk is as full as the piece leaves it; the kernel's
operands and matmuls cover the whole chunk. A program without the counters
gives nothing to read."""

from harness import shapes_kda
from harness.window import delta


def read(run):
    chunks = delta(run["win"], "engine", "kda_chunks_ragged")
    if not chunks:
        return None
    tokens = delta(run["win"], "engine", "kda_tokens_ragged")
    return 100.0 * (1.0 - tokens / (shapes_kda.CHUNK * chunks))
