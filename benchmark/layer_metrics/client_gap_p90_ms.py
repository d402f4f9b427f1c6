"""The longest wait between two consecutive events of a stream after its first
token, 90th percentile over the requests due in the window: the stall a long
decode scan or a co-scheduled prefill round imposes, which TPOT averages away.
On one chip it reads a T=64 scan (~710 ms) when over a tenth of the requests
met one and a T=16 scan (~206 ms) when fewer did, so it is recorded and the
pooled `itl_p99_ms` is judged."""


def read(run):
    return run["summary"].get("gap_p90_ms")
