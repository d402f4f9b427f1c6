"""How late the generator sent: sent - due, 90th percentile. It guards
`correct` (a starved generator is not a fast server) and is printed so that
a slow TTFT can be told from a late send."""


def read(run):
    return run["summary"].get("gen_late_p90_ms")
