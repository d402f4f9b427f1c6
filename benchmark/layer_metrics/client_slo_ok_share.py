"""Share of the requests sent that met both latency limits of the cell
(TTFT, which grows with the prompt, and TPOT). A failed or refused request
met neither. The knee of a cell is the highest rate that keeps this at 90 %."""


def read(run):
    share = run["summary"].get("slo_ok_share")
    return None if share is None else 100.0 * share
