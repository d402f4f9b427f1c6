"""The idle share of the chip that was idle most, in a cell across chips:
chips of one mesh wait for each other, so the mean hides a straggler."""


def read(run):
    t = run["trace"]
    if not t or not t.get("window_s") or t["devices"] < 2:
        return None
    return 100.0 * (1.0 - t["busy_s_min"] / t["window_s"])
