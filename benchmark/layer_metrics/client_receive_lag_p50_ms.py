"""From the return of the socket write of a stream's first token-bearing
event on the direct server's loop (`direct.first_write`'s `written`, the
program's stamp) to the instant the generator stamped that event (the row's
`t[0]`): loopback, aiohttp on both sides and the generator's own loop;
median over the requests due in the window, ms. Both are
`time.monotonic()`, one clock for every process of the machine. A row may
read a little below zero: the send releases the interpreter's lock, and the
server's thread stamps `written` only once it has the lock back, by when
the generator, another process, may have stamped its receipt; the notes
say how many rows did. An event received before the pump thread yielded its
chunk (`pumped`, taken before the send) says the two are not one clock: the
reader then returns nothing and says so in the run's notes. Only traced
requests have a timeline; a program without the event reads nothing."""

from harness.metrics import percentile

NAME = "client.receive_lag_p50_ms"


def read(run):
    lags, early = [], []
    for row in run["sample"]:
        for name, _, a in (row.get("timeline") or {}).get("events") or []:
            if name == "direct.first_write" and a and row["t"]:
                lags.append((row["t"][0] - a["written"]) * 1e3)
                early.append((row["t"][0] - a["pumped"]) * 1e3)
    if early and min(early) < 0.0:
        run["notes"][NAME] = (
            f"{sum(e < 0 for e in early)} of {len(early)} rows received "
            "their first event before the program handed it to the direct "
            f"server (least {min(early):.3f} ms): the generator's clock is "
            "not the program's")
        return None
    if lags and min(lags) < 0.0:
        run["notes"][NAME] = (
            f"{sum(lag < 0 for lag in lags)} of {len(lags)} rows stamped "
            "their first event before the direct server stamped its write's "
            f"return (least {min(lags):.3f} ms)")
    return percentile(lags, 50)
