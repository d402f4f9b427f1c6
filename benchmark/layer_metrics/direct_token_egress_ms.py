"""Mean time a token takes from its round's return on the engine thread to
the return of its event's socket write on the direct server's loop:
`DirectServer.stats` `egress_s` over `sse_events`, window delta, ms. The
program stamps the round once where it returns (`ready`) and the event
where it is written; `egress_notify_s`, `egress_pump_s` and
`egress_write_s` split the same seconds by the thread that held the token
(the run's detail file keeps all four under `counters`). A program without
the counters (the parent of the PR that added them) reads nothing."""

from harness.window import delta


def read(run):
    events = delta(run["win"], "direct", "sse_events")
    return 1e3 * delta(run["win"], "direct", "egress_s") / events \
        if events else None
