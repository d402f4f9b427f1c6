"""Share of the window that events spent over 50 ms on their way from their
round's return to the socket write: `DirectServer.stats` `egress_stall_s`
(each stalled event's seconds above the 50 ms, and a log line with its four
stamps) over the window's seconds, %. 0.0 is a reading: nothing stalled on
the way out. A program without the counter reads nothing."""

from harness.window import delta


def read(run):
    win = run["win"]
    if "egress_stall_s" not in win["c1"]["direct"]:
        return None
    return 100.0 * delta(win, "direct", "egress_stall_s") \
        / (win["w1"] - win["w0"])
