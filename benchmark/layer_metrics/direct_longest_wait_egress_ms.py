"""What delivery adds to, or takes from, the wait a stream is judged by:
for the two events either side of the stream's longest stretch between two
socket writes (`direct.longest_wait` on the request's timeline, kept by the
direct server), the time the later one took from its round's return to its
write (`written` - `ready`) less the same of the earlier one; median over
the requests due in the window, ms. The stretch between the two writes is
the stretch between the two rounds' returns plus this difference. Only
traced requests have a timeline; a program without the event reads
nothing."""

from harness.metrics import percentile


def read(run):
    added = []
    for row in run["sample"]:
        for name, _, a in (row.get("timeline") or {}).get("events") or []:
            if name == "direct.longest_wait" and a:
                added.append(1e3 * ((a["written"] - a["ready"])
                                    - (a["prev_written"] - a["prev_ready"])))
    return percentile(added, 50)
