"""The 99th percentile of the window's pooled waits between two consecutive
events of a stream: the stall metric judged until PR 30. It is a quantile of
a mixture of a few round lengths and jumps where a cluster's share crosses
1 % (35 or 47 ms on one program at 2.0 req/s; 55 or 67 where a T=16 scan's
waits reach it at 4.0), so it is recorded and a cell is judged by a
statistic that repeats in it (PERF.md, section 2)."""


def read(run):
    return run["summary"].get("itl_p99_ms")
