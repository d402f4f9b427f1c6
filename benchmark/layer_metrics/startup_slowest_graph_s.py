"""The largest `trace_s + lower_s` of one graph `lower_serving_graphs`
lowered, from the table of `get_stats()["startup"]` where the window opens.
The graph's name, the whole table and the phases' seconds and starts go to
`run["notes"]["startup"]`, and with it into the run's detail file. A program
that times no start, or lowered no graph, gives nothing to read."""


def read(run):
    startup = run["win"]["c0"]["engine"].get("startup")
    if not startup or not startup.get("graphs"):
        return None
    cost = {name: row["trace_s"] + row["lower_s"]
            for name, row in startup["graphs"].items()}
    slowest = max(cost, key=cost.get)
    run["notes"]["startup"] = dict(startup, slowest_graph=slowest)
    return cost[slowest]
