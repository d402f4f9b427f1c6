"""Seconds tracing the round graphs `lower_serving_graphs` lowered (each
jitted function to its jaxpr), summed over the graphs: `graphs_trace_s` of
`get_stats()["startup"]` where the window opens. A warm start pays them
like a cold one. A program that times no start gives nothing to read."""


def read(run):
    startup = run["win"]["c0"]["engine"].get("startup")
    return None if startup is None else startup.get("graphs_trace_s")
