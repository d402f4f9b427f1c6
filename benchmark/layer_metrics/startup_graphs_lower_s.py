"""Seconds lowering the round graphs' jaxprs to MLIR, the Mosaic kernels'
lowering inside them, summed over the graphs `lower_serving_graphs` lowered:
`graphs_lower_s` of `get_stats()["startup"]` where the window opens. A warm
start pays them like a cold one. A program that times no start gives
nothing to read."""


def read(run):
    startup = run["win"]["c0"]["engine"].get("startup")
    return None if startup is None else startup.get("graphs_lower_s")
