"""Seconds the engine's load took, where the window opens: the
`dgi.engine.init` span's `init_s` of `get_stats()["startup"]` (weights made
or loaded and quantised, the pools, the jitted functions built; the round
graphs' lowering is not in it). A program that times no start (the parent of
the PR that added the spans) has no `startup` and gives nothing to read."""


def read(run):
    startup = run["win"]["c0"]["engine"].get("startup")
    return None if startup is None else startup.get("init_s")
