"""Compile requests of the process that missed the persistent compile cache,
up to the window's opening: `compile_misses` of `get_stats()["startup"]`.
0 on a warm start, so a line says whether its `setup_s` was one. A program
that times no start gives nothing to read."""


def read(run):
    startup = run["win"]["c0"]["engine"].get("startup")
    return None if startup is None else startup.get("compile_misses")
