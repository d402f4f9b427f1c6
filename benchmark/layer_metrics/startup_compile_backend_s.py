"""Seconds of the process's XLA compile requests up to the window's opening
(`compile_s`): the round graphs' `.compile()` and the small programs the
probes and the warm requests meet. Cache retrievals on a warm start, XLA
and Mosaic on a cold one (`startup.cache_miss_compiles` says which). Read
only from a program that times its start (one with `startup`), so that the
six `startup.*` metrics of a line come from one program."""


def read(run):
    engine = run["win"]["c0"]["engine"]
    return None if "startup" not in engine else engine.get("compile_s")
