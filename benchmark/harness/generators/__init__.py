"""Traffic generators, one module each, found by the name a traffic file
gives under ``generator``. A module has two functions:

``generate(params, rate_rps, seed, seconds) -> plan``
    the requests of one run. ``plan["loop"]`` is ``"open"`` (``requests``,
    each with its ``due_s`` from the start of the plan) or ``"closed"``
    (``clients``, each a list of requests a client sends one after the
    other). ``plan["ramp_s"]`` seconds of the same traffic run before the
    measured window, so that the window opens on a system in its stride.
``prompt_lengths(params) -> iterable of int``
    every prompt length the mix can send: the harness warms the round
    shapes those lengths reach, and no others.
"""

import importlib
from types import ModuleType


def load(name: str) -> ModuleType:
    if not name.isidentifier():
        raise ValueError(f"bad generator name {name!r}")
    return importlib.import_module(f"{__name__}.{name}")
