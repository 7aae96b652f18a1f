"""Find a piece of the benchmark by its name: ``bench/<kind>/<name>.py``.

Metrics (``metrics``), graph generators (``gen/graphs``) and batch kinds
(``gen/batches``) are each a file of their own, named as ``BENCHMARK.json``,
a configuration or a traffic mix names them, so that a new one is a new file
and no existing file changes."""
from __future__ import annotations

import importlib.util
import os
import re

__all__ = ["load"]

BENCH = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"no {kind} named {name!r} ({path})")
    modname = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
    loaded = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(mod)
    return mod
