"""Find one part of the benchmark by its name.

Each traffic shape, arrival loop, policy generator and per-layer
metric is a module of its own, ``<kind>/<name>.py`` under this
directory, named by a mix file, a configuration file or
``BENCHMARK.json``.  A new one is a new file; no file that is already
there changes.
"""

from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def load(kind: str, name: str, root: str = HERE):
    """The module ``<root>/<kind>/<name>.py``."""
    if not _NAME.fullmatch(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
