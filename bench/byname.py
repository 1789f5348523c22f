"""Files of this directory found by name: a tree model is
``models/<name>.py``, a metric's reader ``metrics/<name>.py``.  A name is
what ``BENCHMARK.json`` may call a configuration, cell or metric."""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: a name of ``BENCHMARK.json``, and so of a file of this directory
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load(folder: str, name: str):
    """The module ``<folder>/<name>.py`` of this directory."""
    path = HERE / folder / f"{name}.py"
    if not NAME.match(name) or not path.is_file():
        raise ValueError(f"no {folder}/{name}.py in {HERE}")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
