"""Modules found by name: ``<kind>/<name>.py`` under a benchmark home.

A traffic driver is ``traffic/<driver>.py``, a graph generator
``generators/<generator>.py`` and a per-layer metric's reader
``metrics/<metric>.py``. A later cell brings a new kind of each as a
new file; nothing here or in the harness names them.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

HOME = Path(__file__).resolve().parent
_loaded: dict[Path, object] = {}


def load(kind: str, name: str, home: Path = HOME):
    """The module ``<home>/<kind>/<name>.py``, executed once."""
    path = (Path(home) / kind / f"{name}.py").resolve()
    if path not in _loaded:
        if not path.is_file():
            raise KeyError(f"no {kind} {name!r}: {path} is missing")
        tag = re.sub(r"\W", "_", f"bench_{kind}_{name}_{len(_loaded)}")
        spec = importlib.util.spec_from_file_location(tag, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
