"""Readers: small functions from a :class:`benchmark.harness.Run` to one
number (or None when there is nothing to read), registered by name. One
file per source; a metric file names a reader and its arguments, so a new
metric over an existing source is a data file only. A new source is a new
file here, picked up by name: every module of this package is imported.
"""

from __future__ import annotations

import importlib
import pkgutil

_REGISTRY: dict = {}


def reader(fn):
    if fn.__name__ in _REGISTRY:
        raise ValueError(f"reader {fn.__name__} registered twice")
    _REGISTRY[fn.__name__] = fn
    return fn


def get(name: str):
    if not _REGISTRY:
        for m in pkgutil.iter_modules(__path__):
            importlib.import_module(f"{__name__}.{m.name}")
    if name not in _REGISTRY:
        raise KeyError(f"no reader {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]
