"""The benchmark's per-name files, found as ``<root>/<kind>/<name>.py``:
``layouts/`` (a configuration's parameter tree, weights and counts),
``reference/`` (its plain forward pass) and ``metrics/`` (one reader per
per-layer metric). ``root`` is ``bench/`` unless a caller points the lookup
at another directory, as the tests do at their fixtures in ``testdata/``.
"""
from __future__ import annotations

import functools
import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
# a configuration file without a ``layout`` key is a dense GQA decoder
DEFAULT_LAYOUT = "dense_gqa"


@functools.lru_cache(maxsize=None)
def module(kind: str, name: str, root: str = BENCH):
    """The module of ``<root>/<kind>/<name>.py``, loaded once per path."""
    path = os.path.join(root, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layout(c: dict, root: str = BENCH):
    """The layout module that configuration file ``c`` names."""
    return module("layouts", c.get("layout", DEFAULT_LAYOUT), root)
