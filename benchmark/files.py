"""How the harness finds things by name: data files, and code named in them."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_file_module(path: str):
    """A runner, reader or reference, loaded from its file (found by the
    name ``BENCHMARK.json`` or a data file gives it)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def import_name(dotted: str):
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def resolve(node):
    """Configuration files name code by dotted path: ``{"$import": name}``
    is the object, ``{"$call": name, "kwargs": {...}}`` its result,
    ``{"$tuple": [...]}`` a tuple."""
    if isinstance(node, dict):
        if "$import" in node:
            return import_name(node["$import"])
        if "$call" in node:
            return import_name(node["$call"])(**resolve(node.get("kwargs", {})))
        if "$tuple" in node:
            return tuple(resolve(v) for v in node["$tuple"])
        return {k: resolve(v) for k, v in node.items()}
    if isinstance(node, list):
        return [resolve(v) for v in node]
    return node
