"""A family is a set of files found by its name: `families/<family>.py`
(tensors, fills, byte counts; plain Python) and `reference/<family>.py` (its
float32 forward pass). A configuration file names its family under the
bench key `family`; absent means `llama`."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def family_name(config: dict) -> str:
    return config.get("family", "llama")


def load(kind: str, config: dict):
    """The module `<kind>/<family>.py` of a configuration's family; `kind`
    is `families` or `reference`."""
    name = family_name(config)
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"family {name!r} has no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
