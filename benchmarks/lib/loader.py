"""Find a piece of the benchmark by the name the data gives it: one file
per builder, driver, layer metric, generator, reference operation and
control, imported from its path so that a later PR adds a file and edits
none."""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def path_of(kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return os.path.join(ROOT, kind, name + ext)


@functools.lru_cache(maxsize=None)
def module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py` as a module (loaded once)."""
    path = path_of(kind, name, ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    tag = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def data(kind: str, name: str) -> dict:
    """`benchmarks/<kind>/<name>.json` as a dict."""
    with open(path_of(kind, name, ".json")) as f:
        return json.load(f)
