"""Finding a file of the benchmark by the name something else gives it
(stdlib only: the generator uses it too)."""

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.cache
def load_py(folder: str, stem: str):
    """``<folder>/<stem>.py`` as a module; the stem may hold ``-`` and
    ``.``, which no import statement could name."""
    path = os.path.join(HERE, folder, stem + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{stem}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
