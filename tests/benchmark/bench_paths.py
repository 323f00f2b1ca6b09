"""Where the benchmark lives, for the tests beside this file."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(rel: str, name: str):
    """A module of the benchmark by its path (the directory is not a
    package, and some file names are not identifiers)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
