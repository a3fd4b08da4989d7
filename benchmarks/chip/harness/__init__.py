"""The chip benchmark's own code: manifest, traffic, program adapter,
plain references, correctness check, trace reduction and the run loop.

Nothing here is imported by the program; the program is imported only by
``harness.system``.
"""

import importlib.util
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]   # benchmarks/chip
REPO_ROOT = BENCH_DIR.parents[1]


def load_module(path: Path, name: str):
    """The Python file at ``path``, imported as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
