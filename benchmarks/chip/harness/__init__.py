"""The chip benchmark's own code: manifest, traffic, program adapter,
plain references, correctness check, trace reduction and the run loop.

Nothing here is imported by the program; the program is imported only by
``harness.system``.
"""

from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]   # benchmarks/chip
REPO_ROOT = BENCH_DIR.parents[1]
