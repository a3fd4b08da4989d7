"""The traffic generator.  A traffic mix is a JSON file of parameters
under ``benchmarks/chip/traffic/<name>.json``; its ``kind`` names the
module beside it, ``traffic/<kind>.py``, whose ``batches(rng, mix, cfg)``
yields the kind's batches.  The model's sizes come from the
configuration, so a model with a batch layout of its own brings its kind
as a new module and its mixes as new files.

This module keeps what the kinds share: the Zipf-like id draw and the
teacher's per-id weights, copied from ``repro.data.synthetic`` so that no
change to the program can change the yardstick.  Which ids of a batch
belong to which table is the configuration's reference's to say
(``reference/<name>.py`` ``table_ids``).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterator

import numpy as np

from harness import load_module


def kind_file(mix_path: Path, kind: str) -> Path:
    return Path(mix_path).parent / f"{kind}.py"


def load(path: Path) -> dict:
    """The mix at ``path``, with ``kind_file``: the module of its kind."""
    mix = json.loads(Path(path).read_text())
    kind = mix.get("kind")
    if not isinstance(kind, str) or not kind.isidentifier() \
            or not kind_file(path, kind).is_file():
        raise ValueError(f"{path}: kind {kind!r} has no generator "
                         f"{kind_file(path, str(kind))}")
    if int(mix.get("batch", 0)) <= 0:
        raise ValueError(f"{path}: batch must be a positive integer")
    return dict(mix, kind_file=str(kind_file(path, kind)))


def id_weights(ids: np.ndarray, salt: int = 0x9E3779B9) -> np.ndarray:
    """Deterministic pseudo-random weight per id in [-1, 1] (splitmix)."""
    x = (ids.astype(np.uint64) + np.uint64(salt)) * np.uint64(
        0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return (x.astype(np.float64) / 2**64) * 2.0 - 1.0


def zipf_ids(rng: np.random.Generator, shape, vocab: int,
             a: float) -> np.ndarray:
    """Bounded-Pareto (Zipf-like) ids in [0, vocab): id 0 is the hottest."""
    u = rng.random(shape)
    ids = (vocab ** (1 - a) * (1 - u) + u) ** (1 / (1 - a))
    return np.minimum(ids.astype(np.int64), vocab - 1)


def batches(mix: dict, cfg: dict, seed: int) -> Iterator[dict]:
    """The mix's endless batch stream for the configuration ``cfg``,
    fixed by ``seed``."""
    kind = load_module(Path(mix["kind_file"]),
                       f"bench_traffic_{mix['kind']}")
    return kind.batches(np.random.default_rng(seed), mix, cfg)


def distinct_rows(model, batch: dict) -> Dict[str, int]:
    """Distinct ids per table in ``batch``, the tables as the reference
    ``model`` reads them: the rows a pull must move."""
    return {n: int(np.unique(x).size)
            for n, x in model.table_ids(batch).items()}
