"""The one traffic generator.  A traffic mix is a JSON file of parameters
under ``benchmarks/chip/traffic/<name>.json``; the model's sizes come from
the configuration.

The generators are copies of ``repro.data.synthetic.ctr_batches`` and
``dlrm_batches`` (labels from a hidden teacher, so AUC means something),
with the Zipf exponent and the mask rate read from the mix instead of
fixed in code.  They are copied so that no change to the program can
change the yardstick.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterator

import numpy as np

KINDS = ("ctr", "dlrm")


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}, got "
                         f"{mix.get('kind')!r}")
    if int(mix.get("batch", 0)) <= 0:
        raise ValueError(f"{path}: batch must be a positive integer")
    return mix


def _id_weights(ids: np.ndarray, salt: int = 0x9E3779B9) -> np.ndarray:
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


def ctr_batches(rng, batch: int, rows: int, n_fields: int, nnz: int,
                zipf_a: float, keep: float) -> Iterator[Dict[str, np.ndarray]]:
    while True:
        ids = zipf_ids(rng, (batch, nnz), rows, zipf_a)
        field_ids = rng.integers(0, n_fields, (batch, nnz)).astype(np.int32)
        mask = (rng.random((batch, nnz)) < keep).astype(np.float32)
        score = (_id_weights(ids) * mask).sum(1) / np.sqrt(nnz)
        pair = _id_weights(ids, salt=17) * mask
        score = score + 0.5 * (pair.sum(1) ** 2 - (pair ** 2).sum(1)) / nnz
        p = 1.0 / (1.0 + np.exp(-3.0 * score))
        label = (rng.random(batch) < p).astype(np.float32)
        yield {"ids": ids.astype(np.int32), "field_ids": field_ids,
               "mask": mask, "label": label}


def dlrm_batches(rng, batch: int, rows, n_dense: int,
                 zipf_a: float) -> Iterator[Dict[str, np.ndarray]]:
    rows = list(rows)
    while True:
        dense = rng.standard_normal((batch, n_dense)).astype(np.float32)
        ids = np.stack([zipf_ids(rng, (batch,), r, zipf_a) for r in rows],
                       axis=1)
        w = np.stack([_id_weights(ids[:, i], salt=31 * i + 7)
                      for i in range(len(rows))], 1)
        score = (w.mean(1) * 2.0 + 0.3 * dense[:, :4].sum(1) / 2.0
                 + 0.4 * w[:, 0] * w[:, 1])
        p = 1.0 / (1.0 + np.exp(-2.0 * score))
        label = (rng.random(batch) < p).astype(np.float32)
        yield {"dense": dense, "sparse_ids": ids.astype(np.int32),
               "label": label}


def batches(mix: dict, model: dict, seed: int) -> Iterator[dict]:
    """The mix's endless batch stream for ``model``, fixed by ``seed``."""
    rng = np.random.default_rng(seed)
    if mix["kind"] == "ctr":
        return ctr_batches(rng, int(mix["batch"]), int(model["rows"]),
                           int(model["n_fields"]),
                           int(model["nnz_per_instance"]),
                           float(mix["zipf_a"]), float(mix["keep"]))
    return dlrm_batches(rng, int(mix["batch"]), model["rows"],
                        int(model["n_dense"]), float(mix["zipf_a"]))


def table_ids(batch: dict) -> Dict[str, np.ndarray]:
    """Each table's ids in ``batch``, (instances, ids per instance): the
    one table ``sparse`` of the CTR model, or DLRM's 26 one-hot tables."""
    if "ids" in batch:
        return {"sparse": batch["ids"]}
    ids = batch["sparse_ids"]
    return {f"emb_{i:02d}": ids[:, i:i + 1] for i in range(ids.shape[1])}


def distinct_rows(batch: dict) -> Dict[str, int]:
    """Distinct ids per table in ``batch``: the rows a pull must move."""
    return {n: int(np.unique(x).size) for n, x in table_ids(batch).items()}
