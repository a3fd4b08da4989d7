"""Traffic kind ``ctr``: the paper's multi-hot CTR instances.

A copy of ``repro.data.synthetic.ctr_batches`` (labels from a hidden
teacher, so AUC means something), with the Zipf exponent and the mask
rate read from the mix.  Batch fields: ``ids`` and ``field_ids`` (batch,
nnz_per_instance), ``mask``, ``label``.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

from harness.traffic import id_weights, zipf_ids


def ctr_batches(rng, batch: int, rows: int, n_fields: int, nnz: int,
                zipf_a: float, keep: float) -> Iterator[Dict[str, np.ndarray]]:
    while True:
        ids = zipf_ids(rng, (batch, nnz), rows, zipf_a)
        field_ids = rng.integers(0, n_fields, (batch, nnz)).astype(np.int32)
        mask = (rng.random((batch, nnz)) < keep).astype(np.float32)
        score = (id_weights(ids) * mask).sum(1) / np.sqrt(nnz)
        pair = id_weights(ids, salt=17) * mask
        score = score + 0.5 * (pair.sum(1) ** 2 - (pair ** 2).sum(1)) / nnz
        p = 1.0 / (1.0 + np.exp(-3.0 * score))
        label = (rng.random(batch) < p).astype(np.float32)
        yield {"ids": ids.astype(np.int32), "field_ids": field_ids,
               "mask": mask, "label": label}


def batches(rng, mix: dict, cfg: dict) -> Iterator[Dict[str, np.ndarray]]:
    return ctr_batches(rng, int(mix["batch"]), int(cfg["rows"]),
                       int(cfg["n_fields"]), int(cfg["nnz_per_instance"]),
                       float(mix["zipf_a"]), float(mix["keep"]))
