"""Traffic kind ``dlrm``: MLPerf DLRM instances, 13 dense features and one
id per table.

A copy of ``repro.data.synthetic.dlrm_batches`` (labels from a hidden
teacher), with the Zipf exponent read from the mix.  Batch fields:
``dense`` (batch, n_dense), ``sparse_ids`` (batch, tables), ``label``.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

from harness.traffic import id_weights, zipf_ids


def dlrm_batches(rng, batch: int, rows, n_dense: int,
                 zipf_a: float) -> Iterator[Dict[str, np.ndarray]]:
    rows = list(rows)
    while True:
        dense = rng.standard_normal((batch, n_dense)).astype(np.float32)
        ids = np.stack([zipf_ids(rng, (batch,), r, zipf_a) for r in rows],
                       axis=1)
        w = np.stack([id_weights(ids[:, i], salt=31 * i + 7)
                      for i in range(len(rows))], 1)
        score = (w.mean(1) * 2.0 + 0.3 * dense[:, :4].sum(1) / 2.0
                 + 0.4 * w[:, 0] * w[:, 1])
        p = 1.0 / (1.0 + np.exp(-2.0 * score))
        label = (rng.random(batch) < p).astype(np.float32)
        yield {"dense": dense, "sparse_ids": ids.astype(np.int32),
               "label": label}


def batches(rng, mix: dict, cfg: dict) -> Iterator[Dict[str, np.ndarray]]:
    return dlrm_batches(rng, int(mix["batch"]), cfg["rows"],
                        int(cfg["n_dense"]), float(mix["zipf_a"]))
