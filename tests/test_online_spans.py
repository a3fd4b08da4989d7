"""The online step's host spans and its staging counter: ``fit_online``
over a smoke baidu-ctr ``HybridTrainer`` opens the spans of
``repro.runtime.spans`` in step order, and ``staged_bytes`` counts every
batch shipped to the device, once by ``predict`` and once by
``train_step``."""

import contextlib

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.kstep import KStepConfig
from repro.core.sparse_optim import SparseAdagradConfig
from repro.data import synthetic as S
from repro.runtime import spans
from repro.runtime.factory import build_trainer
from repro.runtime.online import fit_online
from repro.runtime.trainer import TrainerConfig

STEPS, K, LOG_EVERY = 4, 3, 4     # step 3 merges, step 4 logs

PREDICT = ["repro.predict.stage", "repro.predict.launch",
           "repro.predict.fetch"]
TRAIN = ["repro.train.stage", "repro.train.ids", "repro.train.pull",
         "repro.train.pod_batch"]


@pytest.fixture(scope="module")
def batches():
    gen = S.recsys_batches(configs.get("baidu-ctr").smoke_cfg, batch=64,
                           seed=5)
    return [next(gen) for _ in range(STEPS)]


def _trainer(prefetch=False):
    return build_trainer("baidu-ctr", TrainerConfig(
        n_pod=2, kstep=KStepConfig(lr=1e-3, k=K, b1=0.0),
        sparse=SparseAdagradConfig(lr=0.1, initial_accumulator=0.01),
        log_every=LOG_EVERY, prefetch=prefetch))


def _recorded(monkeypatch):
    names = []

    def record(name):
        names.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(spans, "span", record)
    return names


def test_span_sequence_per_step(batches, monkeypatch):
    tr = _trainer()
    names = _recorded(monkeypatch)
    fit_online(tr, iter(batches), STEPS)
    want = []
    for step in range(1, STEPS + 1):
        launch = ("repro.train.launch_merge" if step % K == 0
                  else "repro.train.launch")
        want += (["repro.online.next_batch"] + PREDICT + TRAIN
                 + [launch, "repro.online.meter"])
        if step % LOG_EVERY == 0:
            want.append("repro.online.log")
    assert names == want
    assert names.count("repro.online.log") == 1
    assert len(tr.history) == 1


def test_prefetch_path_puts_its_pull_under_the_pull_span(batches,
                                                         monkeypatch):
    """With ``prefetch``, staging and the ids program run inside the
    prefetcher's dispatch, so the train side opens only the pull span
    (the dispatch and the commit), the pod split and the launch."""
    tr = _trainer(prefetch=True)
    names = _recorded(monkeypatch)
    fit_online(tr, iter(batches[:1]), 1)
    assert names == (["repro.online.next_batch"] + PREDICT
                     + ["repro.train.pull", "repro.train.pod_batch",
                        "repro.train.launch", "repro.online.meter",
                        "repro.online.log"])


def test_staged_bytes_counts_each_batch_twice(batches):
    tr = _trainer()
    assert tr.staged_bytes == 0
    fit_online(tr, iter(batches), STEPS)
    per_batch = sum(np.asarray(x).nbytes for x in batches[0].values())
    assert tr.staged_bytes == 2 * per_batch * STEPS


def test_staged_bytes_skips_leaves_already_on_device(batches):
    tr = _trainer()
    on_device = jax.device_put(batches[0])
    tr.predict(on_device)
    assert tr.staged_bytes == 0
