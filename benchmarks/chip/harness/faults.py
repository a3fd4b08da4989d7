"""Faults planted in the program's k-step merge, for reading the merge
check's upper end (``calibrate.py``) and for its tests.  Each takes the
trainer right after it is built, before its merge program is traced, and
breaks one thing Algorithm 2's merge (Zhao et al., lines 12-13) states.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def no_merge(tr):
    """The merge step replaced by a local step."""
    tr._train_merge = tr._train_local


def merge_copies_pod0(tr):
    """The tower's merge hands every pod pod 0's replica (the half of the
    batch the other pod trained on is dropped); v_hat is merged soundly."""
    mean = tr.opt._mean

    def pod0(tree, allow_lossy=True):
        if not allow_lossy:                   # the v_hat payload
            return mean(tree, allow_lossy=False)
        return jax.tree.map(lambda x: jnp.broadcast_to(x[:1], x.shape), tree)
    tr.opt._mean = pod0


def v_hat_unmeaned(tr):
    """Each pod takes its own v_local as v_hat, with no mean over pods."""
    mean = tr.opt._mean
    tr.opt._mean = lambda tree, allow_lossy=True: (
        mean(tree, allow_lossy=True) if allow_lossy else tree)


def v_hat_kept(tr):
    """v_hat is not refreshed at the merge (``merge_v`` off)."""
    tr.opt.cfg = dataclasses.replace(tr.opt.cfg, merge_v=False)


MERGE_FAULTS = {f.__name__: f for f in (no_merge, merge_copies_pod0,
                                         v_hat_unmeaned, v_hat_kept)}
