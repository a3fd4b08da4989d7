"""Whether the timed path is correct: the program's readings from its
first steps against the plain reference's, each number beside its limit.

Numbers, each by default a worst case so that one bad leaf or step shows
(``DEFAULT_FORMS``; a configuration's ``check`` group may name the steady
form of a number whose worst case swings by its nature, ``FORMS``):

``loss_gap``     |loss - ref| / |ref|, the max over the first steps
                 (``max_step``) or the first step's (``first_step``).
``score_gap``    max over instances of |score - ref|, the online scores
                 ``predict`` served before each step; max over the steps or
                 the first step's.
``first_score_gap``  the same for the first step alone: the forward pass at
                 the initial weights, which no update or ReLU kink has
                 touched yet, so it reads the arithmetic's precision.
``grad_gap``     the first step's gradient as the optimizer got it, per
                 leaf | |g| - |g_ref| | / max(|g_ref|, median leaf
                 |g_ref|), the worst leaf's or the median leaf's.  Dense
                 leaves (one per pod): Adam's first moment, which is the
                 gradient for beta1 = 0.  Table rows: inverted from the
                 first AdaGrad step, g^2 = a0 r^2 / (1 - r^2) with
                 r = -(w1 - w0) / lr and a0 the initial accumulator (the
                 accumulator's own growth a1 - a0 is no reading: g^2 is
                 near half a float32 ulp of a0 = 0.01 and rounds away).
``change_gap``   the parameters' change over the first steps, the same
                 measure; leaves whose reference gradient is under a
                 thousandth of the median leaf's are left out (they move by
                 round-off alone).
``merge_gap``    the first k-step merge against ``refstep.merge`` worked
                 from each pod's tower before the merge step and the
                 moments that step computed: per pod and per dense leaf of
                 the tower and of the shared denominator v_hat,
                 |p - p_ref| / max(|p_ref|, median leaf |p_ref|), the worst.
                 The merge is elementwise arithmetic with one right answer,
                 so the norm of the difference, not a gap of norms: a merge
                 that keeps one pod's replica has the reference's norm.
``window_compiles``  programs compiled inside the measured window.
"""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np

from harness import refstep

NUMBERS = ("loss_gap", "score_gap", "first_score_gap", "grad_gap",
           "change_gap", "merge_gap", "window_compiles")


def _leaves(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(x, np.float64)
            for p, x in flat}


def _per_pod(tree) -> Dict[str, np.ndarray]:
    out = {}
    for name, x in _leaves(tree).items():
        for p in range(x.shape[0]):
            out[f"pod{p}{name}"] = x[p]
    return out


def _norms(d: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v.reshape(-1))) for k, v in d.items()}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """Per leaf, |prog - ref| / max(ref, median ref)."""
    med = float(np.median(list(ref.values())))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep is None or k in keep}


def adagrad_grad_sq(w0, w1, lr: float, a0: float) -> np.ndarray:
    """Squared gradient of one AdaGrad step from its effect on the rows."""
    r = -(np.asarray(w1, np.float64) - np.asarray(w0, np.float64)) / lr
    r2 = np.minimum(r * r, 1.0 - 1e-12)
    return a0 * r2 / (1.0 - r2)


def merge_leaves(got: dict, cfg: dict) -> Dict[str, float]:
    """Per pod and leaf of the tower and of v_hat, the merge's gap from
    ``refstep.merge`` (see ``merge_gap``)."""
    x_ref, v_ref = refstep.merge(got["before"], got["m"], got["v_local"],
                                 cfg["deployment"]["lr"])
    out = {}
    for what, prog, ref in (("x", got["dense"], x_ref),
                            ("v_hat", got["v_hat"], v_ref)):
        ref, prog = _leaves(ref), _leaves(prog)
        med = float(np.median([np.linalg.norm(r) for r in ref.values()]))
        for name, r in ref.items():
            base = max(float(np.linalg.norm(r)), med, 1e-30)
            for p in range(prog[name].shape[0]):
                out[f"{what}:pod{p}{name}"] = float(
                    np.linalg.norm(prog[name][p] - r)) / base
    return out


def readings(prog: dict, ref: dict, cfg: dict) -> dict:
    """Every gap the numbers are taken from: per step (losses, scores) and
    per leaf (first gradient, change)."""
    d = cfg["deployment"]
    n = len(ref["losses"])
    out = {"loss_steps": [abs(prog["losses"][t] - ref["losses"][t])
                          / abs(ref["losses"][t]) for t in range(n)],
           "score_steps": [float(np.max(np.abs(
               np.asarray(prog["scores"][t], np.float64) - ref["scores"][t])))
               for t in range(n)]}
    g_ref = _norms(_per_pod(ref["grad_dense"]))
    g_prog = _norms(_per_pod(prog["moment1"]))
    for name, g in ref["grad_rows"].items():
        g_ref[name] = float(np.linalg.norm(g))
        g_prog[name] = float(np.sqrt(np.sum(adagrad_grad_sq(
            prog["rows0"][name], prog["rows1"][name], d["sparse_lr"],
            d["initial_accumulator"]))))
    out["grad_leaves"] = leaf_gaps(g_prog, g_ref)
    med = float(np.median(list(g_ref.values())))
    moving = {k for k, v in g_ref.items() if v >= 1e-3 * med}

    def change(first, last, rows0, rows_last):
        a, b = _per_pod(first), _per_pod(last)
        c = {k: b[k] - a[k] for k in a}
        for name in rows0:
            c[name] = np.asarray(rows_last[name], np.float64) - rows0[name]
        return _norms(c)

    c_ref = change(ref["dense0"], ref["dense_last"], ref["rows0"],
                   ref["rows_last"])
    c_prog = change(prog["dense0"], prog["dense_last"], prog["rows0"],
                    prog["rows_last"])
    out["change_leaves"] = leaf_gaps(c_prog, c_ref, keep=moving)
    out["left_out"] = sorted(set(g_ref) - moving)
    # a reference in the program's place (the control) has no merge
    out["merge_leaves"] = ({} if prog["merge"] is None
                           else merge_leaves(prog["merge"], cfg))
    return out


FORMS = {"loss_gap": ("loss_steps", {"max_step": max,
                                     "first_step": lambda x: x[0]}),
         "score_gap": ("score_steps", {"max_step": max,
                                       "first_step": lambda x: x[0]}),
         "grad_gap": ("grad_leaves", {
             "worst_leaf": lambda d: max(d.values()),
             "median_leaf": lambda d: float(np.median(list(d.values())))}),
         "change_gap": ("change_leaves", {
             "worst_leaf": lambda d: max(d.values()),
             "median_leaf": lambda d: float(np.median(list(d.values())))})}
DEFAULT_FORMS = {"loss_gap": "max_step", "score_gap": "max_step",
                 "grad_gap": "worst_leaf", "change_gap": "worst_leaf"}


def numbers(prog: dict, ref: dict, cfg: dict) -> Dict[str, float]:
    """The compared numbers from the program's and the reference's
    readings, each in the form the configuration's ``check`` group names
    (``DEFAULT_FORMS`` where it names none; see module docstring)."""
    r = readings(prog, ref, cfg)
    forms = dict(DEFAULT_FORMS, **cfg.get("check", {}))
    out = {}
    for name, (key, by_form) in FORMS.items():
        out[name] = float(by_form[forms[name]](r[key]))
    out["first_score_gap"] = float(r["score_steps"][0])
    out["merge_gap"] = max(r["merge_leaves"].values(), default=0.0)
    out["window_compiles"] = float(prog.get("window_compiles", 0))
    return out


def verdict(nums: Dict[str, float], limits: Dict[str, float]):
    """(correct, lines): every number at or under its limit, and one line
    per number, ``name value limit``.  A limit of None marks a number that
    is reported and not compared (it has no upper reading)."""
    lines, ok = [], True
    for k in NUMBERS:
        v, lim = nums[k], limits[k]
        if lim is None:
            lines.append(f"{k} {v!r} not compared")
            continue
        good = bool(np.isfinite(v)) and v <= lim
        ok &= good
        lines.append(f"{k} {v!r} limit {lim!r}{'' if good else '  FAIL'}")
    return ok, lines
