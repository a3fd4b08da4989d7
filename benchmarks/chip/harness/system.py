"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program.  It builds the
``HybridTrainer`` that ``repro.runtime.factory.build_trainer`` makes from
the launcher's own flags (``repro.launch.train.build_argparser`` /
``trainer_config``), as ``python -m repro.launch.train`` does, and reads
what the check needs through the trainer's public state and the engine's
read-only lookup.  After the first step it fills a cache tier through the
engine's own pull stage.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np


def launcher_flags(cfg: dict, batch: int) -> list:
    """The ``repro.launch.train`` command line of this configuration."""
    d = cfg["deployment"]
    flags = ["--arch", cfg["arch"], "--batch", str(batch),
             "--placement", d["placement"], "--capacity", str(d["capacity"]),
             "--n-pod", str(d["n_pod"]), "--k", str(d["k"]),
             "--merge", d["merge"], "--lr", repr(d["lr"]),
             "--sparse-lr", repr(d["sparse_lr"]),
             "--fused-kernels", d["fused_kernels"]]
    if d.get("cache_rows"):
        flags += ["--cache-rows", str(d["cache_rows"])]
    return flags


def model_config(cfg: dict):
    """The registry's model config of ``cfg['arch']`` with every field the
    configuration file states replaced by the file's value."""
    from repro import configs

    base = configs.get(cfg["arch"]).model_cfg
    over = {}
    for f in dataclasses.fields(base):
        if f.name in cfg and f.name != "name":
            v = cfg[f.name]
            if f.name == "dtype":
                v = jnp.dtype(v)
            elif isinstance(v, list):
                v = tuple(v)
            over[f.name] = v
    return dataclasses.replace(base, **over)


def _check_stated(cfg: dict, tcfg):
    """The program must run the optimizer the configuration states."""
    d = cfg["deployment"]
    got = {"adam_b1": tcfg.kstep.b1, "adam_b2": tcfg.kstep.b2,
           "adam_eps": tcfg.kstep.eps,
           "local_v_warmup": tcfg.kstep.local_v_warmup,
           "bias_correction": tcfg.kstep.bias_correction,
           "initial_accumulator": tcfg.sparse.initial_accumulator,
           "adagrad_eps": tcfg.sparse.eps}
    bad = {k: (v, d[k]) for k, v in got.items() if v != d[k]}
    if bad:
        raise ValueError(f"the launcher's trainer departs from the "
                         f"configuration {cfg['name']}: {bad}")


def build(cfg: dict, batch: int, seed: int):
    """The trainer for ``cfg`` at ``batch``, its weights drawn from
    ``seed`` on the device by the program's own factory."""
    from repro.launch.train import build_argparser, trainer_config
    from repro.runtime.factory import build_trainer

    args = build_argparser().parse_args(launcher_flags(cfg, batch))
    tcfg = trainer_config(args)
    _check_stated(cfg, tcfg)
    return build_trainer(cfg["arch"], tcfg, model_cfg=model_config(cfg),
                         seed=seed, table_scale=cfg["table_init_std"])


def fill_cache(trainer, batches: Iterator[dict]) -> int:
    """Pull ``batches`` through the trainer's own compiled pull stage,
    training on none of them, until the cache tier has admitted a row into
    each of its slots, and on until its evictions have written rows back
    to the cold tier and a pull writes back none more.  The cache is then
    full, as a deployment's is, and every later miss evicts.  Called after
    a step, the pulls evict the rows that step trained and left dirty (the
    oldest and coldest), so what is read back from them goes through the
    write-back.  A pull changes no row's value, so every later reading is
    the one the trainer gives with no fill.  Stops early where a pull
    admits nothing, and after ``8 * ceil(slots / capacity)`` pulls at the
    most.  Returns the pulls made; 0 where the placement has no cache."""
    engine = trainer.engine
    slots = int(getattr(engine.backend, "cache_rows", 0) or 0)
    if not slots:
        return 0
    target = sum(min(slots, int(t.shape[0]))
                 for t in trainer.tables.values())
    most = 8 * -(-slots // int(engine.capacity))
    pull = engine.pull_stage(donate=trainer.cfg.donate)
    last = counters(trainer)
    spilled = False
    pulls = 0
    batch = next(batches)
    while pulls < most:
        ids = engine.ids_from_batch(jax.device_put(batch))
        # the pull donates the sparse state; its outputs replace it, as
        # ``HybridTrainer.prefetch`` replaces it after a dispatch
        _, trainer.tables, accum, trainer.backend_state = pull(
            trainer.tables, trainer.sparse_state.accum,
            trainer.backend_state, ids)
        trainer.sparse_state = trainer.sparse_state._replace(accum=accum)
        pulls += 1
        batch = next(batches)          # made while the pull runs
        now = counters(trainer)
        if now["cache_fetched"] == last["cache_fetched"]:
            break
        wrote = now["cache_bytes_d2h"] > last["cache_bytes_d2h"]
        if now["cache_fetched"] >= target and spilled and not wrote:
            break
        spilled = spilled or wrote
        last = now
    return pulls


def read_rows(trainer, uids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Current values of the rows ``uids[name]`` (sorted, distinct) of each
    table, through the engine's read-only lookup stage (the serving path:
    cache-fresh rows included, nothing mutated)."""
    stage = trainer.engine.lookup_stage()
    cap = trainer.engine.capacity
    chunks = max(math.ceil(len(u) / cap) for u in uids.values())
    parts = {n: [] for n in uids}
    for c in range(chunks):
        flat = {}
        for n, u in uids.items():
            part = u[c * cap:(c + 1) * cap]
            ids = np.full((cap,), part[0] if part.size else u[0], np.int32)
            ids[:part.size] = part
            flat[n] = jax.device_put(ids)
        wss, _ = stage(trainer.tables, trainer.sparse_state.accum,
                       trainer.backend_state, flat)
        got = jax.device_get({n: (ws.rows, ws.inverse)
                              for n, ws in wss.items()})
        for n, u in uids.items():
            k = min(cap, max(len(u) - c * cap, 0))
            rows, inv = got[n]
            parts[n].append(np.asarray(rows)[np.asarray(inv)[:k]])
    return {n: np.concatenate(p) for n, p in parts.items()}


def dense(trainer):
    """The dense tower, one replica per pod (leading pod axis)."""
    return jax.device_get(trainer.dense)


def first_moment(trainer):
    """Adam's first moment per pod: with beta1 = 0 it is the gradient the
    optimizer got at the last step."""
    return jax.device_get(trainer.opt_state.m)


def merge_state(trainer) -> dict:
    """The dense tower and the k-step Adam moments the last step left, one
    replica per pod: ``m`` and ``v_local`` are what a merge step averages
    from, ``v_hat`` the shared denominator it set."""
    s = trainer.opt_state
    return jax.device_get({"dense": trainer.dense, "m": s.m,
                           "v_local": s.v_local, "v_hat": s.v_hat})


def counters(trainer) -> Dict[str, float]:
    """The program's own cumulative counters: dropped ids, and the cache
    tier's lookups and fetched rows where the placement has them."""
    out = {"overflow_dropped": float(trainer.overflow_dropped)}
    for k, v in trainer.engine.cache_counters(trainer.backend_state).items():
        out[f"cache_{k}"] = float(v)
    return out


def program_devices(trainer):
    """The devices that hold the trainer's state."""
    mesh = trainer._state_mesh()
    return list(mesh.devices.flat) if mesh is not None else [
        jax.devices()[0]]
