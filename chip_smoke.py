"""Chip smoke: baidu-ctr training on a TPU through the launcher's entry points.

    python chip_smoke.py             # one chip: phases a, b, c
    python chip_smoke.py --chips 4   # routed over 4 chips vs gather on one

The paper's own model at its published widths (embed_dim 64, 40 fields,
100 nnz per instance, MLP 512/256/1) at ``train_mb1k`` (batch 1024), over one
chip's share of the 2e9-row table: the full config shards its sparse state
over 512 chips, so one chip holds 2e9 / 512 = 3,906,250 rows (1.0 GB of f32
table plus 1.0 GB of AdaGrad accumulator), and ids are drawn from that
slice.  Weights and data come from fixed seeds.  The trainer is built by
``build_trainer`` from the launcher's own flags and driven by
``fit_online`` (predict-then-train), as ``repro.launch.train`` does.

One chip runs three phases on the same batches:

  a. ``--placement gather``, fused Pallas kernels on;
  b. ``--placement cached`` with a 2^20-row device cache, fused kernels on
     (with a, all five main-path kernels: bag, scatter-AdaGrad, hash
     probe, cached gather, cached scatter-AdaGrad);
  c. phase a's run with fused kernels off.

``--chips 4`` runs only the routed all-to-all exchange over a mesh of all
four chips, and the same batches on ``gather`` on one chip.

Each phase prints one line: the per-step losses, the online AUC,
``overflow_dropped``, ``kernel_mode()``, whether the compiled pull and
train stages hold a ``tpu_custom_call``, the compile seconds and the steady
steps/s — a smoke reading, not a benchmark.  The script exits non-zero,
without the result line, when JAX finds no TPU, when a loss is not finite,
when a fused phase compiled no kernel (or the unfused one did), or when the
compared losses differ by more than ``LOSS_RTOL``.  Its last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ROWS = 2_000_000_000 // 512    # one chip's share of the 2e9-row table
BATCH = 1024                   # train_mb1k
# covers the ~29.4K distinct ids a 1024 x 100 Zipf(1.1) batch draws from
# this slice, so the pull drops nothing and phases compare exactly
CAPACITY = 1 << 15
CACHE_ROWS = 1 << 20           # device cache of phase b: a real hot set
WARMUP, MEASURED = 5, 10
# SparseAdagradConfig's default; the launcher's 0.5, tuned on the smoke
# config, sends this full-width loss past 40 within four steps
SPARSE_LR = "0.05"
GATHER = ("--placement", "gather")
CACHED = ("--placement", "cached", "--cache-rows", str(CACHE_ROWS))
FUSED = ("--fused-kernels", "on")
UNFUSED = ("--fused-kernels", "off")
# Phases a and c run different programs: XLA fuses and rounds the fused
# and unfused train stages differently, and on TPU the dense tower's f32
# matmuls round their inputs to bf16 (2^-9), so a one-ulp difference can
# move a product by 2^-9 of itself.  Training amplifies that: on a v5e the
# first losses agree exactly and the largest gap over 15 steps was 6.4e-4
# on losses of 0.5 to 1.3.  A wrong row, bag or update is caught exactly
# by the kernel parity phase; this bound only has to catch a trajectory
# that goes astray, with ~8x headroom over the measured drift.
LOSS_RTOL = 5e-3
BAG_RTOL = 1e-5     # > 100 * 2^-24: f32 reassociation of a <= 100-row bag


class SmokeFailure(Exception):
    pass


def require_tpu():
    """The device list, or exit non-zero: this script never falls back."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform "
            f"{devices[0].platform!r}, {devices[0].device_kind!r}); "
            f"no result")
    return devices


def model_cfg(rows: int = ROWS):
    from repro.configs.baidu_ctr import MODEL

    return dataclasses.replace(MODEL, rows=rows)


def build(mcfg, flags):
    """The launcher's trainer for ``flags``, logging every step."""
    from repro.launch.train import build_argparser, trainer_config
    from repro.runtime.factory import build_trainer

    args = build_argparser().parse_args([
        "--arch", "baidu-ctr", "--batch", str(BATCH),
        "--capacity", str(CAPACITY), "--sparse-lr", SPARSE_LR, *flags])
    tcfg = dataclasses.replace(trainer_config(args), log_every=1)
    return build_trainer("baidu-ctr", tcfg, model_cfg=mcfg, seed=0), args


def stage_texts(tr, batch):
    """Compiled HLO text of the trainer's pull and train stages for
    ``batch`` (the executables ``train_step`` runs)."""
    import jax

    staged = tr._stage(batch)
    pull_args = (tr.tables, tr.sparse_state.accum, tr.backend_state,
                 tr.engine.ids_from_batch(staged))
    pull = tr._pull.lower(*pull_args).compile().as_text()
    wss, tables, accum, bstate = jax.eval_shape(tr._pull, *pull_args)
    train_fn = tr._train_merge if tr.cfg.kstep.k == 1 else tr._train_local
    train = train_fn.lower(
        tr.dense, tables, accum, bstate, wss, tr.pod_batch(staged),
        tr.opt_state, tr._overflow,
    ).compile().as_text()
    return pull, train


def run_phase(name: str, mcfg, flags, warmup: int = WARMUP,
              measured: int = MEASURED) -> dict:
    """Train ``warmup + measured`` batches online under the launcher
    ``flags``; print the phase line."""
    import jax

    from repro.data import synthetic as S
    from repro.kernels import ops
    from repro.runtime.online import fit_online

    tr, args = build(mcfg, flags)
    batches = S.recsys_batches(mcfg, batch=args.batch, seed=1)
    t0 = time.perf_counter()
    fit_online(tr, batches, 1)          # the first step compiles every stage
    compile_s = time.perf_counter() - t0
    fit_online(tr, batches, warmup - 1)
    jax.block_until_ready(tr.tables)
    t0 = time.perf_counter()
    _, auc = fit_online(tr, batches, measured, window=measured)
    jax.block_until_ready(tr.tables)
    steps_per_s = measured / (time.perf_counter() - t0)
    pull_txt, train_txt = stage_texts(tr, next(batches))
    custom = {"pull": "tpu_custom_call" in pull_txt,
              "train": "tpu_custom_call" in train_txt}

    losses = [h["loss"] for h in tr.history]
    mesh = tr._state_mesh()
    rec = {
        "phase": name, "placement": args.placement,
        "fused_kernels": args.fused_kernels,
        "cache_rows": args.cache_rows or None,
        "kernel_mode": ops.kernel_mode(),
        "tpu_custom_call": custom, "losses": losses, "online_auc": auc,
        "overflow_dropped": tr.overflow_dropped,
        "compile_s": compile_s, "smoke_steps_per_s": steps_per_s,
        "chips": mesh.size if mesh is not None else 1,
    }
    print("phase " + json.dumps(rec) + "  # smoke reading, not a benchmark",
          flush=True)
    del tr
    gc.collect()
    return rec


def kernel_parity(seed: int = 0) -> dict:
    """Each fused kernel against its jnp reference on one batch at full
    width: the row gather, the scatter-AdaGrad push and the hash probe move
    data only and must match bit for bit, on the table and on a 2^20-row
    cache; the bag must be within ``BAG_RTOL`` of each bag's sum of
    absolute terms (f32 reassociation of at most 100 terms)."""
    import jax
    import jax.numpy as jnp

    from repro.core.embedding_backend import _dedup, _with_drop_row
    from repro.data import synthetic as S
    from repro.kernels import ops, ref
    from repro.kernels.hash_map import hash_rebuild, hash_table_size
    from repro.kernels.sparse_adagrad import adagrad_row_updates

    mcfg = model_cfg()
    batch = next(S.recsys_batches(mcfg, batch=BATCH, seed=1))
    k_t, k_g, k_c, k_s = jax.random.split(jax.random.key(seed), 4)
    dim = mcfg.embed_dim
    table = jax.random.normal(k_t, (mcfg.rows, dim)) * 0.05
    accum = jnp.full_like(table, 0.01)
    uids, inv, _ = jax.jit(_dedup, static_argnums=1)(
        jnp.asarray(batch["ids"].reshape(-1)), CAPACITY)
    first = jnp.concatenate([jnp.ones((1,), bool), uids[1:] > uids[:-1]])
    same = lambda x, y: bool(jnp.array_equal(x, y))

    def push_matches(rows, acc, idx, real):
        # pads (repeats of entry 0) carry zero gradient, as in training
        g = jax.random.normal(k_g, (idx.shape[0], dim)) * real[:, None]
        want = jax.jit(lambda t, a: ref.sparse_adagrad_apply_ref(
            t, a, idx, *adagrad_row_updates(a[idx], g, t.dtype, lr=0.05,
                                            eps=1e-10)))(rows, acc)
        got = jax.jit(lambda t, a: ops.sparse_adagrad_apply(
            t, a, idx, g, lr=0.05, eps=1e-10))(rows, acc)
        return same(got[0], want[0]) and same(got[1], want[1])

    rec = {"kernel_mode": ops.kernel_mode()}
    # bag over the pulled working set, as the train stage runs it
    B, F = batch["ids"].shape[0], mcfg.n_fields
    seg = (jnp.arange(B, dtype=jnp.int32)[:, None] * F
           + jnp.asarray(batch["field_ids"])).reshape(-1)
    w = jnp.asarray(batch["mask"]).reshape(-1)
    working = _with_drop_row(jnp.take(table, uids, axis=0))
    bag = jax.jit(ops.embedding_bag_working, static_argnums=4)
    got = bag(working, inv, seg, w, B * F)
    want = ref.embedding_bag_ref(working, inv, seg, w, B * F)
    scale = ref.embedding_bag_ref(jnp.abs(working), inv, seg, jnp.abs(w),
                                  B * F)
    rec["bag_err_over_abs_sum"] = float(jnp.max(
        jnp.abs(got - want) / jnp.maximum(scale, 1e-30)))
    rec["bag_ok"] = rec["bag_err_over_abs_sum"] <= BAG_RTOL
    rec["gather_exact"] = same(jax.jit(ops.gather_rows_cached)(table, uids),
                               jnp.take(table, uids, axis=0))
    rec["push_exact"] = push_matches(table, accum, uids, first)
    del table, accum
    # cache tier: a full 2^20-slot map of distinct ids, probed by the batch
    slot_uid = jax.random.permutation(k_c, mcfg.rows)[:CACHE_ROWS].astype(
        jnp.int32)
    key_tab, slot_tab, _ = jax.jit(hash_rebuild, static_argnums=1)(
        slot_uid, hash_table_size(CACHE_ROWS))
    slots = jax.jit(ops.hash_lookup)(key_tab, slot_tab, slot_uid, uids)
    rec["hash_exact"] = same(slots, ref.hash_lookup_ref(
        key_tab, slot_tab, slot_uid, uids))
    rec["hash_hits"] = int(jnp.sum(slots >= 0))
    cache = jax.random.normal(k_s, (CACHE_ROWS, dim))
    cache_acc = jnp.full_like(cache, 0.01)
    cslots = jax.random.permutation(k_s, CACHE_ROWS)[:CAPACITY].astype(
        jnp.int32)
    n_real = CAPACITY - 100
    cslots = cslots.at[n_real:].set(cslots[0])
    rec["cached_gather_exact"] = same(
        jax.jit(ops.gather_rows_cached)(cache, cslots),
        jnp.take(cache, cslots, axis=0))
    rec["cached_push_exact"] = push_matches(
        cache, cache_acc, cslots, jnp.arange(CAPACITY) < n_real)
    print("kernels " + json.dumps(rec), flush=True)
    bad = [k for k, v in rec.items() if v is False]
    if bad or rec["kernel_mode"] != "pallas" or rec["hash_hits"] == 0:
        raise SmokeFailure(f"kernel parity failed: {bad or rec}")
    return rec


def check_phase(rec: dict, fused: bool):
    if not all(math.isfinite(x) for x in rec["losses"]):
        raise SmokeFailure(f"phase {rec['phase']}: non-finite loss "
                           f"{rec['losses']}")
    has_kernel = rec["tpu_custom_call"]["pull"] or rec["tpu_custom_call"][
        "train"]
    if fused and not has_kernel:
        raise SmokeFailure(f"phase {rec['phase']}: fused kernels on, but no "
                           f"tpu_custom_call in the compiled stages")
    if not fused and has_kernel:
        raise SmokeFailure(f"phase {rec['phase']}: fused kernels off, but "
                           f"the compiled stages hold a tpu_custom_call")


def check_match(a: dict, b: dict):
    diff = max(abs(x - y) / max(abs(y), 1.0)
               for x, y in zip(a["losses"], b["losses"]))
    print(f"compare {a['phase']} vs {b['phase']}: max |loss diff| / "
          f"max(|loss|, 1) {diff!r} (tolerance {LOSS_RTOL})", flush=True)
    if len(a["losses"]) != len(b["losses"]) or not diff <= LOSS_RTOL:
        raise SmokeFailure(f"phases {a['phase']} and {b['phase']} disagree: "
                           f"{a['losses']} vs {b['losses']}")


def one_chip():
    kernel_parity()
    mcfg = model_cfg()
    a = run_phase("a", mcfg, GATHER + FUSED)
    check_phase(a, fused=True)
    b = run_phase("b", mcfg, CACHED + FUSED)
    check_phase(b, fused=True)
    if not b["tpu_custom_call"]["pull"]:
        raise SmokeFailure("phase b: the cached pull compiled no kernel")
    c = run_phase("c", mcfg, GATHER + UNFUSED)
    check_phase(c, fused=False)
    check_match(a, c)


def four_chips():
    import jax

    if jax.device_count() < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX found "
                           f"{jax.device_count()}")
    # the routed exchange hash-shards rows over the 4 chips, so the table
    # takes the largest multiple of 4 within one chip's share
    mcfg = model_cfg(ROWS - ROWS % 4)
    r = run_phase("routed4", mcfg, ("--placement", "routed") + FUSED)
    check_phase(r, fused=True)
    g = run_phase("gather1", mcfg, GATHER + FUSED)
    check_phase(g, fused=True)
    check_match(r, g)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()
    devices = require_tpu()

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"FAILED: {e}", flush=True)
        raise SystemExit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
