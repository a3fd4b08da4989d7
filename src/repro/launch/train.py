"""Production training launcher — config-driven via ``build_trainer``.

    PYTHONPATH=src python -m repro.launch.train --arch baidu-ctr --shape train_mb1k \
        --k 20 --merge two_phase --steps 200 --ckpt-dir /tmp/run1

Model construction is delegated to ``repro.runtime.factory.build_trainer``
(driven by the ``repro.configs`` registry); the launcher only wires flags,
data streams, and fault tolerance.  Every registered arch trains here —
lm and gnn families under ``DenseTrainer``, and ALL recsys archs
(``baidu-ctr``, ``dlrm-mlperf``, ``din``, ``dien``,
``two-tower-retrieval``) under ``HybridTrainer`` through the shared online
predict-then-train loop (``repro.runtime.online.fit_online``).

Sparse placement (``--placement``): how embedding rows move per batch,
behind the ``EmbeddingBackend`` contract
(``pull(table, ids, capacity) -> WorkingSet``,
``push(table, accum, working_set, row_grads, opt)``):

  - ``gather`` (default): dedup + ``jnp.take``; single-device exact, and
    under GSPMD the compiler partitions the gather over row shards at the
    cost of value-blind all-reduce traffic.
  - ``routed``: the paper's PS request routing — ids bucketed by owning
    shard, exchanged with explicit all_to_alls over a hash-sharded table
    (wire ~= rows moved once); dropped-request counters are reported via
    ``trainer.overflow_dropped``.  On this CPU container the mesh
    degenerates to one shard, so the routed path runs end to end and its
    loss matches ``gather`` (the acceptance check).
  - ``cached``: the paper's §2.3 memory hierarchy — the full table and its
    AdaGrad accumulator stay host-resident; a device cache of
    ``--cache-rows`` rows serves the Zipf-hot working set (LFU-with-decay
    admission/eviction, write-through pushes, dirty spills).  Steady-state
    ``cache_hit_rate``/``evictions`` are reported in the training history
    next to ``overflow_dropped``; with ``--cache-rows >= rows`` the cache
    degenerates to a full mirror bit-identical to ``gather``.

``--capacity`` bounds the deduplicated working set per batch (static shape;
must be divisible by the shard count for ``routed``; ``--cache-rows`` must
cover it for ``cached``).

``--store disk`` drops the cold tier one level: full tables + accumulators
live in fixed-size row pages under ``--spill-dir`` (``--page-rows`` rows
per page) with an in-RAM LRU page cache (``--page-cache-pages``), async
read-ahead keyed off each batch's dedup'd id stream, and write-behind
dirty-page flushing — the three-level hierarchy of docs/storage.md.  Works
with ``gather`` and ``cached`` placements (``routed`` addresses
shard-resident rows and is rejected); with an unbounded page cache the
results are bit-identical to ``--store host``.

``--prefetch`` turns on the double-buffered pull prefetch (paper Fig. 5):
the next batch's working-set pull is dispatched while the current step is
still executing, for any placement — bit-identical results, overlapped
pull latency.  ``--merge-delay N`` (DenseTrainer archs only) applies each
k-step merge's cross-pod average N boundaries late (DCN latency hiding).
``--fused-kernels {auto,on,off}`` selects the fused Pallas sparse kernels
(gather+bag pull, scatter+AdaGrad push, cache-tier indirection variants —
see docs/kernels.md); bit-identical to the unfused path except for the
bag's f32 summation order on TPU.

``--serve`` co-locates a CTR serving tier with recsys training: a
``CTRServer`` (``runtime.serve_ctr``) scores a second request stream
through the engine's read-only lookup contract against the trainer's live
tables, draining at each step's commit boundary — freshly trained rows are
servable one step later and the training trajectory is bit-identical to a
run without ``--serve`` (see docs/serving.md).

On a real TPU cluster each process calls ``jax.distributed.initialize()``
(args: --coordinator/--num-processes/--process-id, or TPU auto-detection)
and the production mesh spans all pods; in this CPU container it runs the
same code path on the reduced (smoke) configs so the launcher itself is
exercised end to end.

Fault tolerance: on start the launcher resumes from the newest complete
checkpoint in --ckpt-dir; a crashed/preempted job is restarted with the
same command line (elastic: the mesh may differ across restarts).
"""

from __future__ import annotations

import argparse
import time


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--merge", default="two_phase",
                    choices=["flat", "two_phase", "bf16", "int8_ef"])
    ap.add_argument("--n-pod", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sparse-lr", type=float, default=0.5)
    ap.add_argument("--placement", default="gather",
                    choices=["gather", "routed", "cached"],
                    help="sparse pull/push backend (see module docstring)")
    ap.add_argument("--capacity", type=int, default=0,
                    help="working-set bound per batch (0: arch default)")
    ap.add_argument("--cache-rows", type=int, default=0,
                    help="device cache rows for --placement cached "
                         "(0: working-set capacity, the minimum)")
    ap.add_argument("--store", default="host", choices=["host", "disk"],
                    help="cold tier below the device cache: 'host' keeps "
                         "full tables in host RAM (default); 'disk' pages "
                         "them to --spill-dir (three-level hierarchy: "
                         "device cache -> page cache -> SSD; docs/storage.md)")
    ap.add_argument("--spill-dir", default="",
                    help="DiskStore page directory (required for --store "
                         "disk)")
    ap.add_argument("--page-rows", type=int, default=0,
                    help="rows per spill page for --store disk (0: 1024)")
    ap.add_argument("--page-cache-pages", type=int, default=0,
                    help="in-RAM page-cache budget for --store disk "
                         "(0: unbounded — full mirror)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffered pull prefetch: overlap the next "
                         "batch's pull with the current step (Fig. 5)")
    ap.add_argument("--fused-kernels", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused Pallas sparse pull/push + embedding-bag "
                         "kernels (equal to unfused up to the bag's f32 "
                         "summation order on TPU): auto = on "
                         "for a real TPU backend, off elsewhere; 'on' off-"
                         "TPU runs interpret under REPRO_KERNEL_INTERPRET=1 "
                         "or the jnp reference otherwise")
    ap.add_argument("--merge-delay", type=int, default=0,
                    help="apply k-step merges N boundaries late "
                         "(DenseTrainer archs; 0 = synchronous merges)")
    ap.add_argument("--serve", action="store_true",
                    help="co-locate a CTR serving tier with training "
                         "(recsys archs): a CTRServer scores a second "
                         "request stream through the engine's read-only "
                         "lookup, draining at each commit boundary — the "
                         "rows trained at step t are servable at t+1 and "
                         "the training trajectory is bit-identical to a "
                         "run without --serve (docs/serving.md)")
    ap.add_argument("--serve-batch", type=int, default=64,
                    help="dynamic-batch size of the co-located server "
                         "(one compiled predict executable; tail batches "
                         "pad up to this)")
    ap.add_argument("--strict-transfers", action="store_true",
                    help="fail fast on IMPLICIT host<->device transfers in "
                         "the online hot path (jax.transfer_guard; recsys "
                         "archs). Deliberate crossings stay explicit "
                         "(device_put staging, device_get metrics).")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use reduced configs (CPU container default)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="full production config (real accelerators)")
    # multi-process bring-up (real clusters)
    ap.add_argument("--coordinator", default="")
    ap.add_argument("--num-processes", type=int, default=0)
    ap.add_argument("--process-id", type=int, default=-1)
    return ap


def trainer_config(args):
    """The ``TrainerConfig`` the parsed launcher flags describe."""
    from repro.core.kstep import KStepConfig
    from repro.core.sparse_optim import SparseAdagradConfig
    from repro.runtime.trainer import TrainerConfig

    return TrainerConfig(
        n_pod=args.n_pod,
        kstep=KStepConfig(lr=args.lr, k=args.k, merge=args.merge),
        sparse=SparseAdagradConfig(lr=args.sparse_lr, initial_accumulator=0.01),
        placement=args.placement, capacity=args.capacity or None,
        cache_rows=args.cache_rows or None, prefetch=args.prefetch,
        store=args.store, spill_dir=args.spill_dir or None,
        page_rows=args.page_rows or None,
        page_cache_pages=args.page_cache_pages or None,
        fused_kernels={"auto": None, "on": True, "off": False}[
            args.fused_kernels],
        merge_delay=args.merge_delay,
        ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every,
    )


def main():
    args = build_argparser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.coordinator:
        import jax
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )

    import numpy as np
    from repro import configs
    from repro.data import synthetic as S
    from repro.runtime.factory import build_trainer
    from repro.runtime.online import fit_online

    spec = configs.get(args.arch)
    cfg = spec.smoke_cfg if args.smoke else spec.model_cfg
    tcfg = trainer_config(args)
    t0 = time.perf_counter()

    if spec.family == "lm":
        tr = build_trainer(args.arch, tcfg, smoke=args.smoke)
        if args.ckpt_dir and tr.resume():
            print(f"resumed at step {tr.step_num}")
        gen = S.lm_batches(seed=0, batch=max(args.n_pod * 4, 8), seq_len=64,
                           vocab=cfg.vocab)
        hist = tr.fit(gen, args.steps)
        final = f"{hist[-1]['loss']:.4f}" if hist else "n/a (steps < log_every)"
        print(f"final loss {final} "
              f"({tr.step_num / (time.perf_counter() - t0):.2f} steps/s)")
        return

    if spec.family == "gnn":
        import dataclasses as dc
        gcfg = dc.replace(cfg, d_in=32, n_classes=5)
        g = S.community_graph(seed=0, n_nodes=2000, avg_degree=8,
                              d_feat=32, n_classes=5)
        tr = build_trainer(args.arch, tcfg, smoke=args.smoke, model_cfg=gcfg)
        if args.ckpt_dir and tr.resume():
            print(f"resumed at step {tr.step_num}")
        batch = {k: np.stack([v] * args.n_pod) for k, v in
                 [("x", g.x), ("edge_src", g.edge_src),
                  ("edge_dst", g.edge_dst), ("labels", g.labels)]}
        loss = 0.0
        for _ in range(args.steps):
            loss = tr.train_step(batch, podded=True)
        if tr.ckpt:
            tr.ckpt.wait()   # async writer must land the final checkpoint
        print(f"final loss {loss:.4f} "
              f"({tr.step_num / (time.perf_counter() - t0):.2f} steps/s)")
        return

    # recsys family — hybrid trainer through the factory, every arch
    # (baidu-ctr, dlrm-mlperf, din, dien, two-tower-retrieval): online
    # predict-then-train where the stream carries labels, train-only where
    # it doesn't (two-tower).  --prefetch dispatches each batch's pull
    # before the predict/train pair so it overlaps the previous step.
    tr = build_trainer(args.arch, tcfg, smoke=args.smoke)
    if args.ckpt_dir and tr.resume():
        print(f"resumed at step {tr.step_num}")
    gen = S.recsys_batches(cfg, batch=args.batch, seed=1)

    if args.serve:
        # --- co-located train + serve: one process, one engine.  The
        # server reads the LIVE tables the trainer writes, through the
        # read-only lookup contract; its drain sits at the commit boundary
        # (right after train_step lands), so rows trained at step t are
        # servable for step t+1's traffic, and because lookup mutates
        # nothing the loss trajectory is bit-identical to a run without
        # --serve.
        from repro.runtime.factory import build_ctr_server

        srv = build_ctr_server(tr, max_batch=args.serve_batch)
        serve_gen = S.recsys_batches(cfg, batch=args.serve_batch, seed=2)
        loss = float("nan")
        for _ in range(args.steps):
            b = next(gen)
            if args.prefetch:
                tr.prefetch(b)
            srv.submit_batch(next(serve_gen))   # traffic lands mid-step
            loss = tr.train_step(b)
            srv.drain()                         # commit boundary
        s = srv.summary()
        hit = (f"serve_hit_rate {s['serve_hit_rate']:.3f} "
               if "serve_hit_rate" in s else "")
        print(f"final loss {float(loss):.6f} "
              f"served {int(s['served'])} qps {s['qps']:.1f} "
              f"p50 {s['p50'] * 1e3:.2f}ms p99 {s['p99'] * 1e3:.2f}ms {hit}"
              f"placement {args.placement} prefetch {args.prefetch} "
              f"({args.steps / (time.perf_counter() - t0):.2f} steps/s)")
        return

    hist, online_auc = fit_online(tr, gen, args.steps, window=20, log=print,
                                  strict_transfers=args.strict_transfers)
    loss = hist[-1]["loss"] if hist else float("nan")
    stats = tr.sparse_metrics()
    cache = (
        f"cache_hit_rate {stats['cache_hit_rate_total']:.3f} "
        f"evictions {stats['evictions_total']} "
        if "cache_hit_rate_total" in stats else ""
    )
    auc_s = f"online AUC {online_auc:.4f} " if online_auc is not None else ""
    print(f"final loss {float(loss):.6f} {auc_s}"
          f"placement {args.placement} prefetch {args.prefetch} "
          f"overflow_dropped {tr.overflow_dropped} {cache}"
          f"({args.steps / (time.perf_counter() - t0):.2f} steps/s)")


if __name__ == "__main__":
    main()
