"""Compile a one-chip cell's programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py --workload ctr-gather-mb1k

Builds the cell's trainer at a few rows (the programs' code does not
depend on the row count), then lowers its pull, train (local and merge)
and predict programs with the cell's full-size state as abstract shapes
on one chip of a ``v5e:2x2`` topology, and prints each program's
``memory_analysis()``.  Here JAX sees the CPU, so the kernel dispatch is
steered to the compiled Pallas path the chip takes.  Nothing runs: this
says what the chip's compiler accepts and how much memory each program
asks for, not how fast it is.
"""

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import manifest, system
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.resolve(manifest.load(), args.workload)
    if cell.chips != 1:
        raise SystemExit("rehearse.py compiles one-chip cells")
    full = cell.config
    small = copy.deepcopy(full)
    rows = full["rows"]
    cap = full["deployment"]["capacity"]
    small["rows"] = ([max(8, min(r, cap)) for r in rows]
                     if isinstance(rows, list) else max(cap, 8))
    ops.kernel_mode = lambda: "pallas"          # the chip's dispatch
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    tr = system.build(small, int(cell.mix["batch"]), 0)
    from harness import traffic

    batch = next(traffic.batches(cell.mix, small, 0))

    full_rows = dict(zip(sorted(tr.tables), rows if isinstance(rows, list)
                         else [rows]))

    def sds(tree, table_rows=False):
        def one(path, x):
            shape = x.shape
            name = jax.tree_util.keystr(path[:1]).strip("[]'")
            if table_rows and name in full_rows:
                shape = (full_rows[name],) + shape[1:]
            return jax.ShapeDtypeStruct(shape, x.dtype, sharding=chip)
        return jax.tree_util.tree_map_with_path(one, tree)

    tables = sds(tr.tables, table_rows=True)
    accum = sds(tr.sparse_state.accum, table_rows=True)
    bstate, dense = sds(tr.backend_state), sds(tr.dense)
    opt, over = sds(tr.opt_state), sds(tr._overflow)
    staged = sds(jax.device_put(batch))
    ids = sds(tr.engine.ids_from_batch(jax.device_put(batch)))
    podded = sds(tr.pod_batch(jax.device_put(batch)))
    with jax.default_matmul_precision(full["matmul_precision"]):
        pull = tr._pull.lower(tables, accum, bstate, ids)
        wss = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), jax.eval_shape(
                tr._pull, tables, accum, bstate, ids)[0])
        progs = {
            "pull": pull,
            "train_local": tr._train_local.lower(
                dense, tables, accum, bstate, wss, podded, opt, over),
            "train_merge": tr._train_merge.lower(
                dense, tables, accum, bstate, wss, podded, opt, over),
            "predict": tr._predict_jit.lower(dense, tables, accum, bstate,
                                             staged),
        }
        for name, low in progs.items():
            m = low.compile().memory_analysis()
            print(json.dumps({
                "workload": args.workload, "program": name,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes}), flush=True)


if __name__ == "__main__":
    main()
