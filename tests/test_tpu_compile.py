"""The main-path Pallas kernels compile for a TPU v5e, no chip attached.

Each kernel is compiled by the TPU compiler for one chip of a described
``v5e:2x2`` topology at the widths ``chip_smoke.py`` trains baidu-ctr at:
embed_dim 64, a 32768-row working set, 1024 x 100 ids per batch into
40960 bags, a 2^20-row device cache and one chip's 3,906,250-row table
share; and the gather placement's pull, lookup and push on the
benchmark's 3,906,248-row table packed two rows to a 128-lane row,
checked for whole-table relayouts in the optimised HLO.  This shows what
interpret mode cannot (tile alignment, memory spaces, VMEM/SMEM
budgets); it runs nothing, so it says nothing about
results or times.  The topology is described inside a fixture, so a
worker that cannot describe it skips these tests and no other.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.embedding_backend import GatherBackend, PackCounters, PackedRows
from repro.core.sparse_optim import SparseAdagrad
from repro.kernels import embedding_bag as EB
from repro.kernels import hash_map as HM
from repro.kernels import ops
from repro.kernels import sparse_adagrad as SA

D = 64
ROWS = 3_906_250          # one chip's share of baidu-ctr's 2e9 rows
CAPACITY = 1 << 15        # working-set rows per pull
CACHE = 1 << 20           # device cache rows
NNZ = 1024 * 100          # ids per batch
BAGS = 1024 * 40          # instances x fields

KERNELS = ["embedding_bag", "sparse_adagrad_apply", "hash_lookup",
           "gather_rows_cached", "sparse_adagrad_cached_apply"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _case(kernel, chip):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32 = jnp.int32
    H = HM.hash_table_size(CACHE)
    push_rows = lambda rows: (sds((rows, D)), sds((rows, D)),
                              sds((CAPACITY,), i32), sds((CAPACITY, D)),
                              sds((CAPACITY, D)))
    return {
        "embedding_bag": (
            lambda w, i, s, x: EB.embedding_bag_pallas(w, i, s, x, BAGS),
            (sds((CAPACITY + 1, D)), sds((NNZ,), i32), sds((NNZ,), i32),
             sds((NNZ,)))),
        "sparse_adagrad_apply": (SA.sparse_adagrad_apply_pallas,
                                 push_rows(ROWS)),
        "hash_lookup": (
            HM.hash_lookup_pallas,
            (sds((H,), i32), sds((H,), i32), sds((CACHE,), i32),
             sds((CAPACITY,), i32))),
        "gather_rows_cached": (
            SA.gather_rows_cached_pallas,
            (sds((CACHE, D)), sds((CAPACITY,), i32))),
        "sparse_adagrad_cached_apply": (SA.sparse_adagrad_cached_apply_pallas,
                                        push_rows(CACHE)),
    }[kernel]


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = _case(kernel, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the benchmark's 3,906,248-row share, two 64-wide rows to a 128-lane row
PACKED_LOGICAL = 3_906_248
PACKED_ROWS = PACKED_LOGICAL // 2


def _whole_table_relayouts(hlo: str) -> list:
    """Instructions of the optimised HLO that copy, pad or transpose an
    array with the table's row count (packed or logical)."""
    rows = re.compile(rf"\b({PACKED_ROWS}|{PACKED_LOGICAL})\b")
    op = re.compile(r"=\s*(\S+)\s+(copy|pad|transpose)\(")
    return [line.strip() for line in hlo.splitlines()
            if (m := op.search(line)) and rows.search(m.group(1))]


@pytest.mark.parametrize("stage", ["pull", "lookup", "push"])
def test_packed_table_compiles_without_relayout(one_chip, monkeypatch, stage):
    """The gather and the fused push of a packed table compile for one v5e
    chip with no whole-table copy, pad or transpose: the table stays in
    its lane-dense (rows / 2, 128) layout in every program that touches
    it.  (The same stages on a (3,906,248, 64) table copy it to row layout
    and pad it to 128 lanes.)"""
    monkeypatch.setattr(ops, "kernel_mode", lambda: "pallas")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    gb, opt = GatherBackend(fused=True), SparseAdagrad()
    table = PackedRows(sds((PACKED_ROWS, 128)), PACKED_LOGICAL, D)
    state = PackCounters(sds(()), sds(()))
    ids = sds((NNZ,), jnp.int32)

    def pull(t, a, s, i):
        return gb.pull(t, a, s, i, CAPACITY)

    if stage == "pull":
        fn, args, donate = pull, (table, table, state, ids), (0, 1, 2)
    elif stage == "lookup":
        fn = lambda t, a, s, i: gb.lookup(t, a, s, i, CAPACITY)
        args, donate = (table, table, state, ids), ()
    else:
        ws = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(pull, table, table, state, ids)[0])
        fn = lambda t, a, s, w, g: gb.push(t, a, s, w, g, opt)
        args, donate = (table, table, state, ws, sds((CAPACITY + 1, D))), (0, 1, 2)
    hlo = jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    assert _whole_table_relayouts(hlo) == []
    if stage == "push":
        assert "tpu_custom_call" in hlo


def test_chip_smoke_refuses_cpu():
    """With only CPU devices the smoke script exits non-zero and prints no
    result line: it never falls back to the CPU."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
