"""The main-path Pallas kernels compile for a TPU v5e, no chip attached.

Each kernel is compiled by the TPU compiler for one chip of a described
``v5e:2x2`` topology at the widths ``chip_smoke.py`` trains baidu-ctr at:
embed_dim 64, a 32768-row working set, 1024 x 100 ids per batch into
40960 bags, a 2^20-row device cache and one chip's 3,906,250-row table
share.  This shows what interpret mode cannot (tile alignment, memory
spaces, VMEM/SMEM budgets); it runs nothing, so it says nothing about
results or times.  The topology is described inside a fixture, so a
worker that cannot describe it skips these tests and no other.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import embedding_bag as EB
from repro.kernels import hash_map as HM
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
