"""The benchmark's FLOP and least-byte counters at hand-checked shapes."""

import pytest

from harness import counts, manifest, peaks

PEAK = peaks.peak("TPU v5 lite")


def test_bag_counts_by_hand():
    # 3 distinct rows of 4 floats, 5 (id, bag, weight) entries, 2 bags
    flops, moved = counts.bag(distinct_rows=3, nnz=5, bags=2, dim=4)
    assert flops == 2 * 5 * 4
    assert moved == 4 * (3 * 4 + 3 * 5 + 2 * 4)


def test_push_counts_by_hand():
    flops, moved = counts.push(distinct_rows=10, dim=64)
    assert flops == 2 * 10 * 64
    # rows, accum, gradient read; rows, accum written; 10 ids
    assert moved == 4 * (5 * 10 * 64 + 10)


def test_least_time_names_its_bound():
    t, bound = counts.least_time(1.0, 819e9, PEAK)
    assert (t, bound) == (pytest.approx(1.0), "hbm")
    t, bound = counts.least_time(197e12 * 2, 1.0, PEAK)
    assert (t, bound) == (pytest.approx(2.0), "flops")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def _model(name):
    return manifest.load_module(manifest.reference_file(name),
                                f"test_ref_{name}")


def test_ctr_forward_flops_by_hand():
    cfg = {"embed_dim": 4, "n_fields": 2, "nnz_per_instance": 3,
           "attn_heads": 2, "mlp": [5, 1]}
    proj = 3 * 2 * 2 * 4 * 4              # q, k, v: F rows of d x d
    attn = 2 * (2 * 2 * 2 * 2 * 2)        # scores and mix: H F F hd, x2
    mlp = 2 * 8 * 5 + 2 * 5 * 1           # 8 -> 5 -> 1
    bag = 2 * 3 * 4
    assert _model("ctr").forward_flops(cfg) == proj + attn + mlp + bag
    assert counts.step_flops_per_instance(_model("ctr"), cfg) == 4 * (
        proj + attn + mlp + bag)


def test_dlrm_forward_flops_by_hand():
    cfg = {"embed_dim": 2, "rows": [5, 5], "bot_mlp": [3, 2],
           "top_mlp": [4, 1]}
    # 3 vectors -> 3 pairs of 2-wide dots; top input 3 + 2 = 5
    want = 2 * 3 * 2 + (2 * 5 * 4 + 2 * 4 * 1) + 2 * 2 * 3
    assert _model("dlrm").forward_flops(cfg) == want


def test_published_widths_ctr_is_about_17_mflop_per_instance():
    import json

    c = json.loads((manifest.REPO_ROOT / "benchmarks/chip/configs/"
                    "baidu-ctr-1of512.json").read_text())
    per = counts.step_flops_per_instance(_model("ctr"), c)
    assert 16e6 < per < 18e6


def test_rehearsal_gives_a_packed_table_its_physical_rows():
    """``rehearse.py`` sizes each table at its full logical rows, stored as
    the program stores them: baidu-ctr's 64-wide table two rows to a
    128-lane row, DLRM's 128-wide tables one."""
    import jax.numpy as jnp

    import rehearse
    from repro.core.embedding_backend import PackedRows

    small = {"sparse": PackedRows(jnp.zeros((5, 128)), 9, 64),
             "emb_00": jnp.zeros((9, 128))}
    got = rehearse.abstract(small, {"sparse": 3906249, "emb_00": 40000000})
    assert got["sparse"].data.shape == (1953125, 128)
    assert got["sparse"].rows == 9          # static metadata, not a shape
    assert got["emb_00"].shape == (40000000, 128)
    assert rehearse.abstract(small)["emb_00"].shape == (9, 128)
