"""BENCHMARK.json's rules, and the harness finding every item by name."""

import copy
import json
import shutil

import numpy as np
import pytest

from harness import manifest

M = manifest.load()


def test_the_committed_manifest_is_sound():
    assert manifest.validate(M) == []


@pytest.mark.parametrize("edit, fault", [
    (lambda m: m["workloads"][0].update(name="bad name"), "bad name"),
    (lambda m: m["end_to_end"][1].update(unit="tokens per s"), "bad unit"),
    (lambda m: m["per_layer"][0].update(moves="pull_ms"), "no end-to-end"),
    (lambda m: m["configs"].append(dict(m["configs"][0], name="lonely",
                                        file="benchmarks/chip/run.py")),
     "no cell uses it"),
    (lambda m: m["end_to_end"][1].update(bound=0.5), "bound"),
    (lambda m: m["configs"][0].update(reduced=["embed_dim"]), "width"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")),
     "pair appears twice"),
    (lambda m: m["per_layer"][0]["workloads"].append("no-such-cell"),
     "unknown cell"),
    (lambda m: m.update(run_seconds=60), "run_seconds"),
])
def test_validation_refuses(edit, fault):
    m = copy.deepcopy(M)
    edit(m)
    errs = manifest.validate(m)
    assert any(fault in e for e in errs), errs


def test_every_per_layer_metric_moves_what_its_cells_report():
    for x in M["per_layer"]:
        for cell in x["workloads"]:
            reported = {e["name"] for e in M["end_to_end"]
                        if "workloads" not in e or cell in e["workloads"]}
            assert x["moves"] in reported


def test_new_items_are_found_by_name(tmp_path):
    """A new configuration, traffic mix, per-layer metric and cell take
    new files and a new manifest entry only."""
    root = tmp_path / "repo"
    shutil.copytree(manifest.BENCH_DIR, root / "benchmarks" / "chip")
    chip = root / "benchmarks" / "chip"
    cfg = json.loads((chip / "configs/baidu-ctr-1of512.json").read_text())
    cfg["name"] = "baidu-ctr-wide-cache"
    (chip / "configs/baidu-ctr-wide-cache.json").write_text(json.dumps(cfg))
    (chip / "traffic/ctr-zipf1.05-b1k.json").write_text(json.dumps(
        {"kind": "ctr", "batch": 1024, "zipf_a": 1.05, "keep": 0.9}))
    (chip / "metrics/steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    m = copy.deepcopy(M)
    m["configs"].append({"name": "baidu-ctr-wide-cache",
                         "source": "https://arxiv.org/abs/2201.05500",
                         "file": "benchmarks/chip/configs/"
                                 "baidu-ctr-wide-cache.json",
                         "reduced": ["rows"], "why": "a test item"})
    m["workloads"].append({"name": "ctr-new", "config": "baidu-ctr-wide-cache",
                           "traffic": "ctr-zipf1.05-b1k", "chips": 1,
                           "why": "a test item"})
    m["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "device", "moves": "train_instances_per_s",
                           "workloads": ["ctr-new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.validate(m, root) == []
    cell = manifest.resolve(m, "ctr-new", root)
    assert cell.config["name"] == "baidu-ctr-wide-cache"
    assert cell.mix["zipf_a"] == 1.05
    assert set(cell.readers) == {"steps_seen"}
    assert cell.readers["steps_seen"].read(type("C", (), {"steps": 7})) == 7.0
    assert cell.model.tables(cell.config)[0][0] == "sparse"


DIN_KIND = """
import numpy as np
from harness.traffic import zipf_ids


def batches(rng, mix, cfg):
    B, L, rows = int(mix["batch"]), int(cfg["hist_len"]), int(cfg["rows"])
    while True:
        n = rng.integers(1, L + 1, B)
        yield {"hist_ids": zipf_ids(rng, (B, L), rows, mix["zipf_a"])
               .astype(np.int32),
               "target_id": zipf_ids(rng, (B,), rows, mix["zipf_a"])
               .astype(np.int32),
               "hist_mask": (np.arange(L)[None] < n[:, None])
               .astype(np.float32),
               "label": (rng.random(B) < 0.5).astype(np.float32)}
"""

DIN_REFERENCE = """
import numpy as np


def tables(cfg):
    return [("item", int(cfg["rows"]), int(cfg["embed_dim"]))]


def table_ids(batch):
    return {"item": np.concatenate(
        [batch["hist_ids"], batch["target_id"][:, None]], axis=1)}
"""


def test_a_new_batch_layout_is_found_by_name(tmp_path):
    """A model whose batches the harness has never seen (a DIN-shaped
    layout: a behaviour history and a target over one item table) takes
    a kind module, a reference, a configuration, a mix and a cell, all
    new files and entries; the manifest, the feed's statistics and the
    window's id count read it through the reference's ``table_ids``."""
    from harness import traffic
    from harness.feed import Feed
    from harness.window import ids_per_batch

    root = tmp_path / "repo"
    shutil.copytree(manifest.BENCH_DIR, root / "benchmarks" / "chip")
    chip = root / "benchmarks" / "chip"
    (chip / "traffic/din.py").write_text(DIN_KIND)
    (chip / "reference/din.py").write_text(DIN_REFERENCE)
    (chip / "configs/din-small.json").write_text(json.dumps(
        {"name": "din-small", "reference": "din", "rows": 5000,
         "embed_dim": 18, "hist_len": 100, "limits": {}}))
    (chip / "traffic/din-zipf1.1-b256.json").write_text(json.dumps(
        {"kind": "din", "batch": 256, "zipf_a": 1.1}))
    m = copy.deepcopy(M)
    m["configs"].append({"name": "din-small",
                         "source": "https://arxiv.org/abs/1706.06978",
                         "file": "benchmarks/chip/configs/din-small.json",
                         "reduced": ["rows"], "why": "a test item"})
    m["workloads"].append({"name": "din-new", "config": "din-small",
                           "traffic": "din-zipf1.1-b256", "chips": 1,
                           "why": "a test item"})
    m["per_layer"][0]["workloads"].append("din-new")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.validate(m, root) == []
    cell = manifest.resolve(m, "din-new", root)
    assert cell.mix["kind"] == "din"
    feed = Feed(traffic.batches(cell.mix, cell.config, 11), stats=cell.model)
    try:
        batches = feed.take(2)
    finally:
        feed.close()
    assert set(batches[0]) == {"hist_ids", "target_id", "hist_mask", "label"}
    assert [set(d) for d in feed.distinct] == [{"item"}, {"item"}]
    for b, d in zip(batches, feed.distinct):
        ids = np.concatenate([b["hist_ids"].ravel(), b["target_id"]])
        assert d["item"] == np.unique(ids).size
        assert ids_per_batch(cell.model, b) == 256 * 101

    # a mix of a kind with no module is refused by name
    (chip / "traffic/din.py").unlink()
    errs = manifest.validate(m, root)
    assert any("din-new" in e and "no generator" in e for e in errs), errs
