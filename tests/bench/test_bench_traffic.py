"""The traffic generator: each kind's module found by the mix's ``kind``,
the committed mixes' streams pinned bit for bit, and no kind or batch
field named by the harness itself."""

import ast
import hashlib
import json

import numpy as np
import pytest

from harness import BENCH_DIR, manifest, traffic

# sha256 of the first 8 batches (every field, by sorted key: name, dtype,
# shape, bytes) at the committed configuration's sizes, as the generator
# gave them before the kinds moved to modules of their own
PINNED = [
    ("ctr-zipf1.1-b1k", "baidu-ctr-1of512", 7,
     "bac95e5368d2e246fded8a91e26f8e4d5c991e8aaadb651044d8221784ca4b30"),
    ("ctr-zipf1.1-b1k", "baidu-ctr-1of512", 2**33 + 5,
     "a1bc2c24385bbed68f5977071d02c5eb82fe2375bc42f1a0f6535996a3d2c36f"),
    ("dlrm-zipf1.1-b2k", "dlrm-mlperf-1of32", 7,
     "d6d2a528bc53ef7286288206ff757b76600d426c9f5d9477851ecda9b7a1c971"),
    ("dlrm-zipf1.1-b2k", "dlrm-mlperf-1of32", 2**33 + 5,
     "d2b2aca5af47c5e93809cd856b62da4ac653902619fa7f893ae779e842979580"),
]


def stream_hash(stream, n=8) -> str:
    h = hashlib.sha256()
    for _ in range(n):
        b = next(stream)
        for k in sorted(b):
            x = np.ascontiguousarray(b[k])
            h.update(f"{k}:{x.dtype.str}:{x.shape}".encode())
            h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("mix, config, seed, want", PINNED,
                         ids=[f"{p[0]}-{p[2]}" for p in PINNED])
def test_committed_mixes_give_the_pinned_stream(mix, config, seed, want):
    cfg = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    m = traffic.load(manifest.traffic_file(mix))
    assert stream_hash(traffic.batches(m, cfg, seed)) == want


def test_a_kind_without_a_module_is_refused(tmp_path):
    mix = tmp_path / "odd.json"
    mix.write_text(json.dumps({"kind": "no_such_kind", "batch": 8}))
    with pytest.raises(ValueError, match="no generator"):
        traffic.load(mix)
    (tmp_path / "no_such_kind.py").write_text(
        "def batches(rng, mix, cfg):\n    while True:\n"
        "        yield {'x': rng.random(int(mix['batch']))}\n")
    got = traffic.load(mix)
    assert next(traffic.batches(got, {}, 0))["x"].shape == (8,)


# strings that would tie the harness to one kind or one batch layout
KIND_AND_FIELD_NAMES = {"ctr", "dlrm", "din", "ids", "sparse_ids",
                        "field_ids", "hist_ids", "target_id"}


@pytest.mark.parametrize("path", sorted((BENCH_DIR / "harness").glob("*.py")),
                         ids=lambda p: p.name)
def test_harness_names_no_kind_or_batch_field(path):
    named = [(n.lineno, n.value) for n in ast.walk(ast.parse(path.read_text()))
             if isinstance(n, ast.Constant) and n.value in KIND_AND_FIELD_NAMES]
    assert named == []
