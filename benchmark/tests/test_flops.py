"""flops.py against values computed by hand for both configurations."""

import json
import os

import pytest

from benchmark.harness import flops
from benchmark.harness.common import BENCH_DIR
from benchmark.harness.peaks import PEAKS, peaks_for


def _cfg(name):
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_attended_pairs_by_enumeration():
    for seq, window in ((8, 3), (8, 0), (8, 8), (8, 20), (5, 1), (64, 16)):
        want = sum(
            1 for q in range(seq) for k in range(seq)
            if k <= q and (window <= 0 or k > q - window))
        assert flops.attended_pairs(seq, window) == want, (seq, window)


def test_attended_pairs_at_the_cells_shape():
    # 4096*4097/2 for the first window of queries, 4096 each for the rest
    assert flops.attended_pairs(8192, 4096) == 8_390_656 + 16_777_216
    assert flops.attended_pairs(8192, 0) == 8192 * 8193 // 2


def test_matmul_params_by_hand():
    mp = flops.matmul_params(_cfg("mistral7b-l2"))
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three 4096x14336
    assert mp["layer"] == (16_777_216 + 2 * 4_194_304 + 16_777_216
                           + 3 * 58_720_256) == 218_103_808
    assert mp["head"] == 131_072_000  # the embedding lookup is no matmul


@pytest.mark.parametrize("name,layers,total", [
    ("mistral7b-l2", 2, 3_705_692_160.0),
    ("mistral7b-l8", 8, 12_463_472_640.0),
])
def test_flops_per_token_by_hand(name, layers, total):
    got = flops.model_flops_per_token(_cfg(name), 8192)
    matmul = 6 * (layers * 218_103_808 + 131_072_000)
    # per layer: 12 * heads * head_dim * pairs / seq
    attn = layers * 12 * 32 * 128 * 25_167_872 / 8192
    assert got["matmul"] == matmul
    assert got["attention"] == attn
    assert got["total"] == total == matmul + attn


def test_causal_window_costs_less_than_the_full_square():
    cfg = _cfg("mistral7b-l2")
    full = dict(cfg, sliding_window=0)
    ours = flops.model_flops_per_token(cfg, 8192)["attention"]
    causal = flops.model_flops_per_token(full, 8192)["attention"]
    square = 2 * 12 * 32 * 128 * 8192 * 8192 / 8192  # bench.py's 12*L*S^2*H*D
    assert ours < causal < square
    assert ours / square == pytest.approx(0.375, abs=1e-3)


def test_flash_least_seconds_by_hand():
    peaks = peaks_for("TPU v5 lite")
    got = flops.flash_least_seconds(_cfg("mistral7b-l2"), 2, 8192, peaks)
    want_flops = 7 * 2 * 32 * 128 * 25_167_872 * 2
    q, kv = 2 * 2 * 8192 * 32 * 128, 2 * 2 * 8192 * 8 * 128
    want_bytes = 6 * q + 6 * kv
    assert got["flops"] == want_flops
    assert got["bytes"] == want_bytes
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(want_flops / 197e12)
    half = flops.flash_least_seconds(_cfg("mistral7b-l2"), 2, 8192, peaks,
                                     shards=2)
    assert half["seconds"] == pytest.approx(got["seconds"] / 2)


def test_unknown_device_is_an_error():
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("TPU v99")
