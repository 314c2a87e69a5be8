"""Work counts of the configuration against numbers worked out by hand
from the published shapes."""
import json
import os

import pytest

from lib.peaks import peaks
from lib.registry import BENCH, load_module

V5E = peaks("TPU v5 lite")


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        c = json.load(f)
    return c, load_module(os.path.join(BENCH, "configs", f"{name}.py"), f"work_{name[:4]}")


def test_qwen3_counts_two_n_per_token_with_the_tied_head_once():
    c, w = _config("qwen3-1.7b")
    # per layer: q 2048x2048, k and v 2048x1024 each, o 2048x2048,
    # SwiGLU 3 x 2048x6144 = 50,331,648; 28 layers; head 151936x2048
    n = 28 * 50_331_648 + 151_936 * 2048
    assert w.matmul_params(c) == n == 1_720_451_072
    # one token of prefill: 2N plus its one (query, key) pair per layer
    assert w.prefill(c, 1).flops == 2 * n + 28 * 4 * 16 * 128
    # norms: 28 x (2 x 2048 + 2 x 128) + 2048, all f32
    assert w.weight_bytes(c) == (n + 123_904) * 4 == c["memory"]["weights_bytes"]
    # K/V: 28 layers x 2 x 8 heads x 128 x 4 bytes = 224 KiB per token
    assert c["memory"]["kv_bytes_per_token"] == 229_376
    step = w.decode(c, [10, 20])
    assert step.bytes == w.weight_bytes(c) + 30 * 229_376
    assert step.flops == 2 * 2 * n + 28 * 4 * 16 * 128 * 30
    assert step.bound(V5E) == "memory"


def test_a_99_token_prefill_is_memory_bound():
    c, w = _config("qwen3-1.7b")
    p = w.prefill(c, 99)
    assert p.bound(V5E) == "memory"
    assert p.least_s(V5E) == pytest.approx(p.bytes / 819e9)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks("cpu")
