"""Each per-layer metric reader on a hand-built record."""
import json
import os

import pytest

from lib.harness import Span, WindowRecord, end_to_end, layer_record
from lib.registry import BENCH, ROOT, load_module

PEAKS = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}


def _record(loop, trace=True):
    return {
        "loop": loop,
        "window_s": 10.0,
        "spans": {"join": {"count": 4, "total_s": 0.2}, "step": {"count": 100, "total_s": 2.0}},
        "work": {"prefill": {"calls": 4, "flops": 400.0, "bytes": 40.0, "least_s": 0.05, "bound": "memory"},
                 "decode": {"calls": 100, "flops": 600.0, "bytes": 90.0, "least_s": 1.0, "bound": "memory"}},
        "peaks": PEAKS,
        "trace": {"modules": {"jit_prefill": 0.1, "jit_decode": 1.6}} if trace else None,
    }


# metric -> (value in the open-loop record, value in the closed-loop record)
EXPECTED = {
    "join_ms.closed": (None, 50.0),
    "step_ms.closed": (None, 20.0),
    "prefill_roofline.closed": (None, 50.0),
    "decode_roofline.closed": (None, 62.5),
    "mfu.serve": (None, 100.0),          # 1000 FLOPs / (10 s x 100 FLOP/s) = 1 -> 100%
}


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"), f"reader_{name}")


def test_every_per_layer_metric_has_a_reader_and_an_expectation():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert names == set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_built_record(name):
    read = _reader(name).read
    for loop, want in zip(("open", "closed"), EXPECTED[name]):
        got = read(_record(loop))
        assert got == pytest.approx(want) if want is not None else got is None


@pytest.mark.parametrize("name", [n for n in EXPECTED if "roofline" in n])
def test_a_roofline_without_a_trace_reads_nothing(name):
    for loop in ("open", "closed"):
        assert _reader(name).read(_record(loop, trace=False)) is None


def _window():
    spans = [Span("join", 0.0, 0.5, 32), Span("step", 0.5, 0.6, [33]),
             Span("join", 0.6, 1.0, 64), Span("step", 1.0, 1.2, [34, 65])]
    return WindowRecord(loop="open", seconds=2.0, requests={}, due={0: 0.0, 1: 0.4, 2: 1.9},
                        tokens={0: [0.5, 0.6, 1.2, 2.5], 1: [1.0, 1.2]}, shed=[2], spans=spans,
                        max_offer_lag_s=0.2, window_compiles=0, drain_compiles=0, drain_s=0.5)


def test_end_to_end_on_a_hand_built_window():
    m = end_to_end(_window())
    # first tokens: 0.5 - 0.0, 1.0 - 0.4; the shed request counts as
    # missing, timed to the end of the drain: 2.5 - 1.9
    assert m["ttft_p90_ms"] == pytest.approx(600.0)
    # gaps in the window: 0.1, 0.6 (request 0), 0.2 (request 1)
    assert m["itl_p95_ms"] == pytest.approx(600.0)
    assert m["tokens_per_s"] == pytest.approx(5 / 2.0)


def test_layer_record_sums_least_work_per_call():
    class Work:
        def __init__(self, flops, nbytes):
            self.flops, self.bytes = flops, nbytes

        def least_s(self, peaks):
            return max(self.flops / peaks["flops_bf16"], self.bytes / peaks["hbm_bytes_per_s"])

        def bound(self, peaks):
            return "memory"

    class Mod:
        @staticmethod
        def prefill(dims, n):
            return Work(10.0 * n, 1.0)

        @staticmethod
        def decode(dims, contexts):
            return Work(1.0, 5.0 * len(contexts))

    rec = layer_record(_window(), Mod, {}, PEAKS, None)
    assert rec["spans"]["join"] == {"count": 2, "total_s": pytest.approx(0.9)}
    assert rec["work"]["prefill"]["least_s"] == pytest.approx((320 + 640) / 100.0)
    assert rec["work"]["decode"]["least_s"] == pytest.approx((5 + 10) / 10.0)
    assert rec["work"]["decode"]["calls"] == 2 and rec["window_s"] == 2.0
