"""The program's spans, name scopes and counters: their reduction on a
hand-built trace whose numbers are worked out by hand, and the serving
engine's spans and counters under the profiler on the CPU."""
import gzip
import os

import pytest

from lib import program_trace as PT
from test_bench_trace import _plane

# times in ms, a 100 ms window.  Two decode steps, each inside a
# bench.step, with serve.step's four children and a device gap under
# each; in each, jit_decode runs a while container holding an attention
# and an mlp op, then the head and a convert in no scope.  Then a join
# whose prefill runs one attention op.  A decode after the window does
# not count.
HOST = [("bench.window", 0, 100),
        ("bench.step", 10, 40), ("serve.step", 10.5, 39.5), ("serve.step.inputs", 10.5, 12),
        ("serve.step.dispatch", 12, 14), ("serve.step.pick", 14, 38),
        ("serve.step.bookkeep", 38, 39),
        ("bench.step", 50, 80), ("serve.step", 50.5, 79.5), ("serve.step.inputs", 50.5, 52),
        ("serve.step.dispatch", 52, 54), ("serve.step.pick", 54, 78),
        ("serve.step.bookkeep", 78, 79.5),
        ("bench.join", 84, 96), ("serve.join", 85, 95), ("serve.join.alloc", 85, 86),
        ("serve.join.prefill", 86, 90), ("serve.join.merge", 90, 93), ("serve.join.pick", 93, 95),
        ("serve.step", 101, 110)]
MODULES = [("jit_decode(13)", 13, 35), ("jit_decode(13)", 53, 75), ("jit_prefill(14)", 87, 89),
           ("jit_decode(13)", 101, 110)]


def _step_ops(t):
    return [("while.1", t + 13, t + 30), ("fusion.1", t + 14, t + 20), ("fusion.2", t + 21, t + 28),
            ("dot.3", t + 30, t + 34), ("convert.4", t + 34, t + 35)]


OPS = _step_ops(0) + _step_ops(40) + [("fusion.9", 87, 89), ("dot.3", 101, 105)]
HLO = {"jit_decode": ['''
  %fusion.1 = f32[8,2048] fusion(%p), kind=kLoop, metadata={op_name="jit(decode)/while/body/attention/dot_general"}
  %fusion.2 = f32[8,2048] fusion(%q), kind=kLoop, metadata={op_name="jit(decode)/while/body/closed_call/mlp/mul"}
  %while.1 = (s32[], f32[8,2048]) while(%t), condition=%c, body=%b, metadata={op_name="jit(decode)/while"}
  ROOT %dot.3 = f32[8,151936] dot(%h, %e), metadata={op_name="jit(decode)/head/squeeze;head/dot_general"}
  %convert.4 = bf16[28,2048,6144] convert(%w)
'''], "jit_prefill": ['''
  %fusion.9 = f32[64,2048] fusion(%x), kind=kOutput, metadata={op_name="jit(prefill)/while/body/attention/dot_general"}
''']}


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    from jax.profiler import ProfileData

    text = (_plane(1, "/host:CPU", {"python": HOST}) + " "
            + _plane(2, "/device:TPU:0", {"XLA Modules": MODULES, "XLA Ops": OPS}))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return PT.reduce(str(path), HLO)


def test_device_self_time_per_scope(program):
    # per step: attention 6, mlp 7, head 4, other: the while's own 17 - 6 - 7
    # plus the convert's 1; the containers count once
    decode = program["scopes"]["jit_decode"]
    assert {s: v["self_s"] for s, v in decode.items()} == pytest.approx(
        {"attention": 0.012, "mlp": 0.014, "head": 0.008, "other": 0.010})
    assert decode["other"]["ops"] == pytest.approx({"while.1": 0.008, "convert.4": 0.002})
    assert sum(v["self_s"] for v in decode.values()) == pytest.approx(0.044)  # jit_decode's time
    assert program["scopes"]["jit_prefill"] == {
        "attention": {"self_s": pytest.approx(0.002), "ops": {"fusion.9": pytest.approx(0.002)}}}


def test_idle_goes_to_the_innermost_program_span(program):
    # gaps: [0,13) host 10.5, inputs 1.5, dispatch 1; [35,53) step pick 3,
    # bookkeep 1, serve.step 0.5, host 11, inputs 1.5, dispatch 1; [75,87)
    # step pick 3, bookkeep 1.5, host 5.5, alloc 1, prefill 1; [89,100)
    # prefill 1, merge 3, join pick 2, host 5
    assert program["idle"] == pytest.approx({
        "host": 0.032, "serve.step.pick": 0.006, "serve.step.inputs": 0.003,
        "serve.join.merge": 0.003, "serve.step.bookkeep": 0.0025, "serve.step.dispatch": 0.002,
        "serve.join.prefill": 0.002, "serve.join.pick": 0.002, "serve.join.alloc": 0.001,
        "serve.step": 0.0005})
    assert sum(program["idle"].values()) == pytest.approx(0.100 - 0.046)


def test_steps_and_joins_are_the_program_spans_in_the_window(program):
    assert program["steps"] == 2 and program["joins"] == 1
    assert program["window_s"] == pytest.approx(0.100)


def test_metrics_per_step(program):
    record = {"loop": "closed", "program": program}
    got = {name: read(record) for name, read in PT.METRICS.items()}
    assert got == pytest.approx({"decode_attention_ms.closed": 6.0, "decode_mlp_ms.closed": 7.0,
                                 "decode_head_ms.closed": 4.0, "step_idle_ms.closed": 7.0})
    assert all(read({"loop": "open", "program": program}) is None for read in PT.METRICS.values())


def test_a_program_without_spans_or_scopes_reads_nothing(program):
    bare = dict(program, steps=0)
    assert all(read({"loop": "closed", "program": bare}) is None for read in PT.METRICS.values())
    assert all(read({"loop": "closed"}) is None for read in PT.METRICS.values())
    unscoped = dict(program, scopes={"jit_decode": {"other": program["scopes"]["jit_decode"]["other"]}})
    assert PT.scope_ms({"loop": "closed", "program": unscoped}, "jit_decode", "attention",
                       "closed") is None


def test_counts_line(program):
    counters = {"steps": 4, "slot_steps": 12, "valid_positions": 3000, "max_batch": 8,
                "capacity_positions": 8 * 1000, "waits_s": [0.002, 0.001, 0.010]}
    c = PT.counts(program, counters)
    assert list(c["decode_scope_ms"]) == ["mlp", "attention", "other", "head"]
    assert c["decode_scope_ms"]["other"] == {"ms": pytest.approx(5.0), "top": [
        ["while.1", pytest.approx(4.0)], ["convert.4", pytest.approx(1.0)]]}
    assert c["prefill_scope_ms"] == pytest.approx({"attention": 2.0})
    assert c["idle_by_program_span_s"] is program["idle"]
    assert c["occupancy"] == pytest.approx(12 / 32)
    assert c["cache_valid_share"] == pytest.approx(3000 / (4 * 8000))
    assert c["queue_wait_ms"] == pytest.approx({"p50": 2.0, "max": 10.0, "n": 3})
    assert PT.counts(None, None) == {}


def test_scope_of_a_path():
    assert PT.scope_of("jit(decode)/while/body/attention/dot_general") == "attention"
    assert PT.scope_of("jit(decode)/head/squeeze;head/dot_general") == "head"
    assert PT.scope_of("jit(decode)/while/body/dynamic_update_slice") == "other"
    assert PT.scope_map(["%a.1 = f32[] add(), metadata={op_name=\"mlp/add\"}",
                         "%a.1 = f32[] add(), metadata={op_name=\"attention/add\"}"]) == {"a.1": "other"}


def test_self_times_subtract_nested_events():
    got = PT.self_times([(0.0, 10.0, "while"), (1.0, 3.0, "a"), (2.0, 2.5, "inner"),
                         (4.0, 6.0, "b"), (11.0, 12.0, "c")])
    assert {n: s for n, _, _, s in got} == pytest.approx(
        {"while": 6.0, "a": 1.5, "inner": 0.5, "b": 2.0, "c": 1.0})


# ---------------------------------------------------------------------------
# the engine under the profiler, on the CPU
# ---------------------------------------------------------------------------

PROMPTS = (64, 128, 256)
STEPS = 5


@pytest.fixture(scope="module")
def engine_trace():
    from jax.profiler import ProfileData

    from lib.harness import Session
    from lib.registry import load_cell
    from lib.traffic import Request

    sess = Session(load_cell("qwen3-1.7b.decode-batch"), 2**31 + 5, smoke=True)
    sess.build()
    sess.warm_up()
    e = sess.engine
    tracer = PT.ProgramTracer(e)
    tracer.start()
    for i, s in enumerate(PROMPTS):
        e.queue.offer(Request(100 + i, s, 64, i))
    while len(e.queue):
        e._join(e.queue.take())
    for _ in range(STEPS):
        e._decode_step()
    tracer.stop()
    try:
        data = ProfileData.from_file(tracer.path())
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                 for plane in data.planes for line in plane.lines for ev in line.events
                 if ev.name.startswith("serve.")]
    finally:
        tracer.close()
    return spans, tracer.counters, e


def _parent(spans, child):
    name, a, b, _ = child
    return [s for s in spans if s is not child and s[1] <= a and b <= s[2]
            and name.startswith(s[0] + ".")]


def test_engine_spans_nest_in_their_parent_and_carry_the_request(engine_trace):
    spans, _, _ = engine_trace
    joins = [s for s in spans if s[0] == "serve.join"]
    assert [(int(s[3]["request_id"]), int(s[3]["prompt_len"])) for s in joins] == [
        (100 + i, p) for i, p in enumerate(PROMPTS)]
    for child in ("alloc", "prefill", "merge", "pick"):
        got = [s for s in spans if s[0] == f"serve.join.{child}"]
        assert len(got) == len(PROMPTS)
        for s in got:
            (parent,) = _parent(spans, s)
            assert parent[3]["request_id"] == s[3]["request_id"]
    steps = [s for s in spans if s[0] == "serve.step"]
    assert [int(s[3]["active"]) for s in steps] == [len(PROMPTS)] * STEPS
    assert len({s[3]["step"] for s in steps}) == STEPS
    for child in ("inputs", "dispatch", "pick", "bookkeep"):
        got = [s for s in spans if s[0] == f"serve.step.{child}"]
        assert len(got) == STEPS and all(len(_parent(spans, s)) == 1 for s in got)


def test_engine_counters_over_the_window(engine_trace):
    _, counters, e = engine_trace
    n = len(PROMPTS)
    assert counters["steps"] == STEPS and counters["slot_steps"] == n * STEPS
    # a slot at depth p has p + 1 valid positions when it decodes; one more each step
    assert counters["valid_positions"] == sum(p + k + 1 for p in PROMPTS for k in range(STEPS))
    assert counters["capacity_positions"] == e.max_batch * e.max_context
    assert len(counters["waits_s"]) == n and all(w >= 0 for w in counters["waits_s"])
    c = PT.counts(None, counters)
    assert c["occupancy"] == pytest.approx(n / e.max_batch)
    assert c["queue_wait_ms"]["n"] == n


def test_trace_program_reads_both_reductions_of_one_window(program, monkeypatch):
    """``bench/trace_program.py`` end to end at the smoke size, with the
    two reductions (which need a TPU's trace) replaced by the hand-built
    trace's: it reads the existing metrics and the four new ones from
    one record, and cross-checks the scopes and the step idle."""
    import trace_program as TP

    from lib import peaks as P
    from lib import trace as T
    from lib.compiles import CompileCounter
    from lib.registry import load_cell

    reduced = {"window_s": 0.1, "busy_s": 0.046, "devices": 1, "top_ops": [],
               "modules": {"jit_decode": 0.044, "jit_prefill": 0.002},
               "idle": {"host": 0.038, "step": 0.016}}
    monkeypatch.setattr(T, "reduce", lambda path: reduced)
    monkeypatch.setattr(PT, "reduce", lambda path, hlo: program)
    monkeypatch.setattr(P, "peaks", lambda kind: P.PEAKS["TPU v5 lite"])
    info, metrics, cross = TP.trace_program(load_cell("qwen3-1.7b.decode-batch"), 2**31 + 9,
                                            1.0, smoke=True, counter=CompileCounter())
    assert info["hlo_compiles"] == 0
    assert info["occupancy"] > 0 and info["queue_wait_ms"]["n"] > 0
    assert set(info["decode_scope_ms"]) == {"attention", "mlp", "head", "other"}
    assert metrics["decode_mlp_ms.closed"] == pytest.approx(7.0)
    assert metrics["step_idle_ms.closed"] == pytest.approx(7.0)
    assert metrics["step_ms.closed"] > 0 and metrics["decode_roofline.closed"] > 0
    assert cross["decode_scopes_over_module"] == pytest.approx(1.0)
    assert cross["decode_other_share"] == pytest.approx(10 / 44)
    assert cross["step_idle_over_bench_step_idle"] == pytest.approx(0.014 / 0.016)
    assert cross["decode_other_top_ms"] == [["while.1", pytest.approx(4.0)],
                                            ["convert.4", pytest.approx(1.0)]]


# ---------------------------------------------------------------------------
# a recorded TPU trace (bench/tests/record_program_trace_fixture.py)
# ---------------------------------------------------------------------------

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "program_trace")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("tpu") / "engine.xplane.pb"
    with gzip.open(os.path.join(FIXTURE, "engine.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(FIXTURE, "engine.jit_decode.hlo.txt.gz"), "rt") as f:
        hlo = {"jit_decode": [f.read()]}
    return str(path), PT.reduce(str(path), hlo)


def test_recorded_tpu_trace_puts_every_decode_op_in_a_scope(recorded):
    from lib import trace as T

    path, program = recorded
    decode = program["scopes"]["jit_decode"]
    assert {"attention", "mlp", "head"} <= set(decode) <= PT.SCOPES | {PT.OTHER}
    assert program["steps"] == 6
    module_s = T.reduce(path)["modules"]["jit_decode"]
    assert sum(v["self_s"] for v in decode.values()) == pytest.approx(module_s, rel=0.02)


def test_recorded_tpu_trace_has_no_negative_self_time(recorded):
    from jax.profiler import ProfileData

    from lib import trace as T

    path, _ = recorded
    for plane in ProfileData.from_file(path).planes:
        if T.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                    assert ops and min(s for *_, s in PT.self_times(ops)) >= 0
