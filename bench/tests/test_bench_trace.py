"""The trace reduction on a small hand-built trace whose numbers are
worked out by hand (a serialized XSpace, as the profiler writes it)."""
import pytest

from lib import trace as T

MS = 1_000_000_000  # picoseconds


def _plane(pid, name, lines):
    """Text proto of one XPlane; ``lines`` maps a line name to its
    (event name, start ms, end ms) events."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    out = f'planes {{ id: {pid} name: "{name}"'
    for lid, (line, evs) in enumerate(lines.items(), 1):
        out += f' lines {{ id: {lid} name: "{line}" timestamp_ns: 0'
        out += "".join(f" events {{ metadata_id: {names.index(n) + 1} offset_ps: {int(a * MS)}"
                       f" duration_ps: {int((b - a) * MS)} }}" for n, a, b in evs)
        out += " }"
    out += "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                   for i, n in enumerate(names, 1))
    return out + " }"


# times in ms: a 100 ms window; a join with one prefill, two steps with a
# decode each, a small op after the join, a wait for an arrival; and a
# device op after the window, which does not count
HOST = [("bench.window", 0, 100), ("bench.join", 5, 25), ("bench.step", 30, 40),
        ("bench.step", 45, 55), ("bench.await_arrival", 60, 100)]
MODULES = [("jit_prefill(11)", 10, 24), ("jit_argmax(12)", 24.5, 25), ("jit_decode(13)", 32, 39),
           ("jit_decode(13)", 47, 54), ("jit_decode(13)", 100, 110)]
OPS = [("fusion.1", 10, 17), ("fusion.2", 17, 24), ("reduce.4", 24.5, 25), ("dot.3", 32, 39),
       ("dot.3", 47, 54), ("dot.3", 100, 110)]


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData

    text = (_plane(1, "/host:CPU", {"python": HOST}) + " "
            + _plane(2, "/device:TPU:0", {"XLA Modules": MODULES, "XLA Ops": OPS}))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return T.reduce(str(path))


def test_busy_is_the_union_of_ops_inside_the_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.100)
    assert reduced["busy_s"] == pytest.approx(0.0285)


def test_device_time_per_module(reduced):
    assert reduced["modules"] == pytest.approx({"jit_prefill": 0.014, "jit_decode": 0.014,
                                                "jit_argmax": 0.0005})


def test_top_ops(reduced):
    assert [n for n, _ in reduced["top_ops"]] == ["dot.3", "fusion.1", "fusion.2", "reduce.4"]
    assert reduced["top_ops"][0][1] == pytest.approx(0.014)


def test_idle_is_attributed_to_what_the_host_did(reduced):
    # gaps: [0,10) join 5 + host 5; [24,24.5) join; [25,32) step 2 + host 5;
    # [39,47) step 1 + step 2 + host 5; [54,100) step 1 + host 5 + wait 40
    assert reduced["idle"] == pytest.approx({"await_arrival": 0.040, "host": 0.020,
                                             "step": 0.006, "join": 0.0055})
    assert sum(reduced["idle"].values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_breakdown_has_at_most_ten_entries_each(reduced):
    b = T.breakdown(reduced)
    assert b["device_ops"][0] == ["dot.3", pytest.approx(0.014)]
    assert b["idle_gaps"][0] == ["await_arrival", pytest.approx(0.040)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_unions_and_clips():
    assert T._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T._clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
    assert T._module_name("jit_decode(12345)") == "jit_decode"


def test_idle_gaps_split_by_the_span_that_covers_them():
    busy = [(1.0, 2.0), (3.0, 4.0)]
    spans = {"join": [(0.5, 1.5)], "step": [(2.5, 3.5)], "await_arrival": [(3.8, 6.0)]}
    idle = T._attribute_idle(busy, 0.0, 5.0, spans)
    # gaps: [0, 1) join 0.5 + host 0.5; [2, 3) step 0.5 + host 0.5; [4, 5) await 1.0
    assert idle == pytest.approx({"host": 1.0, "await_arrival": 1.0, "join": 0.5, "step": 0.5})


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_plane(1, "/host:CPU", {"python": HOST})))
    with pytest.raises(ValueError):
        T.reduce(str(path))
