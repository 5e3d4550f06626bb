"""The trace reduction on a hand-built trace whose busy union, gaps,
self times, classes and collective overlap are known by construction."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks")]

from harness import trace_reduce as tr  # noqa: E402

# as a v5e trace names them: the instruction's whole text
NAMES = {
    1: "%fusion.1 = bf16[64,1024]{1,0:T(8,128)(2,1)} fusion(bf16[64] %p)",
    2: "%while.2 = (u32[]{:T(128)}, bf16[8,8]{1,0}) while((u32[]) %t)",
    3: "%checkpoint.3 = (bf16[8,64]{1,0:T(8,128)(2,1)}, f32[8]{0}) "
       "custom-call(bf16[8,64]{1,0} %q), "
       "custom_call_target=\\\"tpu_custom_call\\\"",
    4: "%all-reduce.4 = f32[8]{0} all-reduce(f32[8]{0} %g), to_apply=%add",
    5: "%copy.5 = bf16[8,8]{0,1:T(8,128)(2,1)} copy(bf16[8,8]{1,0} %x)",
    6: "%loop_add_fusion.6 = f32[8]{0} fusion(f32[8]{0} %a)",
    7: "%all-gather-start.7 = (f32[4]{0}, f32[8]{0}) "
       "all-gather-start(f32[4]{0} %w), dimensions={0}",
}


def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}\n")


def _plane(pid, name, lines):
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: '
                   f'"{v}" }} }}\n' for k, v in NAMES.items())
    body = "".join(f'lines {{ id: {i} name: "{ln}" timestamp_ns: 1000\n'
                   f'{"".join(_ev(*e) for e in evs)} }}\n'
                   for i, (ln, evs) in enumerate(lines, 1))
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta} }}\n'


# device 0, microseconds:   0....10   20..........................70
#   fusion.1 [0,10]   while.2 [20,70] holding checkpoint.3 [25,35] and
#   fusion.1 [40,60];  all-reduce.4 [55,85] overlaps fusion.1 until 60 and
#   copy.5 [60,70];    idle: [10,20];  window [0,85]
DEV0 = [(1, 0, 10), (2, 20, 50), (3, 25, 10), (1, 40, 20), (4, 55, 30),
        (5, 60, 10)]
# device 1: one fusion over [0,40], then idle to the window's end at 85;
# an asynchronous all-gather runs [30,50] on the async line
DEV1 = [(6, 0, 40)]
DEV1_ASYNC = [(7, 30, 20)]


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", [("XLA Ops", DEV0),
                                        ("XLA Modules", [(1, 0, 85)])])
            + _plane(2, "/device:TPU:1", [("XLA Ops", DEV1),
                                          ("Async XLA Ops", DEV1_ASYNC)])
            + _plane(3, "/host:CPU", [("python", [(1, 0, 500)])]))
    return tr.reduce_profile(ProfileData.from_text_proto(text))


def test_window_is_first_op_to_last_op_over_devices(reduced):
    assert reduced["window_s"] == pytest.approx(85e-6)
    assert sorted(reduced["devices"]) == [0, 1]
    assert reduced["lines"][0] == {"XLA Ops": 6, "XLA Modules": 1}


def test_busy_union_counts_overlap_once(reduced):
    assert reduced["devices"][0]["busy_s"] == pytest.approx(75e-6)
    assert reduced["devices"][1]["busy_s"] == pytest.approx(40e-6)
    assert tr.mean_busy_s(reduced) == pytest.approx(57.5e-6)


def test_idle_gaps_longest_first_with_neighbours(reduced):
    (gap,) = reduced["devices"][0]["idle_gaps"]
    assert gap["seconds"] == pytest.approx(10e-6)
    assert gap["at_s"] == pytest.approx(10e-6)
    assert (gap["after_op"], gap["before_op"]) == ("fusion.1", "while.2")
    (tail,) = reduced["devices"][1]["idle_gaps"]
    assert tail["seconds"] == pytest.approx(45e-6)
    assert tail["before_op"] == "end"


def test_time_by_name_is_self_time(reduced):
    by_name = reduced["devices"][0]["by_name"]
    # the while holds 30 us of children in its 50 us
    assert by_name["while.2 (while)"] == pytest.approx(20e-6)
    assert by_name["fusion.1 (fusion)"] == pytest.approx(30e-6)
    # copy.5 lies inside the all-reduce's interval
    assert by_name["all-reduce.4 (all-reduce)"] == pytest.approx(20e-6)
    assert by_name["checkpoint.3 (custom-call)"] == pytest.approx(10e-6)
    assert list(by_name)[0] == "fusion.1 (fusion)"     # most time first


def test_time_by_class(reduced):
    by_class = reduced["devices"][0]["by_class"]
    assert by_class["custom-call"] == pytest.approx(10e-6)
    assert by_class["fusion"] == pytest.approx(30e-6)
    assert reduced["devices"][1]["by_class"] == {
        "fusion": pytest.approx(40e-6)}


def test_collective_time_and_its_exposed_part(reduced):
    dev = reduced["devices"][0]
    assert dev["collective_s"] == pytest.approx(30e-6)
    # [55,85] less the leaf compute under it, [40,60] and [60,70]
    assert dev["exposed_collective_s"] == pytest.approx(15e-6)
    # the async line's all-gather [30,50]: 10 us under the fusion, 10 not;
    # it is no part of the busy union or of the time by name
    dev1 = reduced["devices"][1]
    assert dev1["collective_s"] == pytest.approx(20e-6)
    assert dev1["exposed_collective_s"] == pytest.approx(10e-6)
    assert list(dev1["by_name"]) == ["loop_add_fusion.6 (fusion)"]


def test_breakdown_of_lowest_device(reduced):
    b = tr.breakdown(reduced)
    assert b["device_ops"][0] == ["fusion.1 (fusion)", pytest.approx(30e-6)]
    assert len(b["device_ops"]) == 5
    assert b["idle_gaps"] == [[
        "unattributed (after fusion.1, before while.2)",
        pytest.approx(10e-6)]]


def test_no_device_plane_gives_nothing():
    from jax.profiler import ProfileData

    text = _plane(1, "/host:CPU", [("python", [(1, 0, 5)])])
    assert tr.reduce_profile(ProfileData.from_text_proto(text)) is None


@pytest.mark.parametrize("name,cls", [
    (NAMES[3], "custom-call"), (NAMES[2], "while"), (NAMES[5], "copy"),
    (NAMES[7], "all-gather"), (NAMES[1], "fusion"),
    ("%slice-done.13 = bf16[256,1024]{1,0:T(8,128)(2,1)S(1)} async-done("
     "((bf16[1024,1024]{1,0}), bf16[256,1024]{1,0}, s32[]{:S(2)}) %s)",
     "async-done"),
    ("fusion.123", "fusion"), ("%fusion.1", "fusion"),
    ("loop_add_fusion.6", "fusion"), ("custom-call.4", "custom-call"),
    ("all-reduce.7", "all-reduce"), ("all-reduce-start.2", "all-reduce"),
    ("all-gather-done.1", "all-gather"), ("reduce-scatter.3",
                                          "reduce-scatter"),
    ("collective-permute-start.9", "collective-permute"),
    ("all-to-all.1", "all-to-all"), ("while.2", "while"),
    ("copy", "copy"), ("dynamic-update-slice.55", "dynamic-update-slice"),
])
def test_op_class(name, cls):
    assert tr.op_class(name) == cls


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [(0, 10)], []),
    ([(5, 6)], [(0, 1), (9, 12)], [(5, 6)]),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 6.5)]) == [
        (0, 4), (5, 7)]
    assert tr.measure([(0, 4), (5, 7)]) == 6


def test_recorded_cpu_trace_has_no_device_plane(tmp_path):
    """A real .xplane.pb, recorded here on the CPU: found, read, and —
    no TPU plane in it — reduced to nothing, not to zeros."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert tr.reduce_file(tr.find_xplane(str(tmp_path))) is None
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path / "nothing"))
