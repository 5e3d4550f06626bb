"""harness/host_spans.py and the seven readers built on it, on a hand-made
profile whose gaps, spans, scopes and kernel events are known by
construction (microseconds on one clock):

    device 0, "XLA Ops"
      program A [0,40]   fusion.1 [0,10], flash_fwd.3 [10,20]   forward
                         flash_fwd.4 [20,25]                    recompute
                         flash_bwd_dq.5 [25,30], flash_bwd_dkv.6 [30,40]
                                                                backward
                         (flash_bwd_dq: a kernel of programs before PR 27,
                         here an operation like any other)
      idle [40,60]
      program B [60,100] while.9 [60,90] holding fusion.7 [65,85] optimizer;
                         copy.8 [90,100]; the while's own 10 and the copy: other
    host, thread 1: outer.span [35,50] holding inner.span [42,46];
                    step.span [-5,45]; a runtime event [0,100] with no ids
    host, thread 2: late.span [55,70]

The scopes come as they do on the chip: by instruction name from the
program's map (a v5e trace's events carry no op_name).
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks")]

from harness import cells, flops, host_spans  # noqa: E402
from harness import trace_reduce as tr  # noqa: E402

FWD = "jit(step)/jvp(train.loss)/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp(train.loss))/while/body/closed_call/checkpoint/"
OPS = {   # metadata id -> instruction text, as a v5e trace names events
    1: "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %transpose.2), "
       "kind=kOutput, calls=%fused_computation.1",
    3: "%flash_fwd.3 = (bf16[8,8]{1,0}, f32[8]{0}) custom-call(%q, %k, %v)",
    4: "%flash_fwd.4 = (bf16[8,8]{1,0}, f32[8]{0}) custom-call(%q, %k, %v)",
    5: "%flash_bwd_dq.5 = bf16[8,8]{1,0} custom-call(%q, %k, %v, %g)",
    6: "%flash_bwd_dkv.6 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) "
       "custom-call(%q, %k, %v, %g)",
    7: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %m)",
    8: "%copy.8 = bf16[8,8]{0,1} copy(bf16[8,8]{1,0} %x)",
    9: "%while.9 = (u32[], f32[8]{0}) while((u32[], f32[8]{0}) %t)",
    20: "jit_step(123)",
    30: "outer.span", 31: "inner.span", 32: "step.span", 33: "late.span",
    34: "PjitFunction(step)",
}
SCOPES = {"fusion.1": FWD + "dot_general",
          "flash_fwd.3": FWD + "flash_fwd/pallas_call",
          "flash_fwd.4": BWD + "rematted_computation/flash_fwd/pallas_call",
          "flash_bwd_dq.5": BWD + "flash_bwd_dq/pallas_call",
          "flash_bwd_dkv.6": BWD + "flash_bwd_dkv/pallas_call",
          "fusion.7": "jit(step)/train.optimizer/add"}
STATS = {2: "span", 3: "trace"}
DEVICE_OPS = [(1, 0, 10, ""), (3, 10, 10, ""), (4, 20, 5, ""),
              (5, 25, 5, ""), (6, 30, 10, ""), (9, 60, 30, ""),
              (7, 65, 20, ""), (8, 90, 10, "")]
IDS = "stats {{ metadata_id: 2 int64_value: {} }} " \
      "stats {{ metadata_id: 3 int64_value: 1 }}"
HOST_1 = [(32, -5, 50, IDS.format(5)), (30, 35, 15, IDS.format(6)),
          (31, 42, 4, IDS.format(7)), (34, 0, 100, "")]
HOST_2 = [(33, 55, 15, IDS.format(8))]
BASE_US = 10        # the lines' timestamp: events may start before 0


def _plane(pid, name, lines):
    def ev(mid, start_us, dur_us, stats):
        return (f"events {{ metadata_id: {mid} "
                f"offset_ps: {(start_us + BASE_US) * 10**6} "
                f"duration_ps: {dur_us * 10**6} {stats} }}\n")

    body = "".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 0\n'
        f'{"".join(ev(*e) for e in evs)} }}\n'
        for i, (ln, evs) in enumerate(lines, 1))
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: '
                   f'"{v}" }} }}\n' for k, v in OPS.items())
    meta += "".join(f'stat_metadata {{ key: {k} value {{ id: {k} name: '
                    f'"{v}" }} }}\n' for k, v in STATS.items())
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta} }}\n'


PROFILE = (
    _plane(1, "/device:TPU:0", [
        ("XLA Ops", DEVICE_OPS),
        ("XLA Modules", [(20, 0, 40, ""), (20, 60, 40, "")])])
    + _plane(2, "/host:CPU", [("python", HOST_1), ("python", HOST_2)]))


def us(x):
    return (x + BASE_US) * 1e3


@pytest.fixture(scope="module")
def view():
    from jax.profiler import ProfileData

    return host_spans.view_of(ProfileData.from_text_proto(PROFILE), SCOPES)


def test_program_spans_are_the_host_events_with_ids_by_thread(view):
    by = host_spans.spans_by(view)
    assert sorted(by) == [("inner.span", "python#0"),
                          ("late.span", "python#1"),
                          ("outer.span", "python#0"),
                          ("step.span", "python#0")]
    (s, e, args), = by[("outer.span", "python#0")]
    assert (s, e) == pytest.approx((us(35), us(50)))
    assert args == {"span": 6, "trace": 1}
    assert view["window"] == pytest.approx((us(0), us(100)))


def test_idle_gap_goes_to_the_innermost_span_that_covers_it(view):
    got = host_spans.idle_by_span(view)
    assert got["idle_s"] == pytest.approx(20e-6)
    # [40,60]: step.span to 45 but outer.span, opened later, from 40;
    # inner.span [42,46] inside it; nothing over [50,55]; late.span after
    assert got["by_span"] == pytest.approx({
        "outer.span": 6e-6, "inner.span": 4e-6, "late.span": 5e-6,
        "unattributed": 5e-6})
    assert "step.span" not in got["by_span"]
    assert got["attributed_share"] == pytest.approx(0.75)
    assert got["unattributed_between"] == pytest.approx({
        "after outer.span, before late.span": 5e-6})
    (gap,) = got["longest_gaps"]
    assert gap["seconds"] == pytest.approx(20e-6)
    assert gap["by_span"] == pytest.approx(got["by_span"])
    # between program A's last operation and program B's first
    assert (gap["after_op"], gap["before_op"]) == ("flash_bwd_dkv.6",
                                                   "while.9")


def test_split_by_span_without_spans_is_all_unattributed():
    gaps = [(0.0, 4.0), (6.0, 8.0)]
    assert host_spans.split_by_span(gaps, []) == (
        {"unattributed": 6.0}, gaps)
    spans = [("a", 3.0, 7.0), ("b", 20.0, 30.0)]
    got, left = host_spans.split_by_span(gaps, spans)
    assert got == {"a": 2.0, "unattributed": 4.0}
    assert left == [(0.0, 3.0), (7.0, 8.0)]
    assert host_spans.between(left, spans) == {
        "after the first span, before a": 3.0, "after a, before b": 1.0}


def test_the_clock_check_counts_programs_inside_one_span(view):
    assert host_spans.modules_inside(view, ("late.span", "step.span")) == {
        "events": 2, "inside": {"late.span": 0, "step.span": 1},
        "outside": 1}


@pytest.mark.parametrize("op_name, cls", [
    ("jit(step)/train.optimizer/transpose(x)/mul", "optimizer"),
    (BWD + "rematted_computation/dot_general", "recompute"),
    (BWD + "dot_general", "backward"),
    ("jit(step)/transpose(jvp(train.loss))/reduce_sum", "backward"),
    (FWD + "checkpoint/dot_general", "forward"),
    ("checkpoint/rematted_computation/reduce_sum", "other"),
    ("", "other"),
])
def test_scope_class_order(op_name, cls):
    assert host_spans.scope_class(op_name) == cls


def test_scopes_are_looked_up_by_instruction_name(view):
    # an operand called %transpose.2 is no scope; a name not in the map
    # has none
    got = {tr.short_name(r[0]): r[3] for r in view["ops"][0]}
    assert got["fusion.1"] == FWD + "dot_general"
    assert got["flash_fwd.4"] == SCOPES["flash_fwd.4"]
    assert got["copy.8"] == "" and got["while.9"] == ""


def test_self_time_by_scope_sums_to_one(view):
    got = host_spans.busy_by_scope(view)
    assert got["busy_s"] == pytest.approx(80e-6)
    assert got["seconds"] == pytest.approx({
        "forward": 20e-6, "recompute": 5e-6, "backward": 15e-6,
        "optimizer": 20e-6, "other": 20e-6})
    assert sum(got["share"].values()) == pytest.approx(1.0)
    assert got["share"]["recompute"] == pytest.approx(0.0625)


def test_no_train_scope_anywhere_is_none(view):
    bare = dict(view, ops={0: [(t, s, e, "", self_ns)
                               for t, s, e, _, self_ns in view["ops"][0]]})
    assert host_spans.busy_by_scope(bare) is None


def test_kernels_by_name_and_a_roofline_counted_per_call_and_pass(view):
    kernels = host_spans.kernel_events(view)
    # flash_bwd_dq.5 is an older program's second backward kernel: since
    # PR 27 one fused backward runs a pass, and only its name is a kernel's
    assert kernels == {
        "flash_fwd": {"events": 2, "seconds": pytest.approx(15e-6)},
        "flash_bwd_dkv": {"events": 1, "seconds": pytest.approx(10e-6)}}
    assert host_spans.kernel_of(OPS[5], SCOPES["flash_bwd_dq.5"]) is None
    roof = host_spans.flash_roofline(kernels, fwd_s=3e-6, bwd_s=6e-6)
    # two forward calls (one of them remat's) and one backward pass:
    # (2 x 3 + 6) / 25
    assert (roof["forward_calls"], roof["backward_passes"]) == (2, 1)
    assert roof["share"] == pytest.approx(0.48)
    assert host_spans.flash_roofline({}, 3e-6, 6e-6) is None
    assert host_spans.kernel_of("%flash_fwd_helper.1 = f32[] fusion()") \
        is None
    # under shard_map the instruction may be named after the transform
    shard = "%shard_map.360 = bf16[8,8]{1,0} custom-call(%q, %k, %v)"
    assert host_spans.kernel_of(shard) is None
    assert host_spans.kernel_of(
        shard, BWD + "shard_map/flash_bwd_dkv/pallas_call") \
        == "flash_bwd_dkv"
    assert host_spans.kernel_of(
        OPS[7], "jit(step)/flash_fwd/pallas_call") is None   # a fusion


# ---------------------------------------------------------- the readers --
NEW_READERS = [m["name"] for m in cells.load_benchmark(REPO)["per_layer"]
               if m["name"] in (
                   "engine.queue_wait_ms_p90", "engine.host_ms_per_step_p50",
                   "device.idle_attributed_share.serve",
                   "flash_attention.roofline_share",
                   "train_step.recompute_busy_share",
                   "train_step.backward_busy_share",
                   "train_step.optimizer_busy_share")]


def reader(name):
    return cells.load_reader(os.path.join(REPO, "benchmarks"), name)


@pytest.fixture
def profiled_run(tmp_path, monkeypatch):
    """A run dictionary as run.py hands it to the readers, its profile
    on disk where host_spans looks for it."""
    from jax.profiler import ProfileData

    prof = tmp_path / "plugins" / "profile" / "2026_01_01"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(PROFILE))
    monkeypatch.setattr(host_spans, "trace_dir", lambda run: str(tmp_path))
    monkeypatch.setattr(host_spans, "program_scopes", lambda: dict(SCOPES))
    monkeypatch.setattr(host_spans, "_LOADED", {})
    return {
        "trace": {"window_s": 1e-4}, "spans": [],
        "cell": {"name": "made-up.train", "chips": 4},
        "config": {"architecture": {"num_heads": 8, "head_size": 64},
                   "train": {"mesh": {"tp": 2}}},
        "traffic": {"batch": 4, "seq": 256},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    }, tmp_path


def test_seven_new_readers_are_in_the_benchmark():
    assert len(NEW_READERS) == 7


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_is_silent_without_a_trace_or_a_profile(name, tmp_path,
                                                       monkeypatch):
    assert reader(name)({"setup": {}, "spans": [], "trace": None}) is None
    # a trace but no profile on disk (and no span of the reader's name)
    monkeypatch.setattr(host_spans, "trace_dir", lambda run: str(tmp_path))
    assert reader(name)({"setup": {}, "spans": [], "cell": {"name": "x"},
                         "trace": {"window_s": 1.0}}) is None


def test_train_readers_on_the_profile(profiled_run, capsys):
    run, out_dir = profiled_run
    assert reader("train_step.recompute_busy_share")(run) \
        == pytest.approx(0.0625)
    assert reader("train_step.backward_busy_share")(run) \
        == pytest.approx(0.1875)
    assert reader("train_step.optimizer_busy_share")(run) \
        == pytest.approx(0.25)
    # the whole split is logged once, and written beside reduced.json
    assert capsys.readouterr().out.count('"busy_by_scope"') == 1
    assert (out_dir / "busy_by_scope.json").exists()
    # per shard: batch 4 / dp 2, heads 8 / tp 2
    shape = dict(batch=2, heads=4, seq_q=256, seq_k=256, head_dim=64,
                 causal=True, itemsize=2)
    least = 2 * flops.roofline_seconds(
        flops.flash_attention_fwd(**shape), run["peaks"]) \
        + flops.roofline_seconds(
            flops.flash_attention_bwd(**shape), run["peaks"])
    share = reader("flash_attention.roofline_share")(run)
    # over the two kernels' 25 us: flash_bwd_dq.5 is no kernel's name
    assert share == pytest.approx(least / 25e-6)
    assert 0 < share <= 1
    assert (out_dir / "flash_kernels.json").exists()


def test_idle_reader_on_the_profile(profiled_run, capsys):
    run, out_dir = profiled_run
    assert reader("device.idle_attributed_share.serve")(run) \
        == pytest.approx(0.75)
    assert '"programs_inside_spans"' in capsys.readouterr().out
    assert (out_dir / "idle_by_span.json").exists()


def test_breakdown_names_each_idle_gap_by_what_the_host_was_doing(
        profiled_run, view):
    """The one gap [40,60]: outer.span holds 6 of its 20 us (inner.span's
    4 are inner's), late.span 5, nothing covers [50,55]."""
    run, _ = profiled_run
    gaps = host_spans.longest_gaps_by_span(run)
    assert gaps == [["outer.span 30% + late.span 25%", pytest.approx(20e-6)]]
    from jax.profiler import ProfileData

    reduced = tr.reduce_profile(ProfileData.from_text_proto(PROFILE))
    assert tr.breakdown(reduced, gaps)["idle_gaps"] == gaps
    # the part of a gap that no span covers says where it lies
    assert host_spans.longest_gaps_by_span(run, top=3)[0][0] == (
        "outer.span 30% + late.span 25% + unattributed 25% (after "
        "flash_bwd_dkv.6, before while.9)")
    # a run whose profile holds no program span (the train cells) keeps
    # the operations on either side of the gap
    path = next(iter(host_spans._LOADED))
    host_spans._LOADED[path] = dict(view, spans=[])
    assert host_spans.longest_gaps_by_span(run) is None
    assert tr.breakdown(reduced, None)["idle_gaps"][0][0].startswith(
        "unattributed (after flash_bwd_dkv.6, before ")


def span(name, dur_ms, **args):
    return {"name": name, "ts": 0.0, "dur": dur_ms * 1e3, "args": args}


def test_decode_step_mfu_reader():
    """Real rows a step by the counters, the step's time by its spans."""
    run = {"spans": [span("generate.decode_step", ms) for ms in (70, 80, 90)]
           + [span("generate.prefill", 5)],
           "serve": {"snap0": {"steps_total": 10, "step_rows_total": 60},
                     "snap1": {"steps_total": 110, "step_rows_total": 810}},
           "config": {"architecture": {"n_params": 354871296}},
           "peaks": {"bf16_flops": 197e12}, "trace": None}
    got = reader("engine.decode_step.mfu")(run)
    assert got == pytest.approx(7.5 * 2 * 354871296 / 0.080 / 197e12)
    assert 0 < got < 1
    # nothing to read: no decode span, or no step counted
    assert reader("engine.decode_step.mfu")(dict(run, spans=[])) is None
    assert reader("engine.decode_step.mfu")(
        dict(run, serve={"snap0": run["serve"]["snap0"],
                         "snap1": run["serve"]["snap0"]})) is None
    assert reader("engine.decode_step.mfu")(
        {"spans": run["spans"], "trace": None}) is None


def test_span_readers():
    spans = [span("generate.queue_wait", ms, rid="r0") for ms in range(1, 12)]
    assert reader("engine.queue_wait_ms_p90")(
        {"spans": spans, "trace": None}) == pytest.approx(10.0)
    # two passes with a decode step (3+1+2+4 and 1+1+1+1 ms, the wait
    # left out), one pass that only admitted, one other replica's
    spans = [span("generate.admit", 3, rid="r0", iter=1),
             span("generate.decode_step.stage", 1, rid="r0", iter=1),
             span("generate.decode_step.launch", 2, rid="r0", iter=1),
             span("generate.decode_step.wait", 70, rid="r0", iter=1),
             span("generate.decode_step", 73, replica="r0"),
             span("generate.emit", 4, rid="r0", iter=1),
             span("generate.admit", 9, rid="r0", iter=2),
             span("generate.idle", 50, rid="r0", iter=2)] \
        + [span(n, 1, rid="r1", iter=1) for n in (
            "generate.admit", "generate.decode_step.stage",
            "generate.decode_step.launch", "generate.emit")]
    assert reader("engine.host_ms_per_step_p50")(
        {"spans": spans, "trace": None}) == pytest.approx(7.0)
