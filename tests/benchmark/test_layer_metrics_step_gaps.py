"""The six readers of what PR 37 put into the serving engine — the gap
between two step reads with its cause (args of `generate.emit`, counters of
`GenerativeMetrics`) and the programs' names on the device's `XLA Modules`
line — each on a synthetic run: the value where the data is there, None
and no exception where it is not (the parent's program)."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH]

from harness import cells, host_spans  # noqa: E402

SPAN_READERS = ("engine.itl_ms_p95", "engine.pass_cadence_ms_p50",
                "engine.admission_gap_ms_p50")
PROGRAM_READERS = ("engine.decode_program_ms_p50",
                   "engine.prefill_program_ms_p50")
NEW = SPAN_READERS + PROGRAM_READERS + ("engine.admission_gap_token_share",)
CAUSES = ("steady", "rowset", "admission")


def reader(name):
    return cells.load_reader(BENCH, name)


def emit(gap_ms=None, cause=None, rows=None, **args):
    if gap_ms is not None:
        args.update(gap_ms=gap_ms, cause=cause, rows=rows)
    return {"name": "generate.emit", "ts": 0.0, "dur": 500.0,
            "args": {"iter": 1, "rid": 0, **args}}


def snap(steady, rowset, admission, **rest):
    return {"steps_total": steady + rowset + admission + 1,
            **{f"step_gap_tokens_{c}_total": n
               for c, n in zip(CAUSES, (steady, rowset, admission))}, **rest}


def view(modules):
    return {"ops": {0: [("%fusion.1", 0.0, 1.0, "", 1.0)],
                    1: [("%fusion.1", 0.0, 1.0, "", 1.0)]},
            "modules": {0: modules, 1: [("jit_gpt_decode_c1024_b8(3)",
                                         0.0, 9e9)]},
            "spans": [], "window": (0.0, 1.0)}


# ---------------------------------------------------------- by the spans --
def test_span_readers_on_emits_that_carry_a_gap():
    # 90 steady reads of 4 ms to 8 rows, 4 rowset reads of 7 ms to 7 rows,
    # 6 admissions of 30 … 35 ms to ONE row; one first read and one
    # old-style emit carry no gap
    spans = [emit(4.0, "steady", 8) for _ in range(90)] \
        + [emit(7.0, "rowset", 7) for _ in range(4)] \
        + [emit(30.0 + i, "admission", 1) for i in range(6)] \
        + [emit(rows=8), emit(),
           {"name": "generate.decode_step", "ts": 0.0, "dur": 2000.0,
            "args": {"step": 4, "rows": 8}}]
    run = {"spans": spans, "trace": None}
    assert reader("engine.pass_cadence_ms_p50")(run) == pytest.approx(4.0)
    assert reader("engine.admission_gap_ms_p50")(run) == pytest.approx(32.5)
    # by tokens: 720 at 4 ms, 28 at 7, 6 over 30 — the 95th of 754 is a
    # steady one. By reads (100 of them) it would be 30.05: one row's wait
    # is not eight rows'
    assert reader("engine.itl_ms_p95")(run) == pytest.approx(4.0)
    # the admissions emit to eight rows each: 48 of 796 tokens, over a
    # twentieth, and the p95 gap is an admission (the 756th and a quarter)
    heavy = dict(run, spans=[dict(s, args=dict(s["args"], rows=8))
                             if s["args"].get("cause") == "admission" else s
                             for s in spans])
    assert reader("engine.itl_ms_p95")(heavy) == pytest.approx(30.25)


def test_a_window_without_admissions_reads_no_admission_gap():
    run = {"spans": [emit(4.0, "steady", 8) for _ in range(5)],
           "trace": None}
    assert reader("engine.admission_gap_ms_p50")(run) is None
    assert reader("engine.pass_cadence_ms_p50")(run) == pytest.approx(4.0)
    assert reader("engine.itl_ms_p95")(run) == pytest.approx(4.0)


# -------------------------------------------------------- by the counters --
def test_admission_share_is_the_windows_tokens_not_the_lifetimes():
    run = {"serve": {"snap0": snap(1000, 50, 500),
                     "snap1": snap(1000 + 900, 50 + 40, 500 + 60)},
           "spans": [], "trace": None}
    assert reader("engine.admission_gap_token_share")(run) == \
        pytest.approx(60 / 1000)
    nothing = dict(run, serve={"snap0": run["serve"]["snap1"],
                               "snap1": run["serve"]["snap1"]})
    assert reader("engine.admission_gap_token_share")(nothing) is None


# ------------------------------------------------------- by the programs --
MODULES = [("jit_gpt_decode_c1024_b8(3)", 0.0, 4.0e6),
           ("jit_gpt_decode_c1024_b8(3)", 5.0e6, 9.2e6),
           ("jit_gpt_decode_c1024_b4(7)", 10.0e6, 13.0e6),
           ("jit_lfm2_prefill_c1024_b128(1)", 20.0e6, 42.0e6),
           ("jit_gpt_dprefill_c1024_b64(9)", 50.0e6, 51.0e6),
           ("jit__unknown(4)", 60.0e6, 99.0e6)]


def test_program_readers_join_by_name_on_the_lowest_device(monkeypatch):
    monkeypatch.setattr(host_spans, "load", lambda run: view(MODULES))
    run = {"trace": {}, "spans": []}
    assert reader("engine.decode_program_ms_p50")(run) == pytest.approx(4.0)
    # one event: the draft's `dprefill` and the unnamed one are not it
    assert reader("engine.prefill_program_ms_p50")(run) == pytest.approx(22.0)


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_program_readers_find_nothing_among_unnamed_programs(monkeypatch,
                                                             name):
    monkeypatch.setattr(host_spans, "load", lambda run: view(
        [("jit__unknown(4)", 0.0, 4e6), ("jit__unknown(5)", 5e6, 9e6)]))
    assert reader(name)({"trace": {}, "spans": []}) is None
    monkeypatch.setattr(host_spans, "load", lambda run: view([]))
    assert reader(name)({"trace": {}, "spans": []}) is None


# ------------------------------------------------------------ the parent --
@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_empty_run_or_the_parents(name):
    read = reader(name)
    assert read({"setup": {"build_s": 1.0, "cache_misses": 0}, "spans": [],
                 "trace": None}) is None
    # the parent's program: emits with `iter` and `rid` alone, steps with no
    # number, snapshots without the counters, no profile on disk
    parent = {"serve": {"snap0": {"steps_total": 0, "step_rows_total": 0},
                        "snap1": {"steps_total": 9, "step_rows_total": 70}},
              "spans": [emit() for _ in range(9)]
              + [{"name": "generate.decode_step", "ts": 0.0, "dur": 2e3,
                  "args": {"rows": 8, "ahead": 1}}],
              "trace": None, "cell": {"name": "gpt3-medium.serve-decode"}}
    assert read(parent) is None


def test_the_benchmark_lists_the_six_with_their_cells():
    bench = cells.load_benchmark(REPO)
    serve = [w["name"] for w in bench["workloads"] if "serve" in w["traffic"]]
    assert len(serve) == 4
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(entries)
    reports = {e["name"]: set(e.get("workloads", serve))
               for e in bench["end_to_end"]}
    for name in NEW:
        m = entries[name]
        assert m["layer"] == "serving engine"
        assert set(m["workloads"]) <= reports[m["moves"]]
    assert entries["engine.itl_ms_p95"]["workloads"] == serve
    assert "gpt3-medium.serve-longprompt" not in \
        entries["engine.pass_cadence_ms_p50"]["workloads"]
    assert "gpt3-medium.serve-decode" not in \
        entries["engine.prefill_program_ms_p50"]["workloads"]
