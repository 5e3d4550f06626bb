"""The gap between a row's tokens, measured where the worker hands them
over (`GenerativeEngine._emit_step`): every read of a decode step is
counted by what its launch found queued in front of it on the device — a
prefill or import (admission), rows the host staged anew (rowset), or
nothing but the step (steady: its rows taken from the last step's outputs
or joined on the device) — in `GenerativeMetrics` always, and with tracing
on as args of `generate.emit`; a step carries its number from its launch
(`generate.decode_step`'s `step`) to its read (`read_step`); the pass that
admits runs under `generate.admission`; every program carries its model's
name."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spec_draft import noisy_draft  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference.serving import GenerativeEngine  # noqa: E402
from paddle_tpu.inference.serving.generate import (  # noqa: E402
    STEP_GAP_CAUSES, GenerativeMetrics, stack_gpt_params)
from paddle_tpu.models import brumby, lfm2  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402


def tiny_gpt():
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=64, dropout=0.0))
    model.eval()
    return model


@pytest.fixture(scope="module")
def tiny_model():
    return tiny_gpt()


def make_engine(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_context", 64)
    kw.setdefault("max_new_tokens_cap", 16)
    return GenerativeEngine(model, **kw)


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, size=n)


def gaps_of(snap):
    """{cause: (gaps, tokens)} and the seconds' sum, of a snapshot."""
    return ({c: (snap[f"step_gaps_{c}_total"],
                 snap[f"step_gap_tokens_{c}_total"])
             for c in STEP_GAP_CAUSES},
            sum(snap[f"step_gap_seconds_{c}_total"]
                for c in STEP_GAP_CAUSES))


def serve_queued(eng, asks):
    """Every request of `asks` ([(prompt length, max_new)]) queued BEFORE
    the worker starts, so what each pass admits is fixed -> the results."""
    handles = [eng.submit(prompt(n, i), new)
               for i, (n, new) in enumerate(asks)]
    eng.start()
    out = [h.result(120) for h in handles]
    assert [len(r["tokens"]) for r in out] == [new for _, new in asks]
    return out


# ---------------------------------------------------------- the counters --
def test_a_finish_with_no_admission_counts_rowset_and_a_first_read_nowhere(
        tiny_model):
    """Two rows from the start, the short one ends on its fourth token:
    s1 (the class's first read: no gap) | s2 s3 steady, two tokens each |
    s4 steady, the ended row's token launched in vain | restage (one row
    is bucket 1) | s5 rowset | s6-s9 steady. Nothing is admitted while a
    row decodes."""
    eng = make_engine(tiny_model, auto_start=False)
    try:
        serve_queued(eng, [(5, 4), (9, 10)])
        snap = eng.metrics.snapshot()
        by_cause, seconds = gaps_of(snap)
        assert by_cause == {"steady": (7, 9), "rowset": (1, 1),
                            "admission": (0, 0)}
        assert seconds > 0
        # the decode steps emitted 3 + 9 tokens; the first read's two
        # follow no read and count in no cause
        assert snap["tokens_out_total"] - snap["prefills_total"] == 12
        assert snap["step_rows_total"] == 12 and snap["steps_total"] == 9
        # the class now holds no row: the next request's first step read
        # follows nothing either, though a prefill ran before it
        assert len(eng.generate(prompt(4, 7), 3, timeout=120)["tokens"]) == 3
        by_cause, _ = gaps_of(eng.metrics.snapshot())
        assert by_cause == {"steady": (8, 10), "rowset": (1, 1),
                            "admission": (0, 0)}
    finally:
        eng.shutdown()


def test_an_admission_while_a_row_decodes_counts_admission(tiny_model):
    """Two slots, three requests (A 12 tokens, B 3, C 4): C is admitted
    when B ends, joins A on the device, and the step after it is launched
    ahead — the step that crosses C's prefill is the first one launched
    behind it, and only its read is the admission's:

        launch  s1 [A B] restaged | s2 | s3 (B's token in vain) |
                prefill C, join, s4 [A C] | s5 | s6 | s7 (C's in vain) |
                restage (one row: bucket 1), s8 [A] | s9 ...
        read    s1 first | s2 steady | s3 steady (A) | s4 admission (A, C:
                C's first token just before) | s5 s6 steady | s7 steady
                (A) | s8 rowset (A) | s9 ... steady"""
    eng = make_engine(tiny_model, auto_start=False)
    try:
        serve_queued(eng, [(6, 12), (3, 3), (8, 4)])
        snap = eng.metrics.snapshot()
        by_cause, _ = gaps_of(snap)
        assert by_cause["admission"] == (1, 2)
        assert by_cause["rowset"] == (1, 1)
        assert snap["rowset_joins_total"] == 1
        assert snap["restages_total"] == 2
        assert snap["prefills_total"] == 3
        # 11 + 2 + 3 tokens from decode steps, the first read's two aside
        assert sum(t for _, t in by_cause.values()) == 16 - 2
        assert sum(n for n, _ in by_cause.values()) == snap["steps_total"] - 1
    finally:
        eng.shutdown()


def test_snapshot_and_prometheus_expose_the_three_counters_by_cause():
    m = GenerativeMetrics()
    m.on_step(2, 2)                                   # a first read
    m.on_step(2, 2, cause="steady", gap_s=0.004, tokens=2)
    m.on_step(1, 1, cause="admission", gap_s=0.03, tokens=1)
    m.on_step(3, 4, cause="rowset", gap_s=0.006, tokens=3)
    m.on_step(3, 4, cause="steady", gap_s=0.004, tokens=3)
    snap = m.snapshot()
    assert [snap[f"step_gaps_{c}_total"] for c in STEP_GAP_CAUSES] == [2, 1, 1]
    assert [snap[f"step_gap_tokens_{c}_total"]
            for c in STEP_GAP_CAUSES] == [5, 3, 1]
    assert snap["step_gap_seconds_steady_total"] == pytest.approx(0.008)
    assert snap["step_gap_seconds_admission_total"] == pytest.approx(0.03)
    assert snap["steps_total"] == 5
    # flat scalars, so two snapshots subtract key by key and engines add
    assert all(isinstance(snap[f"step_gap{part}_{c}_total"], (int, float))
               for part in ("s", "_seconds", "_tokens")
               for c in STEP_GAP_CAUSES)
    text = m.prometheus_text()
    for c, n, tok in (("steady", 2, 5), ("rowset", 1, 3), ("admission", 1, 1)):
        assert f'paddle_generate_step_gaps_total{{cause="{c}"}} {n}' in text
        assert f'paddle_generate_step_gap_tokens_total{{cause="{c}"}} {tok}' \
            in text
        assert f'paddle_generate_step_gap_seconds_total{{cause="{c}"}} ' \
            in text


def test_a_draft_model_moves_the_same_counters(tiny_model):
    """`_spec_step` ends in the same `_emit_step`: a row's burst of tokens
    counts at its read; every speculative step stages its rows anew, so
    none is steady."""
    eng = make_engine(tiny_model, auto_start=False,
                      draft=noisy_draft(tiny_model), spec_tokens=3)
    try:
        serve_queued(eng, [(5, 12), (7, 3), (4, 9)])
        snap = eng.metrics.snapshot()
        by_cause, seconds = gaps_of(snap)
        assert by_cause["steady"] == (0, 0)
        assert by_cause["admission"][0] == 1 and by_cause["rowset"][0] >= 2
        assert sum(n for n, _ in by_cause.values()) == snap["steps_total"] - 1
        stepped = snap["tokens_out_total"] - snap["prefills_total"]
        assert stepped == 11 + 2 + 8
        # all of them but the first read's, at most a burst of 3 a row
        counted = sum(t for _, t in by_cause.values())
        assert stepped - 6 <= counted <= stepped - 2
        assert seconds > 0
    finally:
        eng.shutdown()


# -------------------------------------------------------------- the spans --
@pytest.fixture(scope="module")
def traced(tiny_model, tmp_path_factory):
    """Six requests through two slots, admitted while others decode,
    tracing on -> (spans, the engine's snapshot)."""
    paddle.set_flags({"FLAGS_trace_dir":
                      str(tmp_path_factory.mktemp("spans"))})
    trace.reset()
    eng = make_engine(tiny_model, auto_start=False)
    try:
        serve_queued(eng, [(6, 12), (3, 3), (8, 4), (5, 9), (12, 2), (4, 6)])
        snap = eng.metrics.snapshot()
        eng.shutdown()
        return trace.spans(), snap
    finally:
        eng.shutdown()
        paddle.set_flags({"FLAGS_trace_dir": ""})
        trace.reset()


def named(spans, name):
    return sorted((s for s in spans if s["name"] == name),
                  key=lambda s: s["ts"])


def test_every_read_step_was_launched_once_and_in_order(traced):
    spans, _ = traced
    launched = [s["args"]["step"] for s in named(spans, "generate.decode_step")]
    assert launched == sorted(set(launched)) and launched[0] >= 1
    emits = [s for s in named(spans, "generate.emit")]
    read = [s["args"]["read_step"] for s in emits]
    assert read == launched            # each once, in the launch's order
    waits = [s["args"]["read_step"]
             for s in named(spans, "generate.decode_step.wait")
             if "read_step" in s["args"]]
    assert waits == read
    # a step is read after it was launched, by a later pass's emit
    at = {s["args"]["step"]: s for s in named(spans, "generate.decode_step")}
    for e in emits:
        step = at[e["args"]["read_step"]]
        assert step["ts"] < e["ts"]


def test_emit_carries_what_the_counters_took(traced):
    spans, snap = traced
    emits = [s["args"] for s in named(spans, "generate.emit")
             if "rows" in s["args"]]
    first = [a for a in emits if "cause" not in a]
    assert len(first) == 1 and "gap_ms" not in first[0]
    by_cause, seconds = gaps_of(snap)
    for c in STEP_GAP_CAUSES:
        mine = [a for a in emits if a.get("cause") == c]
        assert (len(mine), sum(a["rows"] for a in mine)) == by_cause[c]
    assert sum(a["gap_ms"] for a in emits if "cause" in a) / 1e3 == \
        pytest.approx(seconds)
    assert by_cause["admission"][0] >= 3


def test_the_cause_is_what_the_launch_found_queued_before_it(traced):
    """The cause is the read step's, fixed at its launch: `admission`
    where a prefill was queued since the launch before it; `rowset` where
    the host staged its seven arrays anew; `steady` otherwise — a join
    (one plan staged) included."""
    spans, _ = traced
    emits = [s for s in named(spans, "generate.emit") if "rows" in s["args"]]
    prefills = [s["ts"] + s["dur"] for s in named(spans, "generate.prefill")]
    launched = {s["args"]["step"]: s
                for s in named(spans, "generate.decode_step")}
    assert {s["args"]["staged"] for s in launched.values()} == {0, 1, 7}
    for prev, e in zip(emits, emits[1:]):
        step = e["args"]["read_step"]
        since = launched[step - 1]["ts"]
        want = "admission" if any(
            since < t <= launched[step]["ts"] for t in prefills) else \
            "rowset" if launched[step]["args"]["staged"] == 7 else "steady"
        assert e["args"]["cause"] == want, e["args"]
        # the gap is the time between the two reads, on the engine's clock
        assert e["args"]["gap_ms"] == pytest.approx(
            (e["ts"] - prev["ts"]) / 1e3, abs=2.0)


def test_the_pass_that_admits_is_one_span(traced):
    spans, _ = traced
    admissions = named(spans, "generate.admission")
    prefills = named(spans, "generate.prefill")
    assert sum(a["args"]["admitted"] for a in admissions) == len(prefills) == 6
    assert sum(a["args"]["prompt_tokens"] for a in admissions) == \
        sum(p["args"]["prompt_tokens"] for p in prefills)

    def inside(child, parent):
        return parent["ts"] <= child["ts"] and \
            child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3

    for adm in admissions:
        it, rid = adm["args"]["iter"], adm["args"]["rid"]
        mine = [s for s in spans if s is not adm
                and s["args"].get("iter") == it
                and s["args"].get("rid") == rid
                and s["name"] != "generate.queue_wait"]
        names = [s["name"] for s in mine]
        # the admit that found the requests comes before it, the rest of
        # the pass — its prefills, the settle's reads, the first launch —
        # inside it
        assert names.count("generate.prefill") == adm["args"]["admitted"]
        assert names.count("generate.decode_step.launch") == 1
        for s in mine:
            if s["name"] == "generate.admit":
                assert s["ts"] + s["dur"] <= adm["ts"] + 1e-3
            else:
                assert inside(s, adm) and s["tid"] == adm["tid"], s["name"]
        # spans that name no parent of their own are its children: every
        # read of the settle, and the prefill's device wait is not one
        steps = {s["args"]["span"] for s in named(spans,
                                                  "generate.decode_step")}
        for s in mine:
            if s["name"] == "generate.emit" or (
                    s["name"] == "generate.decode_step.wait"
                    and s["args"].get("parent") not in steps):
                assert s["args"]["parent"] == adm["args"]["span"]
    # and a pass that admits nothing has no such bracket
    steady = {(s["args"]["rid"], s["args"]["iter"])
              for s in named(spans, "generate.decode_step.launch")}
    assert len(steady) > len(admissions)


def test_the_spans_nothing_read_are_gone(traced):
    spans, _ = traced
    assert not {s["name"] for s in spans} & {
        "generate.kv_export", "generate.kv_import", "generate.migrate"}


# -------------------------------------------------------------- the names --
def seeded_params(module, preset):
    paddle.seed(3)
    return module.init_params(module.PRESETS[preset]), module.PRESETS[preset]


@pytest.mark.parametrize("prefix, params", [
    ("gpt", lambda: stack_gpt_params(tiny_gpt())),
    ("lfm2", lambda: seeded_params(lfm2, "lfm2-tiny")),
    ("brumby", lambda: seeded_params(brumby, "brumby-tiny"))])
def test_every_models_programs_carry_its_name(prefix, params):
    """`jit_<prefix>_<family>_c<cap>_b<bucket>` in the lowered text: what
    the profiler's `XLA Modules` line shows and a trace's reader joins by."""
    eng = GenerativeEngine(params=params(), slots=2, warmup=False,
                           auto_start=False, max_context=64)
    try:
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        cap, b, S = eng._caps[-1], eng._batch_buckets[-1], 8
        p = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng._params)
        pool_k, pool_v, rec = eng._cache_avals(cap)
        i32, f32 = np.int32, np.float32
        decode = eng._program("decode", cap, b).lower(
            p, pool_k, pool_v, sds((b,), i32), sds((b,), i32),
            sds((b,), i32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
            sds((b, 2), np.uint32), rec).as_text()
        assert f"jit_{prefix}_decode_c{cap}_b{b}" in decode
        firsts = sds((eng._slots + 1, 3), i32)
        prefill = eng._program("prefill", cap, S).lower(
            p, pool_k, pool_v, sds((1, S), i32), sds((7,), i32), firsts,
            rec).as_text()
        assert f"jit_{prefix}_prefill_c{cap}_b{S}" in prefill
        assert "jit__unknown" not in decode + prefill
    finally:
        eng.shutdown(drain=False)


@pytest.mark.parametrize("prefix, params", [
    ("gpt", lambda: stack_gpt_params(tiny_gpt())),
    ("lfm2", lambda: seeded_params(lfm2, "lfm2-tiny")),
    ("brumby", lambda: seeded_params(brumby, "brumby-tiny"))])
def test_the_join_is_named_as_no_decode_or_prefill_program(prefix, params):
    """`jit_<prefix>_join_c<cap>_b<bucket>`: the three readers that pick
    programs by name — the decode and the prefill programs' device time
    (`engine.decode_program_ms_p50`, `engine.prefill_program_ms_p50`) and
    a routed model's decode layer-steps (benchmarks/harness/moe_work.py) —
    select by `_decode_` and `_prefill_`, and must not count the join."""
    eng = GenerativeEngine(params=params(), slots=2, warmup=False,
                           auto_start=False, max_context=64)
    try:
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        cap, b, i32 = eng._caps[-1], eng._batch_buckets[-1], np.int32
        join = eng._program("join", cap, b).lower(
            sds((b,), i32), sds((b,), i32), sds((b, 2), np.uint32),
            sds((eng._slots + 1, 3), i32), sds((b, 9), i32)).as_text()
    finally:
        eng.shutdown(drain=False)
    name = f"jit_{prefix}_join_c{cap}_b{b}"
    assert name in join and "jit__unknown" not in join
    assert "_decode_" not in name and "_prefill_" not in name
    assert "_decode_" not in join and "_prefill_" not in join
