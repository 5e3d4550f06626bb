"""One clock and real names: the tracer's spans on the profiler's timeline
(under the benchmark's own profiler options), the generation worker's
time as a partition of spans, and the names the program sets for what
runs on the device — the train step's scopes, the flash kernels."""
import glob
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(REPO, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference.serving import GenerativeEngine  # noqa: E402
from paddle_tpu.inference.serving import generate as gen_mod  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402

WORKER_SPANS = ("generate.admit", "generate.prefill", "generate.decode_step",
                "generate.decode_step.stage", "generate.decode_step.launch",
                "generate.decode_step.wait", "generate.emit",
                "generate.idle")


@pytest.fixture()
def tracing(tmp_path):
    paddle.set_flags({"FLAGS_trace_dir": str(tmp_path / "spans")})
    trace.reset()
    yield
    paddle.set_flags({"FLAGS_trace_dir": ""})
    trace.reset()


def host_events(profile_dir):
    """{name: [(line index, start_ns, end_ns, stats)]} of /host:CPU."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (i, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)))
    return out


# ------------------------------------------------- (a) the shared clock --
def test_span_is_an_event_of_the_profilers_host_plane(tracing, tmp_path):
    """Under benchmarks/harness/common.start_profile's exact options
    (host tracer 1, Python tracer off) a span shows in the .xplane.pb
    under its own name, with its ids and scalar args, nested as opened."""
    from harness import common

    common.start_profile(str(tmp_path / "prof"))
    try:
        with trace.span("clock.outer", "test",
                        {"iter": 3, "rid": "r0", "rows": [1, 2]}) as sp:
            with trace.span("clock.inner", "test"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    got = host_events(str(tmp_path / "prof"))
    (line_o, s_o, e_o, args_o), = got["clock.outer"]
    (line_i, s_i, e_i, args_i), = got["clock.inner"]
    assert line_o == line_i and s_o <= s_i and e_i <= e_o
    assert args_o == {"trace": sp.ctx.trace_id, "span": sp.ctx.span_id,
                      "iter": 3, "rid": "r0"}     # the list is left out
    assert args_i["parent"] == sp.ctx.span_id
    # and the host-clock record is what it always was
    rec = {e["name"]: e for e in trace.spans()}
    assert rec["clock.outer"]["args"]["rows"] == [1, 2]
    assert rec["clock.inner"]["args"]["parent"] == sp.ctx.span_id


def test_tracing_off_records_nothing_in_the_profile(tmp_path):
    from harness import common

    assert not trace.enabled()
    # switching the tracer off keeps what it recorded (trace.reconfigure),
    # and a neighbour in this process may have recorded: nothing is ADDED
    before = len(trace.spans())
    common.start_profile(str(tmp_path / "prof"))
    try:
        h = trace.span("clock.off", "test", {"iter": 1})
        assert h is trace.span("other")        # the shared no-op
        with h:
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert "clock.off" not in host_events(str(tmp_path / "prof"))
    assert len(trace.spans()) == before


# ------------------------------------------- (b) the worker's partition --
@pytest.fixture(scope="module")
def small_model():
    # wide enough that a decode step (milliseconds on the CPU) dwarfs the
    # tens of microseconds the loop spends between two spans
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=512, num_layers=4, num_heads=8,
        max_seq_len=64, dropout=0.0))
    model.eval()
    return model


def prompts(n):
    rng = np.random.RandomState(1)
    return [rng.randint(0, 256, size=int(length))
            for length in rng.randint(3, 30, size=n)]


@pytest.fixture(scope="module")
def served_spans(small_model, tmp_path_factory):
    """Six requests through two slots with tracing on -> the spans."""
    eng = GenerativeEngine(small_model, slots=2, max_context=64,
                           max_new_tokens_cap=16)
    paddle.set_flags({"FLAGS_trace_dir":
                      str(tmp_path_factory.mktemp("spans"))})
    trace.reset()
    try:
        for h in [eng.submit(p, 10) for p in prompts(6)]:
            assert len(h.result(120)["tokens"]) == 10
        return trace.spans()
    finally:
        eng.shutdown()         # first: a live generate.idle still records
        paddle.set_flags({"FLAGS_trace_dir": ""})
        trace.reset()


def test_worker_spans_exist_and_carry_iter_and_rid(served_spans):
    names = {e["name"] for e in served_spans}
    assert set(WORKER_SPANS) - {"generate.idle"} <= names
    assert "generate.queue_wait" in names
    steps = [e for e in served_spans
             if e["name"] == "generate.decode_step"]
    (tid,) = {e["tid"] for e in steps}
    for e in served_spans:
        if e["name"] in WORKER_SPANS and e["name"] not in (
                "generate.prefill", "generate.decode_step"):
            assert e["tid"] == tid
            assert e["args"]["rid"] == steps[0]["args"]["replica"]
            assert e["args"]["iter"] >= 1
    # the three phases of a step are its children, in order
    for step in steps:
        kids = sorted((e for e in served_spans
                       if e["args"].get("parent") == step["args"]["span"]),
                      key=lambda e: e["ts"])
        assert [k["name"].rsplit(".", 1)[1] for k in kids] == [
            "stage", "launch", "wait"]
        assert len({k["args"]["iter"] for k in kids}) == 1


def test_step_spans_say_whether_they_ran_ahead_and_what_they_staged(
        served_spans):
    """`ahead`: launched while an earlier step was unread; `staged`: host
    arrays put for it — seven where the host staged the rows anew (the
    class's first fill, a change of bucket), one plan where the row set
    changed within the bucket and the rows were joined on the device
    (`joined` of them new), none on a steady step (whose `.stage`
    brackets nothing). A `.wait` is the read of the OLDEST unread step:
    the one launched a pass before, under the step launched in this pass
    — or a bare `.wait`, child of no step, where a pass only reads."""
    steps = sorted((e for e in served_spans
                    if e["name"] == "generate.decode_step"),
                   key=lambda e: e["ts"])
    assert {e["args"]["ahead"] for e in steps} == {0, 1}
    assert {e["args"]["staged"] for e in steps} == {0, 1, 7}
    assert steps[0]["args"]["ahead"] == 0 and steps[0]["args"]["staged"] == 7
    for e in steps:
        # staged anew: everything was read first, so not ahead; nothing
        # staged: a steady step, always launched ahead; a join reads
        # nothing for it (here both rows end on one step, so nothing is
        # left unread when the next two join)
        assert e["args"]["staged"] != 7 or e["args"]["ahead"] == 0
        assert e["args"]["staged"] != 0 or e["args"]["ahead"] == 1
        assert e["args"]["joined"] == 0 or e["args"]["staged"] == 1
    # six requests of ten tokens through two slots: most steps run ahead
    assert sum(e["args"]["ahead"] for e in steps) >= 0.6 * len(steps)
    step_ids = {e["args"]["span"] for e in steps}
    waits = [e for e in served_spans
             if e["name"] == "generate.decode_step.wait"]
    bare = [e for e in waits if e["args"].get("parent") not in step_ids]
    assert len(waits) == len(steps) + len(bare)
    assert all(e["args"]["iter"] >= 1 for e in bare)


def test_worker_spans_partition_the_threads_time(served_spans):
    steps = [e for e in served_spans
             if e["name"] == "generate.decode_step"]
    tid = steps[0]["tid"]
    t0 = min(e["ts"] for e in steps)
    t1 = max(e["ts"] + e["dur"] for e in steps)
    mine = sorted((e for e in served_spans
                   if e["tid"] == tid and e["name"] in WORKER_SPANS),
                  key=lambda e: (e["ts"], -e["dur"]))
    # no two overlap unless one holds the other
    open_ends = []
    for e in mine:
        s, end = e["ts"], e["ts"] + e["dur"]
        while open_ends and open_ends[-1] <= s:
            open_ends.pop()
        assert not open_ends or end <= open_ends[-1], e["name"]
        open_ends.append(end)
    # and between the first and the last decode step they leave under a
    # twentieth of the thread's time uncovered
    covered, cur = 0.0, t0
    for e in mine:
        s, end = max(e["ts"], cur), min(e["ts"] + e["dur"], t1)
        if end > s:
            covered += end - s
            cur = end
    assert covered / (t1 - t0) >= 0.95


def test_queue_wait_runs_from_enqueue_to_admission(served_spans):
    waits = [e for e in served_spans if e["name"] == "generate.queue_wait"]
    assert len(waits) == 6
    by_trace = {}
    for e in served_spans:
        if e["name"] in ("generate.enqueue", "generate.prefill"):
            by_trace.setdefault(e["args"]["trace"], {})[e["name"]] = e
    for w in waits:
        enq = by_trace[w["args"]["trace"]]["generate.enqueue"]
        pre = by_trace[w["args"]["trace"]]["generate.prefill"]
        assert w["args"]["parent"] == enq["args"]["span"]
        # it begins where the request was made (inside generate.enqueue)…
        assert enq["ts"] <= w["ts"] <= enq["ts"] + enq["dur"]
        # …and ends no later than its prefill begins
        assert w["ts"] + w["dur"] <= pre["ts"] + 1e-3
        assert w["args"]["prompt_tokens"] == enq["args"]["prompt_tokens"]
        assert w["args"]["queue_depth"] >= 0
    # two slots, six requests sent together: four of them waited for one
    assert sum(w["dur"] > 1000 for w in waits) >= 4


def test_span_readers_on_the_engines_own_spans(served_spans):
    from harness import cells

    run = {"spans": served_spans, "trace": None}
    bench = os.path.join(REPO, "benchmarks")
    wait = cells.load_reader(bench, "engine.queue_wait_ms_p90")(run)
    host = cells.load_reader(bench, "engine.host_ms_per_step_p50")(run)
    step = cells.load_reader(bench, "engine.decode_step_ms_p50")(run)
    assert wait > 0
    assert 0 < host < step


def test_tracing_off_new_sites_build_no_args(small_model, monkeypatch):
    """With FLAGS_trace_dir unset every site gets the shared no-op and
    none builds an args dictionary (nor emits a measured span). The
    ring keeps what a neighbour of this process recorded while ITS
    tracing was on (switching off keeps the capture), so "records
    nothing" is read as "adds nothing"."""
    assert not trace.enabled()
    before = len(trace.spans())
    seen = []

    class Recorder:
        enabled = staticmethod(trace.enabled)

        @staticmethod
        def span(name, cat="span", args=None, parent=None):
            seen.append((name, args))
            return trace.span(name, cat, args, parent)

        @staticmethod
        def emit_span(*a, **k):
            raise AssertionError(f"emit_span{a} with tracing off")

    monkeypatch.setattr(gen_mod, "_tr", Recorder)
    eng = GenerativeEngine(small_model, slots=2, max_context=64,
                           max_new_tokens_cap=16)
    try:
        assert len(eng.generate(prompts(1)[0], 4, timeout=120)["tokens"]) \
            == 4
    finally:
        eng.shutdown()
    assert set(WORKER_SPANS) - {"generate.idle"} <= {n for n, _ in seen}
    assert all(args is None for _, args in seen)
    assert trace.span("x") is trace.span("y")
    assert len(trace.spans()) == before


# ------------------------------------------ (c) names on the device side --
def test_train_step_is_traced_under_its_two_scopes():
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.core import rng
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    model = nn.Linear(8, 4)
    step = TrainStep(model, opt.AdamW(1e-3, parameters=model.parameters()),
                     lambda m, x, y: ((m(x) - y) ** 2).mean())
    step._build()
    x, y = jnp.ones((2, 8), jnp.float32), jnp.ones((2, 4), jnp.float32)
    text = step._step_fn.lower(
        step._params, step._buffers, step._opt_state,
        jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.int32),
        rng.next_key(), (x, y)).as_text(debug_info=True)
    assert "jvp(train.loss)" in text
    assert "transpose(jvp(train.loss))" in text
    assert "train.optimizer" in text


def test_flash_kernels_carry_their_names_into_the_lowered_program():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(1, 256, 2, 64), "bfloat16")
               for _ in range(3)]

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    def lowered(fn):      # lower only, for the TPU target, on this host
        return jax.jit(fn).trace(q, k, v).lower(
            lowering_platforms=("tpu",)).as_text()

    assert 'kernel_name = "flash_fwd"' in lowered(fwd)
    text = lowered(bwd)
    # one fused backward kernel: it keeps the dK/dV kernel's name, and the
    # benchmark's reader counts a backward pass by the larger of the two
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert f'kernel_name = "{name}"' in text
    assert 'kernel_name = "flash_bwd_dq"' not in text


def test_op_scopes_join_instruction_names_to_scopes(tracing):
    hlo = '''
HloModule jit_step
%fused (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/train.optimizer/add" source_file="a.py" source_line=3}
}
ENTRY %main {
  %flash_fwd.14 = (bf16[8,8]{1,0}, f32[8]{0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(train.loss)/flash_fwd/pallas_call"}
  %copy.2 = f32[8]{0} copy(%x)
  ROOT %fusion.7 = f32[8]{0} fusion(%m), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/train.optimizer/add"}
}'''
    trace.note_op_scopes(hlo)
    assert trace.op_scopes() == {
        "add.3": "jit(step)/train.optimizer/add",
        "flash_fwd.14": "jit(step)/jvp(train.loss)/flash_fwd/pallas_call",
        "fusion.7": "jit(step)/train.optimizer/add"}
    trace.reset()
    assert trace.op_scopes() == {}
    paddle.set_flags({"FLAGS_trace_dir": ""})
    trace.note_op_scopes(hlo)                  # off: nothing is kept
    assert trace.op_scopes() == {}
