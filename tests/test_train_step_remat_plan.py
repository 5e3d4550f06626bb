"""What the scanned block's remat keeps (jit/remat_plan.py, TrainStep's
`_plan_remat`, the names in models/gpt.py): the plan as a pure function,
and on four virtual CPU devices (dp2 x tp2, tiny widths) what it does to
the compiled step — one all-reduce fewer in the backward, not a bit of
the gradients moved. The CPU knows no device memory, so no plan is made
there; the tests hand `_device_budget` a device's."""
import contextlib
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from _hlo_text import residual_reduces
from paddle_tpu.jit import remat_plan as rp
from paddle_tpu.jit import train_step as ts
from paddle_tpu.models import gpt
from paddle_tpu.profiler.stats.flops import DEVICE_PEAKS

V5E = DEVICE_PEAKS["TPU v5 lite"]
C = rp.RematCandidate
# the issue's table, a layer of 24: (name, bytes, seconds of the stack)
PROJ = C("proj", 64 << 20, 24, 0.051)
ATTN = C("attn", 33 << 20, 24, 0.012)
QKV = C("qkv", 96 << 20, 24, 0.041)
GIB = 1 << 30


# ------------------------------------------------------------ the plan --
@pytest.mark.parametrize("candidates, spare, want", [
    ([PROJ, ATTN, QKV], 5 * GIB, ("proj", "qkv", "attn")),      # fits all
    ([PROJ, ATTN, QKV], 2 * GIB, ("proj",)),      # the first only
    ([PROJ, ATTN, QKV], GIB, ("attn",)),    # what still fits is kept
    ([PROJ, ATTN, QKV], GIB // 2, ()),            # fits none
    ([PROJ, ATTN, QKV], -GIB, ()),          # the step alone is over
    ([ATTN, QKV, PROJ], 5 * GIB, ("proj", "qkv", "attn")),  # by value,
    ([QKV, C("twin", 96 << 20, 24, 0.041)], 5 * GIB, ("qkv", "twin")),
], ids=["all", "first", "smaller", "none", "over", "order", "ties"])
def test_remat_plan_keeps_the_most_seconds_a_byte_that_fit(candidates,
                                                           spare, want):
    assert rp.remat_plan(candidates, spare) == want
    kept = [c for c in candidates if c.name in want]
    assert sum(c.bytes for c in kept) <= max(spare, 0)


def test_the_plan_is_held_to_the_chosen_programs_report():
    """A kept value can cost twice its shape: the least valuable name
    goes, one compile a candidate at most, and an empty plan's need is
    the policy-free program's."""
    asked = []

    def need_of(names, cost={"proj": 2 * GIB, "qkv": 3 * GIB,
                             "attn": 2 * GIB}):
        asked.append(names)
        return 9 * GIB + sum(cost[n] for n in names)

    got = rp.fit_remat_plan([PROJ, ATTN, QKV], 14 * GIB, need_of)
    assert asked == [(), ("proj", "qkv", "attn"), ("proj", "qkv")]
    assert got == {"names": ["proj", "qkv"], "bytes_per_layer": 160 << 20,
                   "bytes": 24 * (160 << 20), "spare_bytes": 5 * GIB,
                   "need_bytes": 14 * GIB}
    asked.clear()
    got = rp.fit_remat_plan([PROJ], 10 * GIB, lambda names: 9 * GIB + (
        asked.append(names) or 3 * GIB * len(names)))
    assert got["names"] == [] and got["bytes"] == 0
    assert got["need_bytes"] == 9 * GIB and asked == [()]
    assert rp.saved_report(())["bytes"] == 0


# ------------------------------------------- the model's own candidates --
@pytest.fixture(scope="module")
def mesh():
    return bench.dp_tp_mesh(jax.devices()[:4], tp=2)


def _build(mesh, batch=4, seq=64):
    with mesh or contextlib.nullcontext():
        step, ids, labels, _ = bench.build_train_step(
            "gpt3-tiny", batch, seq, mesh=mesh)
    return step, ids, labels


def test_candidates_read_the_link_off_the_mesh_and_the_specs(mesh):
    """gpt3-tiny (D 128, 2 layers), batch 4 x 64: over dp2 x tp2 the
    attention projection is replicated over tp and crossed it; with no
    mesh, or tp = 1, it is worth its product alone — less than half."""
    step, ids, _ = _build(mesh)
    batch_spec = step._batch_sharding[0]
    proj, qkv = step.model.remat_candidates(
        ids.shape, mesh, step._param_specs, batch_spec, V5E)
    assert (proj.name, qkv.name) == (gpt.SAVE_ATTN_PROJ, gpt.SAVE_QKV)
    assert proj.layers == qkv.layers == 2
    assert proj.layer_bytes == 2 * 64 * 128 * 2        # [B/dp, L, D] bf16
    assert qkv.layer_bytes == 2 * 64 * (384 // 2) * 2  # columns over tp
    product_s = 2 * (2 * 64) * (128 // 2) * 128 / V5E["bf16_flops"]
    link_s = proj.layer_bytes / V5E["ici_link_bytes_per_s"]
    assert proj.seconds == pytest.approx(2 * (product_s + link_s))
    assert rp.remat_plan([proj, qkv], 1 << 40) == (proj.name, qkv.name)

    flat = bench.dp_tp_mesh(jax.devices()[:4], tp=1)
    alone, _ = step.model.remat_candidates(
        ids.shape, flat, step._param_specs, batch_spec, V5E)
    assert alone.layer_bytes == 1 * 64 * 128 * 2       # batch over dp 4
    assert alone.seconds == pytest.approx(
        2 * 2 * 64 * 128 * 128 / V5E["bf16_flops"])
    assert alone.seconds / alone.bytes < 0.5 * proj.seconds / proj.bytes
    no_mesh, _ = step.model.remat_candidates(ids.shape, None, None, None,
                                             V5E)
    assert no_mesh.seconds == pytest.approx(4 * alone.seconds)
    step.model.remat = False
    assert step.model.remat_candidates(ids.shape, mesh, None, None,
                                       V5E) == []


# ----------------------------------------------- the compiled step, dp2tp2 --
def _with_budget(monkeypatch, device_bytes):
    monkeypatch.setattr(
        ts, "_device_budget",
        lambda mesh: device_bytes and (device_bytes, V5E))


def _residual_reduces(step, ids, labels) -> list:
    """All-reduces of the residual stream's per-device shape
    [B/dp, L, D], by while body, fewest first."""
    from paddle_tpu.core import compile_cache

    with compile_cache.donated_cpu_guard(True):
        text = step.lowered(ids, labels).compile().as_text()
    return residual_reduces(text, r"(bf16|f32)\[2,64,128\]")


def test_the_backward_holds_one_all_reduce_fewer_with_the_plan(
        mesh, monkeypatch):
    _with_budget(monkeypatch, None)
    bare = _build(mesh)
    assert _residual_reduces(*bare) == [2, 3]
    assert bare[0]._remat_saved == rp.saved_report(())
    _with_budget(monkeypatch, 1 << 40)
    planned = _build(mesh)
    assert _residual_reduces(*planned) == [2, 2]
    saved = planned[0]._remat_saved
    assert saved["names"] == [gpt.SAVE_ATTN_PROJ, gpt.SAVE_QKV]
    assert saved["bytes_per_layer"] == 2 * 64 * (128 + 192) * 2
    assert saved["bytes"] == 2 * saved["bytes_per_layer"]
    assert saved["need_bytes"] <= saved["spare_bytes"] + saved["need_bytes"]
    report = planned[0].compiled_memory_report(*planned[1:])
    assert report["remat_saved"] == saved


def test_a_budget_the_first_name_alone_fits_keeps_it_alone(
        mesh, monkeypatch):
    """The device's memory decides, nothing else: with room for the
    policy-free step, the spare and 1.5 layers' worth of the first
    value, the plan is the first name; with none, it is empty and the
    step is the policy-free one."""
    _with_budget(monkeypatch, None)
    step, ids, labels = _build(mesh)
    from paddle_tpu.core import compile_cache

    with compile_cache.donated_cpu_guard(True):
        need = ts._need_bytes(
            step.lowered(ids, labels).compile().memory_analysis())
    proj = 2 * (2 * 64 * 128 * 2)
    _with_budget(monkeypatch, need + rp.SPARE_BYTES + proj + proj // 2)
    step, ids, labels = _build(mesh)
    step.lowered(ids, labels)
    assert step._remat_saved["names"] == [gpt.SAVE_ATTN_PROJ]
    assert step._remat_saved["spare_bytes"] == proj + proj // 2
    assert step.model.remat_save == (gpt.SAVE_ATTN_PROJ,)
    _with_budget(monkeypatch, need + rp.SPARE_BYTES)
    step, ids, labels = _build(mesh)
    step.lowered(ids, labels)
    assert step._remat_saved["names"] == []
    assert step.model.remat_save == ()


def _two_steps(step, ids, labels):
    """-> (losses, the optimizer's state after the first step — its
    first moment is the gradient's own bits —, the parameters after the
    second), as host arrays."""
    losses = [np.asarray(step(ids, labels).numpy())]
    after_one = jax.device_get(step.state()[2])
    losses.append(np.asarray(step(np.roll(ids, 1, 0),
                                  np.roll(labels, 1, 0)).numpy()))
    return losses, after_one, jax.device_get(step.state()[0])


@pytest.mark.parametrize("sharded", [True, False], ids=["dp2tp2", "one"])
def test_loss_gradients_and_parameters_are_bit_for_bit(mesh, monkeypatch,
                                                       sharded):
    mesh = mesh if sharded else None
    _with_budget(monkeypatch, None)
    want = _two_steps(*_build(mesh))
    _with_budget(monkeypatch, 1 << 40)
    step, ids, labels = _build(mesh)
    got = _two_steps(step, ids, labels)
    assert step._remat_saved["names"] == [gpt.SAVE_ATTN_PROJ, gpt.SAVE_QKV]
    assert step.compile_report["remat_saved"] == step._remat_saved
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_every_gradient_leaf_of_the_stack_is_bit_for_bit(mesh):
    """The scan itself, differentiated with and without the names kept,
    over dp2 x tp2 in bfloat16: every leaf's bits."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    D, F, L, H = 64, 256, 3, 4
    rng = np.random.default_rng(0)
    shapes = gpt.GPTLayer([D], [D], [D, 3 * D], [3 * D], [D, D], [D], [D],
                          [D], [D, F], [F], [F, D], [D])
    shard = gpt.gpt_scan_shard_fn()
    layers = tuple(jax.device_put(
        jnp.asarray(rng.normal(0, 0.05, [L] + s), jnp.bfloat16),
        NamedSharding(mesh, shard(n, np.empty([L] + s))))
        for n, s in zip(gpt.LAYER_PARAMS, shapes))
    x = jax.device_put(
        jnp.asarray(rng.normal(0, 1, (4, 32, D)), jnp.bfloat16),
        NamedSharding(mesh, P("dp", None, None)))

    def grads(names):
        def loss(x, *layers):
            return gpt._gpt_scan_blocks_p._pure_fn(
                x, *layers, num_heads=H, remat=True,
                remat_save=names).astype(jnp.float32).sum()

        return jax.jit(jax.value_and_grad(loss, argnums=tuple(
            range(len(layers) + 1))))(x, *layers)

    want, got = grads(()), grads((gpt.SAVE_ATTN_PROJ, gpt.SAVE_QKV))
    assert len(jax.tree_util.tree_leaves(got)) == 14
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


# ------------------------------------------ where nothing was to move --
# sha256 of the step's lowered text at gpt3-tiny, batch 4 x 64, as the
# PARENT of the PR that brought the plan lowered the program it ran
# (pinned from a checkout of that commit, on the CPU with this
# container's jax): on one device, and over dp2 x tp2 with the batch laid
# out over dp as the step's call lays it.
PARENT_STEP = {
    False: "cac531c4032c0201a59f0b416900820b8874c03fc6dda09978f42eda4db1066d",
    True: "22d5cd2a64df88f8552e1e186ba373e9ef331a275e882057901771b5bf1420b0",
}


@pytest.mark.parametrize("sharded", [False, True], ids=["one", "dp2tp2"])
def test_an_empty_plan_lowers_to_the_parents_text(mesh, monkeypatch,
                                                  sharded):
    """No device memory known (any CPU run), or none to spare: the
    program is the one the parent ran, byte for byte — and making the
    plan took no key off the random stream."""
    from paddle_tpu.core import rng

    for budget in (None, 1):
        _with_budget(monkeypatch, budget)
        step, ids, labels = _build(mesh if sharded else None)
        before = rng.default_generator().get_state()
        text = step.lowered(ids, labels).as_text()
        assert rng.default_generator().get_state() == before
        assert step._remat_saved["names"] == []
        assert "name" not in {
            e.primitive.name for e in _scan_body(step, ids, labels).eqns}
        assert hashlib.sha256(text.encode()).hexdigest() == \
            PARENT_STEP[sharded]


def _scan_body(step, ids, labels):
    """The jaxpr of the scanned block's checkpointed body, as the forward
    traces it."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.functional import swap_state
    from paddle_tpu.core import state as st

    model = step.model

    def hidden(params, ids):
        with st.functional_trace(), \
                swap_state(model, {**params, **step._frozen},
                           step._buffers):
            return model.hidden(Tensor(ids))._data

    jaxpr = jax.make_jaxpr(hidden)(step._params, jnp.asarray(ids)).jaxpr
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    (remat,) = [e for e in scan.params["jaxpr"].jaxpr.eqns
                if e.primitive.name in ("checkpoint", "remat2", "remat")]
    return remat.params["jaxpr"]


def test_a_plan_names_its_values_in_the_body(mesh, monkeypatch):
    _with_budget(monkeypatch, 1 << 40)
    step, ids, labels = _build(mesh)
    step.lowered(ids, labels)
    names = [e.params["name"] for e in _scan_body(step, ids, labels).eqns
             if e.primitive.name == "name"]
    assert names == [gpt.SAVE_QKV, gpt.SAVE_ATTN_PROJ]


def test_the_dispatch_span_carries_the_bytes_kept(mesh, monkeypatch,
                                                  tmp_path):
    from paddle_tpu.observability import trace

    _with_budget(monkeypatch, 1 << 40)
    step, ids, labels = _build(mesh)
    trace.reconfigure(str(tmp_path))
    try:
        step(ids, labels)
        spans = [s for s in trace.spans() if s["name"] == "train.dispatch"]
    finally:
        trace.reconfigure(None)
        trace.reset()
    assert spans and spans[-1]["args"]["remat_saved_bytes"] == \
        step._remat_saved["bytes"] > 0


def test_the_plan_is_kept_beside_the_compile_cache(mesh, monkeypatch,
                                                   tmp_path):
    """A second start under the same program, limit and candidates asks
    the compiler nothing: the plan is read back from beside the cache's
    entries; another limit is another key."""
    from paddle_tpu.core import compile_cache as cc

    monkeypatch.setitem(cc._STATS, "enabled", True)
    monkeypatch.setitem(cc._STATS, "dir", str(tmp_path))
    _with_budget(monkeypatch, 1 << 40)
    step, ids, labels = _build(mesh)
    step.lowered(ids, labels)
    first = step._remat_saved
    assert len(list(tmp_path.glob("*-plan.json"))) == 1
    monkeypatch.setattr(rp, "fit_remat_plan", None)     # not asked again
    step, ids, labels = _build(mesh)
    step.lowered(ids, labels)
    assert step._remat_saved == first
    assert step.model.remat_save == tuple(first["names"])
    assert cc.plan_lookup("another key") is None


def test_the_engines_decode_program_lowers_to_the_parents_text():
    """The engine shares block_qkv / block_out and asks for no names: its
    decode program at gpt3-tiny (4 slots, float32 pools, donated) is the
    text it was before the block could name anything — the hash
    tests/test_lfm2.py pins for all eight GPT programs, here for the one
    every serve cell spends its window in."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import GenerativeEngine
    from paddle_tpu.inference.serving.generate import stack_gpt_params
    from paddle_tpu.models import PRESETS, GPTForCausalLM
    from paddle_tpu.quantization import kv as kvq

    paddle.seed(0)
    model = GPTForCausalLM(PRESETS["gpt3-tiny"])
    model.eval()
    eng = GenerativeEngine(params=stack_gpt_params(model), slots=4,
                           warmup=False, auto_start=False, kv_dtype="f32",
                           donate=True)
    try:
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        cap, b = eng._caps[-1], eng._batch_buckets[-1]
        params = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng._params)
        pool = kvq.aval(eng._pool_shape(cap), "f32")
        i32, f32 = np.int32, np.float32
        text = eng._program("decode", cap, b, 1).lower(
            params, pool, pool, sds((b,), i32), sds((b,), i32),
            sds((b,), i32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
            sds((b, 2), np.uint32)).as_text()
    finally:
        eng.shutdown(drain=False)
    # since PR 37 the program carries a name, and that is all that moved
    name = f"jit_gpt_decode_c{cap}_b{b}"
    assert name in text
    assert hashlib.sha256(text.replace(name, "jit__unknown").encode()
                          ).hexdigest() == \
        "1c971dfd0830cd6449870b597e8516e512425abfb6ea521ab694699ee02babe4"
