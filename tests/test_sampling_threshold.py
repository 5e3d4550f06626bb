"""The sampler's two thresholds come from a search over values
(serving/generate.py::_sample_thresholds), not from a sort: against the
sort-based sampler it replaced — copied in below as the plain reference,
importing nothing from the engine — the kept set satisfies the definition,
the token under the same key is the reference's, and no engine program
holds a `sort` instruction any more."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import GenerativeEngine, generate
from paddle_tpu.quantization import kv as kvq

NEG_INF = -1e30


# ------------------------------------------------- the plain reference --
def reference_token(logits, temp, topk, topp, key):
    """`_sample_token` as it stood before PR 34 (one row): a descending
    sort, the k-th value, the sorted prefix whose exclusive cumulative
    mass is under topp, then the UNSORTED logits masked by value."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temp, 1e-6)
    srt = jnp.sort(scaled)[::-1]
    kth = srt[jnp.clip(topk - 1, 0, V - 1)]
    masked_srt = jnp.where(srt < kth, NEG_INF, srt)
    sp = jax.nn.softmax(masked_srt)
    keep = (jnp.cumsum(sp) - sp) < topp
    cutoff = jnp.min(jnp.where(keep, masked_srt, jnp.inf))
    scaled = jnp.where(scaled < jnp.maximum(kth, cutoff), NEG_INF, scaled)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temp > 0.0, sampled, greedy)


reference_tokens = jax.jit(jax.vmap(reference_token))
sample_tokens = jax.jit(generate._sample_token)
thresholds = jax.jit(generate._sample_thresholds)


# ------------------------------------------------------------ the cases --
KINDS = ("peaked", "flat", "heavy_tailed", "ties_at_kth", "ties_at_cutoff",
         "bfloat16_rounded", "large_and_negative")
VOCABS = (128, 50304, 65536)
TOPPS = (0.1, 0.5, 0.95, 1.0)
TEMPS = (0.0, 0.8, 5.0)


def one_row(kind, V, rng):
    """float32 [V] logits of one shape of distribution."""
    if kind == "peaked":
        x = rng.standard_normal(V) * 10.0
    elif kind == "flat":
        x = rng.standard_normal(V) * 0.2          # a seeded model's own
    elif kind == "heavy_tailed":
        x = rng.standard_cauchy(V) * 2.0
    elif kind == "ties_at_kth":
        # the 4th to 8th largest are one value: top_k = 5 lands inside
        x = rng.standard_normal(V) * 3.0
        order = np.argsort(-x)
        x[order[3:8]] = x[order[3]]
    elif kind == "ties_at_cutoff":
        # runs of 7 equal values along the sorted order, so that every
        # top_p's cut-off falls into a run
        x = np.sort(rng.standard_normal(V) * 3.0)
        x = np.repeat(x[::7], 7)[:V]
        rng.shuffle(x)
    elif kind == "bfloat16_rounded":
        x = np.asarray(jnp.asarray(rng.standard_normal(V) * 3.0,
                                   jnp.bfloat16).astype(jnp.float32))
    elif kind == "large_and_negative":
        x = rng.standard_normal(V) * 3.0 - 40.0
        x[rng.integers(0, V, 5)] = [3e4, -3e4, 0.0, -0.0, 1e-30]
        x[rng.integers(0, V, 2)] = [-1e-30, -2.5e4]
    return x.astype(np.float32)


def batch(kind, V, topks, seed):
    """One row for every (top_k, top_p, temperature): logits [b, V] and
    the rows' sampling fields, greedy and sampled rows mixed."""
    rng = np.random.default_rng([seed, V, KINDS.index(kind)])
    combos = [(k, p, t) for k in topks for p in TOPPS for t in TEMPS]
    logits = np.stack([one_row(kind, V, rng) for _ in combos])
    ks, ps, ts = (np.asarray(c) for c in zip(*combos))
    keys = rng.integers(0, 2 ** 32, (len(combos), 2), dtype=np.uint32)
    return (jnp.asarray(logits), jnp.asarray(ts, jnp.float32),
            jnp.asarray(ks, jnp.int32), jnp.asarray(ps, jnp.float32),
            jnp.asarray(keys))


CASES = [(kind, V) for kind in KINDS for V in VOCABS]


# ------------------------------------------------------- the definition --
def assert_definition(scaled, topks, topps, thr):
    """Reckoned in float64 with numpy, a row at a time: the threshold is
    one of the row's values, at or above its k-th largest; the survivors'
    mass strictly above it is under top_p and the mass at or above it
    reaches top_p (or it is the smallest survivor), both to 1e-5 of that
    mass."""
    V = scaled.shape[-1]
    for x, k, p, t in zip(*map(np.asarray, (scaled, topks, topps, thr))):
        assert t in x
        kth = np.sort(x)[V - k]
        assert t >= kth
        x64 = x.astype(np.float64)
        mass = np.exp(x64 - x64.max())
        survivors = mass[x >= kth].sum()
        above = mass[x > t].sum() / survivors
        at_or_above = mass[x >= t].sum() / survivors
        assert above < p + 1e-5, (k, p, above)
        assert at_or_above >= p - 1e-5 or t == kth, (k, p, at_or_above)


@pytest.mark.parametrize("kind, V", CASES)
def test_the_kept_set_satisfies_the_definition(kind, V):
    logits, temps, topks, topps, _ = batch(kind, V, (1, 5, V), seed=1)
    live = np.asarray(temps) > 0
    scaled = (logits / jnp.maximum(temps, 1e-6)[:, None])[live]
    topks, topps = topks[live], topps[live]
    thr = thresholds(scaled, topks, topps, jnp.ones(len(topks), bool))
    assert_definition(scaled, topks, topps, thr)


# ------------------------------------------------------------ the token --
MIXES = {
    "every_top_k": (1, 5, None),      # None: the vocabulary's size
    "no_top_k": (None,),              # the cells' traffic: no row asks
}


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("kind, V", CASES)
def test_the_token_is_the_sort_based_samplers(kind, V, mix):
    """Same key, same token, greedy and sampled rows mixed — with rows
    that ask for a top-k and (the batch's other path) with none."""
    topks = tuple(V if k is None else k for k in MIXES[mix])
    args = batch(kind, V, topks, seed=2)
    want = np.asarray(reference_tokens(*args))
    got = np.asarray(sample_tokens(*args))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    greedy = np.asarray(args[1]) == 0
    np.testing.assert_array_equal(
        got[greedy], np.asarray(args[0]).argmax(-1)[greedy])


@pytest.mark.parametrize("kind, V", [(k, 50304) for k in KINDS])
def test_greedy_rows_and_top_k_1_are_the_argmax(kind, V):
    """temp == 0 is the argmax whatever the other fields say (a batch of
    nothing else runs the argmax alone), and top_k = 1 is the argmax at
    any temperature."""
    logits, temps, topks, topps, keys = batch(kind, V, (1, 5, V), seed=3)
    best = np.asarray(logits).argmax(-1)
    all_greedy = sample_tokens(logits, jnp.zeros_like(temps), topks, topps,
                               keys)
    np.testing.assert_array_equal(np.asarray(all_greedy), best)
    hot = np.asarray(sample_tokens(
        logits, jnp.maximum(temps, 0.8), jnp.ones_like(topks), topps, keys))
    x = np.asarray(logits)
    # ties of the maximum are all kept (the mask is by value), as they were
    np.testing.assert_array_equal(x[np.arange(len(x)), hot], x.max(-1))
    single = (x == x.max(-1, keepdims=True)).sum(-1) == 1
    np.testing.assert_array_equal(hot[single], best[single])


def test_a_padding_rows_top_k_does_not_start_the_top_k_search():
    """The engine pads a decode batch with rows of temp 0, top_k 1: what
    a greedy row asks for is never used, and must not cost the batch the
    search (the predicate that `lax.cond` takes is over sampled rows)."""
    logits, temps, topks, topps, keys = batch("peaked", 128, (128,), seed=4)
    topks = jnp.where(temps > 0, topks, 1)
    want = np.asarray(reference_tokens(logits, temps, topks, topps, keys))
    got = np.asarray(sample_tokens(logits, temps, topks, topps, keys))
    np.testing.assert_array_equal(got, want)
    # read WITH the search, a greedy row of top_k 1 is cut at its maximum;
    # with the greedy rows out of the predicate the batch skipped the
    # search: its thresholds are those of a batch that asks for no top-k
    greedy = np.asarray(temps) == 0
    everyone = jnp.ones_like(greedy)
    with_k = np.asarray(thresholds(logits, topks, topps, everyone))
    assert (with_k[greedy] == np.asarray(logits).max(-1)[greedy]).all()
    np.testing.assert_array_equal(
        np.asarray(thresholds(logits, topks, topps, ~greedy)),
        np.asarray(thresholds(logits, jnp.full_like(topks, 128), topps,
                              everyone)))


# --------------------------------------------- no sort in any program --
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lowered(eng, kind):
    """The HLO text of the engine's `kind` program at its largest class,
    lowered from shapes alone (tests/test_lfm2.py's way), scopes in."""
    cap, b, S, k = eng._caps[-1], eng._batch_buckets[-1], 16, 4
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype), eng._params)
    pool = kvq.aval(eng._pool_shape(cap), eng._kv_dtype)
    state = eng._state_shape()
    rec = () if state is None else (kvq.aval(state, eng._kv_dtype),)
    i32, f32 = np.int32, np.float32
    row = (_sds((), i32), _sds((1, S), i32))
    one = (_sds((), f32), _sds((), i32), _sds((), f32), _sds((2,), np.uint32))
    rows = (_sds((b,), f32), _sds((b,), i32), _sds((b,), f32),
            _sds((b, 2), np.uint32))
    args = {
        "decode": (_sds((b,), i32), _sds((b,), i32), _sds((b,), i32), *rows),
        "prefill": (*row, _sds((), i32), *one),
        "extend": (*row, _sds((), i32), _sds((), i32), *one),
        "verify": (_sds((b,), i32), _sds((b, k), i32), _sds((b,), i32),
                   *rows),
    }[kind]
    return eng._program(
        kind, cap, S if kind in ("prefill", "extend") else b,
        k if kind == "verify" else 1).lower(
            params, pool, pool, *args, *rec).as_text(debug_info=True)


@pytest.fixture(scope="module")
def engines():
    from paddle_tpu.inference.serving.generate import stack_gpt_params
    from paddle_tpu.models import PRESETS, GPTForCausalLM, lfm2

    paddle.seed(0)
    model = GPTForCausalLM(PRESETS["gpt3-tiny"])
    model.eval()
    made = {
        "gpt3-tiny": GenerativeEngine(
            params=stack_gpt_params(model), slots=4, warmup=False,
            auto_start=False),
        "lfm2-tiny": GenerativeEngine(
            params=(lfm2.init_params(lfm2.PRESETS["lfm2-tiny"]),
                    lfm2.PRESETS["lfm2-tiny"]),
            slots=4, warmup=False, auto_start=False),
    }
    yield made
    for eng in made.values():
        eng.shutdown(drain=False)


@pytest.mark.parametrize("preset, kind", [
    ("gpt3-tiny", "decode"), ("gpt3-tiny", "prefill"),
    ("gpt3-tiny", "verify"), ("gpt3-tiny", "extend"),
    ("lfm2-tiny", "decode"), ("lfm2-tiny", "prefill")])
def test_no_engine_program_holds_a_sort(engines, preset, kind):
    """Every body calls the one sampler, so no program sorts: what says
    the value search engaged everywhere, and that no later edit brings
    the sort back through a helper. The sampler's scope is in the text's
    place of it."""
    text = _lowered(engines[preset], kind)
    assert not re.search(r"\bsort\b", text), \
        re.findall(r".*\bsort\b.*", text)[:3]
    assert "generate.sample" in text


def test_the_reference_above_does_sort():
    """The check's own check: the same search over the reference's text
    finds its sort."""
    text = jax.jit(jax.vmap(reference_token)).lower(
        _sds((4, 128), np.float32), _sds((4,), np.float32),
        _sds((4,), np.int32), _sds((4,), np.float32),
        _sds((4, 2), np.uint32)).as_text()
    assert re.search(r"\bsort\b", text)
