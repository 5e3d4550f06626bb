"""What the Mistral-Small-4 configuration added to the benchmark:
`references/mistral4.py` against hand cases (the latent attention against a
loop over positions, the rotation, the router, the control's rounding), the
configuration's file against the catalog's row and its own counts
recomputed, the latent decode's work (harness/mla_work.py) against a hand
count, and the two new readers on an empty run and on a fixture.
"""
import json
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (os.path.join(REPO, "benchmarks"),)
                if p not in sys.path]

from harness import cells, flops, mla_work, moe_work, peaks  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
CELL = "mistral-small-4-119b.serve-longcontext-128"
# a JSON-lines catalog of published configurations, one row a model
CATALOG = os.environ.get("MODEL_CATALOG", "")
ref = cells.load_module(REPO, "benchmarks/references/mistral4.py",
                        ("serve_logits",))
CONFIG = cells.load_json(os.path.join(BENCH, "configs",
                                      "mistral-small-4-119b.json"))
ARCH = CONFIG["architecture"]
PEAKS = peaks.device_peaks("TPU v5 lite")


# ------------------------------------------------------ the reference by hand
def test_reference_imports_nothing_of_the_system():
    with open(os.path.join(BENCH, "references", "mistral4.py")) as fh:
        source = fh.read()
    assert "paddle_tpu" not in source and "import paddle" not in source
    # the expanded form alone: no pool, no absorption
    assert "absorb" not in source.split('"""')[2]


def small_sizes(**kw):
    z = dict(layers=1, heads=3, kv_lora=8, dn=4, dr=4, top_k=2,
             held=(0, 4), norm_topk_prob=True, routed_scaling=1.0, eps=1e-6,
             theta=1e4, factor=4.0, original=4, beta_fast=32.0,
             beta_slow=1.0, mscale_all_dim=1.0, llama4_beta=0.1)
    z.update(kw)
    return ref._Sizes(z)


def attention_weights(rng, D=12, z=None):
    H, dn, dr, c, dv = z["heads"], z["dn"], z["dr"], z["kv_lora"], 5

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.4, jnp.float32)

    return {"attn_norm": 1 + w(D), "q_a": w(D, 6), "q_a_norm": 1 + w(6),
            "q_b": w(6, H * (dn + dr)), "kv_a": w(D, c + dr),
            "kv_a_norm": 1 + w(c), "kv_b": w(c, H * (dn + dv)),
            "o_w": w(H * dv, D)}


def test_latent_attention_against_a_loop_over_positions():
    """s_h(t, τ) = σ (q_n·k_n + q_r·k_r), softmax over τ ≤ t, by hand in
    float64 with the rotation as 2×2 blocks on pairs (2i, 2i+1) and the
    llama-4 factor past the original length (4 here); causal."""
    rng = np.random.RandomState(0)
    z = small_sizes()
    S, D = 11, 12
    w = attention_weights(rng, D, z)
    u = jnp.asarray(rng.standard_normal((1, S, D)), jnp.float32)
    got = np.asarray(ref.latent_attention(u, w, z))
    f64 = {k: np.asarray(v, np.float64) for k, v in w.items()}
    x = np.asarray(u, np.float64)[0]
    H, dn, dr, c = z["heads"], z["dn"], z["dr"], z["kv_lora"]

    def norm(a, g):
        return a / np.sqrt(np.mean(a * a, -1, keepdims=True) + 1e-6) * g

    inv = np.asarray(ref.yarn_frequencies(dr, 1e4, 4.0, 4, 32.0, 1.0),
                     np.float64)

    def rot(v, t):
        out = v.copy()
        for i in range(dr // 2):
            a, s_ = math.cos(t * inv[i]), math.sin(t * inv[i])
            out[2 * i] = v[2 * i] * a - v[2 * i + 1] * s_
            out[2 * i + 1] = v[2 * i] * s_ + v[2 * i + 1] * a
        return out

    q = (norm(x @ f64["q_a"], f64["q_a_norm"]) @ f64["q_b"]).reshape(
        S, H, dn + dr)
    ckv = x @ f64["kv_a"]
    lat = norm(ckv[:, :c], f64["kv_a_norm"])
    kv = (lat @ f64["kv_b"]).reshape(S, H, -1)
    m2 = (1 + 0.1 * math.log(4.0)) ** 2
    sigma = m2 / math.sqrt(dn + dr)
    o = np.zeros((S, H, kv.shape[-1] - dn))
    for t in range(S):
        scale = 1 + 0.1 * math.log(1 + t // 4)
        for h in range(H):
            qn = q[t, h, :dn] * scale
            qr = rot(q[t, h, dn:] * scale, t)
            s = np.array([sigma * (qn @ kv[r, h, :dn]
                                   + qr @ rot(ckv[r, c:], r))
                          for r in range(t + 1)])
            p = np.exp(s - s.max())
            o[t, h] = (p / p.sum()) @ kv[:t + 1, h, dn:]
    want = o.reshape(S, -1) @ f64["o_w"]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    # a later position moves no earlier output
    u2 = u.at[:, 7:].add(1.0)
    again = np.asarray(ref.latent_attention(u2, w, z))
    np.testing.assert_array_equal(again[:, :7], got[:, :7])


def test_the_router_is_a_softmax_and_the_held_part_alone_is_added():
    rng = np.random.RandomState(3)
    z = small_sizes(held=(1, 2))
    T, D, F, E = 6, 10, 7, 8

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.4, jnp.float32)

    p = {"gate_w": w(D, E), "e_w1": w(2, D, F), "e_w3": w(2, D, F),
         "e_w2": w(2, F, D), "w1": w(D, F), "w3": w(D, F), "w2": w(F, D)}
    n = w(T, D)
    got = np.asarray(ref.expert_layer(n, p, z))
    f = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(n, np.float64)

    def swiglu(a, w1, w3, w2):
        g = a @ w1
        return (g / (1 + np.exp(-g)) * (a @ w3)) @ w2

    for t in range(T):
        logit = x[t] @ f["gate_w"]
        pi = np.exp(logit - logit.max())
        pi /= pi.sum()
        top = np.argsort(-pi)[:2]
        want = swiglu(x[t], f["w1"], f["w3"], f["w2"])
        for e in top:
            if 1 <= e < 3:
                want = want + pi[e] / pi[top].sum() * swiglu(
                    x[t], f["e_w1"][e - 1], f["e_w3"][e - 1],
                    f["e_w2"][e - 1])
        np.testing.assert_allclose(got[t], want, rtol=2e-4, atol=2e-5)


def test_the_control_rounds_every_products_operands():
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    exact = np.asarray(ref._mm("td,de->te", a, b, None))
    np.testing.assert_allclose(exact, np.asarray(a) @ np.asarray(b),
                               atol=1e-5)
    err = {p: np.abs(np.asarray(ref._mm("td,de->te", a, b, p))
                     - exact).max() for p in ("bfloat16", "float8")}
    assert 0 < err["bfloat16"] < err["float8"]
    # the router, stated in float32: exact under "bfloat16", rounded one
    # step down (to bfloat16 operands) under "float8"
    np.testing.assert_array_equal(
        np.asarray(ref._mm32("td,de->te", a, b, "bfloat16")),
        np.asarray(ref._mm32("td,de->te", a, b, None)))
    assert np.abs(np.asarray(ref._mm32("td,de->te", a, b, "float8"))
                  - exact).max() > 0


# ------------------------------------------------ the configuration's file
def catalog_row():
    with open(CATALOG) as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "Mistral-Small-4-119B-2603":
                return row
    pytest.skip("the catalog has no Mistral-Small-4-119B-2603 row here")


def test_the_file_holds_the_catalogs_row_but_for_what_it_lists():
    if not os.path.exists(CATALOG):
        pytest.skip("MODEL_CATALOG names no catalog file")
    row = catalog_row()
    assert CONFIG["source"] == row["source_url"]
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size", "max_position_embeddings"}
    for key, value in row["config"].items():
        if key in reduced:
            assert CONFIG[key] != value and \
                CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert set(CONFIG["reduced_how"]) == reduced
    # cut no lower than 4 layers and 8 experts; every width, head, top-k
    # and the router uncut
    assert ARCH["num_hidden_layers"] == CONFIG["num_hidden_layers"] == 8 >= 4
    assert CONFIG["n_routed_experts"] == ARCH["experts_held_count"] == 16 >= 8
    assert ARCH["n_routed_experts"] == 128 and ARCH["experts_held"] == [0, 16]
    assert CONFIG["vocab_size"] * 8 == 131072
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "rope_parameters", "rms_norm_eps"):
        assert ARCH[key] == row["config"][key], key
    assert {"scoring_func", "attention_factor", "llama_4_scaling",
            "initializers", "vision"} <= set(CONFIG["assumed"])
    assert ARCH["dtype"] == "bfloat16" and "float32" in CONFIG["precision"]
    assert "eight" in CONFIG["stands_for"]


def test_the_files_counts_are_the_architectures_arithmetic():
    """Parameters, the latent pool's bytes and the chip's fill,
    recomputed."""
    a = ARCH
    D, H, V = a["hidden_size"], a["num_attention_heads"], a["vocab_size"]
    ql, c, dn, dr, dv = (a["q_lora_rank"], a["kv_lora_rank"],
                         a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                         a["v_head_dim"])
    expert = 3 * D * a["moe_intermediate_size"]
    attention = D * ql + ql * H * (dn + dr) + D * (c + dr) \
        + c * H * (dn + dv) + H * dv * D
    assert attention == 28_049_408
    norms = 2 * D + ql + c
    layer = attention + norms + D * a["n_routed_experts"] + expert \
        + a["experts_held_count"] * expert
    assert layer == 456_402_176
    assert a["n_params"] == a["num_hidden_layers"] * layer + 2 * V * D + D \
        == 3_785_439_232
    active = a["n_params"] - a["num_hidden_layers"] * expert * (
        a["experts_held_count"] - a["experts_held_count"]
        * a["num_experts_per_tok"] / a["n_routed_experts"])
    assert a["n_params_active"] == active
    counted = CONFIG["cache_bytes"]
    engine = CONFIG["serve"]["engine"]
    assert engine["slots"] == 128 and engine["max_new_tokens_cap"] == 5120
    assert engine["max_queue_depth"] >= 2 * engine["slots"]
    # a position: the latent and the rotary key, padded to 128-lane tiles
    assert a["latent_width"] == 384 >= c + dr == 320
    assert counted["latent_row_layer"] == 2 * a["latent_width"]
    assert counted["latent_row_layer_unpadded"] == 2 * (c + dr) == 640
    assert counted["rows"] == engine["slots"] + 1
    assert counted["pool"] == counted["rows"] * a["num_hidden_layers"] \
        * a["max_seq_len"] * counted["latent_row_layer"] == 6_492_782_592
    assert counted["weights"] == 2 * a["n_params"]
    assert counted["total"] == counted["pool"] + counted["weights"]
    assert 0.75 < counted["total"] / counted["of_bytes"] < 0.9
    traffic = cells.resolve(CELL, REPO)["traffic"]
    assert traffic["clients"] == 2 * engine["slots"]
    longest = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
    assert longest == 7168 <= a["max_seq_len"]
    assert traffic["prompt_tokens"]["max"] <= max(engine["prompt_boundaries"])
    assert traffic["output_tokens"]["max"] <= engine["max_new_tokens_cap"]
    # what the check's logits cost: [4, 7168, V] float32, beside the weights
    assert 4 * longest * V * 4 < 2e9


# ------------------------------------------------- the latent decode's work
def test_mla_work_against_a_hand_count():
    assert mla_work.latent_width(ARCH) == 320
    work = mla_work.decode_work(128 * 3300, ARCH)
    assert work["bytes"] == 128 * 3300 * 8 * 320 * 2
    assert work["flops"] == 128 * 3300 * 8 * 32 * (320 + 256) * 2
    # bound by the latent's bytes, though 32 heads over one shared row take
    # a quarter of that time in FLOPs
    least = flops.roofline_seconds(work, PEAKS)
    assert least == pytest.approx(work["bytes"] / 819e9)
    assert least / 5 < work["flops"] / PEAKS["bf16_flops"] < least / 3
    # the routed experts' least work at 128 rows: 16 held experts hit
    routed = moe_work.routed_work(64, 15.7, ARCH)
    assert routed["bytes"] > 15.7 * 3 * 4096 * 2048 * 2


# ---------------------------------------------- the readers, synthetic runs
NEW = ("mla.attention.busy_share", "mla.decode_kernel.roofline_share")


def snap(steps, rows, attended):
    return {"steps_total": steps, "step_rows_total": rows,
            "tokens_out_total": rows, "attended_positions_total": attended}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_empty_run_or_the_parents(name):
    read = cells.load_reader(BENCH, name)
    assert read({"setup": {"build_s": 1.0, "cache_misses": 0}, "spans": [],
                 "trace": None}) is None
    # a served run of a program without the counter and without a trace
    parent = {"serve": {"snap0": {"steps_total": 0, "step_rows_total": 0},
                        "snap1": {"steps_total": 9, "step_rows_total": 9}},
              "trace": None, "spans": [], "cell": {"name": CELL},
              "config": CONFIG, "peaks": PEAKS}
    assert read(parent) is None


def view_of_steps(seconds_a_step, steps=3, layers=8):
    """A device on which each of `steps` decode programs runs `layers`
    `mla_decode.N` kernels sharing `seconds_a_step` and a fusion as long
    again; and a prefill program with no kernel."""
    ns = seconds_a_step * 1e9 / layers
    ops, modules, t = [], [], 0.0
    for _ in range(steps):
        start = t
        for layer in range(layers):
            ops.append((f"%mla_decode.{3 + layer} = f32[128,32,256] "
                        f"custom-call(%q, %pool)", t, t + ns, "", ns))
            t += ns
        ops.append(("%fusion.9 = f32[128,16384] fusion(%p)", t,
                    t + layers * ns, "", layers * ns))
        t += layers * ns
        modules.append(("jit_mistral4_decode_c8192_b128(3)", start, t))
    modules.append(("jit_mistral4_prefill_c8192_b2048(4)", t, t + 50))
    ops.append(("%fusion.1 = f32[2048,4096] fusion(%x)", t, t + 50, "",
                50.0))
    return {"ops": {0: ops}, "modules": {0: modules}, "spans": [],
            "window": (0.0, t + 50)}


def test_kernel_seconds_finds_the_kernel_and_the_decode_steps():
    got = mla_work.kernel_seconds(view_of_steps(1e-3))
    assert got["events"] == 24 and got["decode_steps"] == 3
    assert got["seconds"] == pytest.approx(3e-3)
    none = {"ops": {0: [("%fusion.1 = f32[8] fusion(%p)", 0.0, 9.0, "",
                         9.0)]}, "modules": {0: []}}
    assert mla_work.kernel_seconds(none) is None


@pytest.mark.parametrize("stretch, share", [(1.0, 1.0), (1.25, 0.8)])
def test_roofline_share_is_one_on_a_run_at_the_roofline(monkeypatch, stretch,
                                                        share):
    from harness import host_spans

    positions = 128 * 3300.5
    least = flops.roofline_seconds(mla_work.decode_work(positions, ARCH),
                                   PEAKS)
    view = view_of_steps(least * stretch)
    monkeypatch.setattr(host_spans, "load", lambda run: view)
    monkeypatch.setattr(host_spans, "note", lambda *a, **k: None)
    run = {"config": CONFIG, "peaks": PEAKS, "trace": {"x": 1},
           "cell": {"name": CELL},
           "serve": {"snap0": snap(0, 0, 0),
                     "snap1": snap(1000, 128000, 1000 * positions)}}
    got = cells.load_reader(BENCH, "mla.decode_kernel.roofline_share")(run)
    assert got == pytest.approx(share, rel=1e-6) and got <= 1.0 + 1e-9


def test_the_cell_lists_what_it_reports():
    res = cells.resolve(CELL, REPO)
    assert res["cell"]["chips"] == 1
    assert res["cell"]["traffic"] == "longcontext-128"
    assert {m["name"] for m in res["end_to_end"]} == {
        "serve_tokens_per_s", "itl_ms_p95", "setup_s"}
    names = {m["name"] for m in res["per_layer"]}
    assert set(NEW) <= names
    assert {"moe.experts.busy_share", "moe.experts.roofline_share",
            "moe.distinct_experts_per_layer_step",
            "moe.expert_load_max_over_mean", "engine.rows_per_step",
            "engine.pass_cadence_ms_p50", "engine.decode_program_ms_p50",
            "device.idle_share.serve", "setup.build_s"} <= names
    assert not {n for n in names if n.startswith(("lfm2.", "retention.",
                                                  "flash_", "train_"))}
    # its span closes before its program does at 128 rows: no reading
    assert "engine.decode_step.mfu" not in names
    # a closed loop with 128 requests queued: a request enqueued in the
    # window waits ~128 completions, longer than the window, so no
    # `generate.queue_wait` span begins inside it
    assert "engine.queue_wait_ms_p90" not in names
    bench = cells.load_benchmark(REPO)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == \
                "latent attention" and m["moves"] == "serve_tokens_per_s"
    assert ARCH["num_dense_layers"] == 0
    assert CONFIG["serve"]["check"]["control"] == "float8"


def test_built_by_holds_key_for_key():
    import importlib

    by = CONFIG["built_by"]
    module, attr = by["table"].split(":")
    preset = getattr(importlib.import_module(module), attr)[by["preset"]]
    assert by["preset"] == CONFIG["name"] == CONFIG["serve"]["preset"]
    assert len(by["sizes"]) >= 10
    for key, theirs in by["sizes"].items():
        assert ARCH[key] == getattr(preset, theirs), key
