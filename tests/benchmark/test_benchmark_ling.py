"""What the Ling-3.0-flash configuration added to the benchmark:
`references/ling.py` against hand cases (the delta rule against a loop in
float64, the group-limited router, the control's rounding), the
configuration's file against the catalog's row and its own counts
recomputed, the traffic against the engine's limits, the delta rule's work
(harness/kda_work.py) against a hand count, and the three new readers on an
empty run, on a parent's run and on fixtures.
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

from harness import cells, flops, kda_work, moe_work, peaks  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
CELL = "ling-3.0-flash.longcontext-256"
# a JSON-lines catalog of published configurations, one row a model
CATALOG = os.environ.get("MODEL_CATALOG", "")
ref = cells.load_module(REPO, "benchmarks/references/ling.py",
                        ("serve_logits",))
CONFIG = cells.load_json(os.path.join(BENCH, "configs",
                                      "ling-3.0-flash.json"))
ARCH = CONFIG["architecture"]
PEAKS = peaks.device_peaks("TPU v5 lite")
NEW = ("kda.state_step.roofline_share", "kda.state_step.busy_share",
       "kda.prefill.busy_share")


# ------------------------------------------------------ the reference by hand
def test_reference_imports_nothing_of_the_system():
    with open(os.path.join(BENCH, "references", "ling.py")) as fh:
        source = fh.read()
    assert "paddle_tpu" not in source and "import paddle" not in source
    # the delta rule's definition, position by position: no chunk
    body = source.split('"""', 2)[2]
    assert "lax.scan" in body and "chunk" not in body


def test_the_delta_rule_against_a_loop_in_float64():
    """`S_t = (I − β k kᵀ) Diag(e^g) S_{t−1} + β k vᵀ`, `o_t = S_tᵀ q_t`,
    by hand in float64, two rows of three heads."""
    rng = np.random.RandomState(0)
    B, S, H, dk, dv = 2, 9, 3, 4, 5
    q, k = (rng.standard_normal((B, S, H, dk)) for _ in range(2))
    v = rng.standard_normal((B, S, H, dv))
    g = -5.0 * rng.uniform(0, 1, (B, S, H, dk))
    beta = rng.uniform(0, 1, (B, S, H))
    got = np.asarray(ref.delta_rule(*(jnp.asarray(x, jnp.float32)
                                      for x in (q, k, v, g, beta))))
    want = np.zeros((B, S, H, dv))
    for b in range(B):
        for h in range(H):
            st = np.zeros((dk, dv))
            for t in range(S):
                kk = k[b, t, h][:, None]
                st = (np.eye(dk) - beta[b, t, h] * kk @ kk.T) \
                    @ np.diag(np.exp(g[b, t, h])) @ st \
                    + beta[b, t, h] * kk @ v[b, t, h][None]
                want[b, t, h] = st.T @ q[b, t, h]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_router_limits_the_groups():
    """16 experts in 4 groups, the 2 best kept: a token whose best single
    expert lies in a group whose two best sum low never takes it; weights
    are the sigmoid scores of the chosen, normalised, times the scale."""
    z = ref._Sizes(top_k=2, n_group=4, topk_group=2, held=(0, 16),
                   norm_topk_prob=True, routed_scaling=2.5)
    logits = np.full((1, 16), -4.0)
    logits[0, 0] = 3.0                         # group 0: one high, one low
    logits[0, 4:6] = [1.0, 1.0]                # group 1: two middling
    logits[0, 8:10] = [0.9, 0.9]               # group 2
    n = jnp.eye(16, dtype=jnp.float32)[:1]
    w = jnp.zeros((16, 16), jnp.float32).at[0].set(logits[0])
    chosen, weight = ref.route(n, w, jnp.zeros(16), z)
    assert sorted(np.asarray(chosen[0]).tolist()) == [4, 5]
    np.testing.assert_allclose(np.asarray(weight[0]), [1.25, 1.25],
                               rtol=1e-5)
    open_ = ref._Sizes(dict(z, topk_group=4))
    assert 0 in np.asarray(ref.route(n, w, jnp.zeros(16), open_)[0][0]
                           ).tolist()


def test_the_control_rounds_every_products_operands_and_the_state():
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    exact = np.asarray(ref._mm("td,de->te", a, b, None))
    err = {p: np.abs(np.asarray(ref._mm("td,de->te", a, b, p))
                     - exact).max() for p in ("bfloat16", "float8")}
    assert 0 < err["bfloat16"] < err["float8"]
    # any other name (a planted fault's) computes at full precision
    np.testing.assert_array_equal(np.asarray(ref._mm("td,de->te", a, b,
                                                     "no_delta")), exact)
    # the delta rule's state: float32 but under "float8"
    x = [jnp.asarray(rng.standard_normal((1, 6, 2, 4)), jnp.float32)
         for _ in range(3)]
    g = jnp.asarray(-rng.uniform(0, 1, (1, 6, 2, 4)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (1, 6, 2)), jnp.float32)
    full = np.asarray(ref.delta_rule(*x, g, beta))
    np.testing.assert_array_equal(
        np.asarray(ref.delta_rule(*x, g, beta, "bfloat16")), full)
    assert np.abs(np.asarray(ref.delta_rule(*x, g, beta, "float8"))
                  - full).max() > 0


# ------------------------------------------------ the configuration's file
def catalog_row():
    with open(CATALOG) as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "Ling-3.0-flash":
                return row
    pytest.skip("the catalog has no Ling-3.0-flash row here")


def test_the_file_holds_the_catalogs_row_but_for_what_it_lists():
    if not os.path.exists(CATALOG):
        pytest.skip("MODEL_CATALOG names no catalog file")
    row = catalog_row()
    assert CONFIG["source"] == row["source_url"]
    reduced = set(CONFIG["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert CONFIG[key] != value and \
                CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    for key in ("hidden_size", "num_attention_heads", "head_dim",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "n_group", "topk_group", "routed_scaling_factor",
                "short_conv_kernel_size", "kda_lower_bound", "rope_theta",
                "moe_shared_expert_intermediate_size", "rms_norm_eps"):
        assert ARCH[key] == row["config"][key], key
    assert ARCH["num_experts"] == row["config"]["num_experts"]


def test_the_cut_keeps_every_width_and_a_whole_period():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size",
                       "num_nextn_predict_layers",
                       "max_position_embeddings"}
    assert set(CONFIG["reduced_how"]) == reduced
    assert CONFIG["num_hidden_layers"] == ARCH["num_hidden_layers"] == 7
    assert CONFIG["num_experts"] == ARCH["experts_held_count"] == 64 >= 8
    assert ARCH["experts_held"] == [0, 64] and ARCH["num_experts"] == 512
    # the held experts are one routing group
    assert 512 // ARCH["n_group"] == 64
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    # layer i is MLA where (i + 1) % layer_group_size == 0: 5 of 0..7
    period = CONFIG["layer_group_size"]
    kinds = ["mla" if (i + 1) % period == 0 else "kda"
             for i in ARCH["published_layers"]]
    assert kinds == ARCH["layer_types"]
    assert ARCH["published_layers"] == [0, 2, 3, 4, 5, 6, 7]
    # the leading dense layers count once; four and more layers after
    assert ARCH["num_dense_layers"] == 1
    assert len(ARCH["layer_types"]) - ARCH["num_dense_layers"] >= 4
    held = ARCH["published_layers"]
    assert not any(CONFIG["expert_swiglu_limit_list"][i]
                   or CONFIG["share_expert_swiglu_limit_list"][i]
                   for i in held)
    assert {"layer_pattern", "kda_gate", "kda_output", "router",
            "swiglu_limits", "initializers", "mtp"} <= set(CONFIG["assumed"])
    assert ARCH["dtype"] == "bfloat16" and "float32" in CONFIG["precision"]
    assert "eight" in CONFIG["stands_for"]


def test_the_files_counts_are_the_architectures_arithmetic():
    """Parameters, the latent pool's and the state's bytes and the chip's
    fill, recomputed."""
    a = ARCH
    D, H, dk, V = (a["hidden_size"], a["num_attention_heads"],
                   a["head_dim"], a["vocab_size"])
    HD = H * dk
    expert = 3 * D * a["moe_intermediate_size"]
    kda_part = 5 * D * HD + 3 * HD * a["short_conv_kernel_size"] \
        + 2 * D * H + H + 2 * HD + 2 * D
    c, dn, dr, dv = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                     a["qk_rope_head_dim"], a["v_head_dim"])
    mla_part = D * H * (dn + dr) + D * (c + dr) + c + c * H * (dn + dv) \
        + D * H + H * dv * D + 2 * D
    moe_part = D * a["num_experts"] + a["num_experts"] + expert \
        + a["experts_held_count"] * expert
    total = kda_part + 3 * D * a["intermediate_size"] \
        + 5 * (kda_part + moe_part) + mla_part + moe_part + 2 * V * D + D
    assert a["n_params"] == total == 2_803_868_864
    layers = len(a["layer_types"]) - a["num_dense_layers"]
    assert a["n_params_active"] == total - layers * expert * (
        a["experts_held_count"] - a["experts_held_count"]
        * a["num_experts_per_tok"] // a["num_experts"])
    counted = CONFIG["cache_bytes"]
    engine = CONFIG["serve"]["engine"]
    assert engine["slots"] == 256
    # a closed loop of 384 clients arrives at once: none may be shed
    traffic = cells.resolve(CELL, REPO)["traffic"]
    assert engine["max_queue_depth"] >= traffic["clients"]
    rows = engine["slots"] + 1
    assert counted["rows"] == rows
    assert a["latent_width"] == 640 >= c + dr == 576
    assert counted["latent_row_layer"] == 2 * a["latent_width"]
    assert counted["pool"] == rows * counted["mla_layers"] \
        * a["max_seq_len"] * counted["latent_row_layer"] == 2_694_840_320
    tiles = -(-H * 3 * (a["short_conv_kernel_size"] - 1) // dk)
    assert tiles == 3
    assert counted["kda_matrix_row_layer"] == H * dk * dv * 4 == 2 * 2**20
    assert counted["kda_matrices"] == rows * 6 * H * dk * dv * 4
    assert counted["kda_window_tiles"] == rows * 6 * tiles * dk * dv * 4
    assert counted["kda_windows"] == rows * 6 * H * 9 * dv * 4
    assert counted["kda_state"] == counted["kda_matrices"] \
        + counted["kda_window_tiles"] == 3_536_977_920
    assert counted["weights"] == 2 * a["n_params"]
    assert counted["total"] == counted["pool"] + counted["kda_state"] \
        + counted["weights"]
    assert 0.6 < counted["total"] / counted["of_bytes"] < 0.8


def test_the_traffic_fits_the_engine():
    traffic = cells.resolve(CELL, REPO)["traffic"]
    engine = CONFIG["serve"]["engine"]
    assert "serve" not in CELL.split(".", 1)[1]
    assert traffic["clients"] == engine["slots"] * 3 // 2
    longest = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
    assert longest == 7168 <= ARCH["max_seq_len"]
    assert traffic["prompt_tokens"]["max"] <= max(engine["prompt_boundaries"])
    assert traffic["output_tokens"]["max"] <= engine["max_new_tokens_cap"]
    assert traffic["greedy_every"] == 4
    # what the check's logits cost: [4, 7168, V] float32, beside the weights
    assert 4 * longest * ARCH["vocab_size"] * 4 < 2.5e9


def test_built_by_holds_key_for_key():
    import importlib

    by = CONFIG["built_by"]
    module, attr = by["table"].split(":")
    preset = getattr(importlib.import_module(module), attr)[by["preset"]]
    assert by["preset"] == CONFIG["name"] == CONFIG["serve"]["preset"]
    assert len(by["sizes"]) >= 20
    for key, theirs in by["sizes"].items():
        assert ARCH[key] == getattr(preset, theirs), key
    assert list(preset.layer_types) == ARCH["layer_types"]


# ------------------------------------------------------- the delta rule's work
def test_kda_work_against_a_hand_count():
    work = kda_work.step_work(256, ARCH)
    assert work["bytes"] == 256 * 32 * 128 * 128 * 4 * 2
    assert work["flops"] == 256 * 32 * 128 * 128 * 7
    # bound by the state's bytes: the VPU's seven operations an element
    # are far under the bf16 peak's time
    least = flops.roofline_seconds(work, PEAKS)
    assert least == pytest.approx(work["bytes"] / 819e9)
    assert work["flops"] / PEAKS["bf16_flops"] < least / 50


# ---------------------------------------------- the readers, synthetic runs
def snap(steps, rows):
    return {"steps_total": steps, "step_rows_total": rows,
            "tokens_out_total": rows}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_empty_run_or_the_parents(name):
    read = cells.load_reader(BENCH, name)
    assert read({"setup": {"build_s": 1.0, "cache_misses": 0}, "spans": [],
                 "trace": None}) is None
    parent = {"serve": {"snap0": snap(0, 0), "snap1": snap(9, 9)},
              "trace": None, "spans": [], "cell": {"name": CELL},
              "config": CONFIG, "peaks": PEAKS}
    assert read(parent) is None


def view_of_steps(seconds_a_step, steps=3, layers=6):
    """A device on which each of `steps` decode programs runs `layers`
    `kda_step.N` kernels sharing `seconds_a_step` and a fusion as long
    again; and a prefill program with no kernel."""
    ns = seconds_a_step * 1e9 / layers
    ops, modules, t = [], [], 0.0
    for _ in range(steps):
        start = t
        for layer in range(layers):
            ops.append((f"%kda_step.{3 + layer} = f32[256,256,128] "
                        f"custom-call(%x, %state)", t, t + ns, "", ns))
            t += ns
        ops.append(("%fusion.9 = f32[256,19648] fusion(%p)", t,
                    t + layers * ns, "", layers * ns))
        t += layers * ns
        modules.append(("jit_ling_decode_c8192_b256(3)", start, t))
    modules.append(("jit_ling_prefill_c8192_b1024(4)", t, t + 50))
    ops.append(("%fusion.1 = f32[1024,2560] fusion(%x)", t, t + 50, "",
                50.0))
    return {"ops": {0: ops}, "modules": {0: modules}, "spans": [],
            "window": (0.0, t + 50)}


def test_kernel_seconds_finds_the_kernel():
    got = kda_work.kernel_seconds(view_of_steps(1e-3))
    assert got["events"] == 18
    assert got["seconds"] == pytest.approx(3e-3)
    assert got["busy_s"] == pytest.approx(6e-3 + 50e-9)
    none = {"ops": {0: [("%fusion.1 = f32[8] fusion(%p)", 0.0, 9.0, "",
                         9.0)]}, "modules": {0: []}}
    assert kda_work.kernel_seconds(none) is None


@pytest.mark.parametrize("stretch, share", [(1.0, 1.0), (1.25, 0.8)])
def test_roofline_share_is_one_on_a_run_at_the_roofline(monkeypatch, stretch,
                                                        share):
    from harness import host_spans

    rows = 255.5
    least = flops.roofline_seconds(kda_work.step_work(rows, ARCH), PEAKS)
    view = view_of_steps(least * 6 * stretch)
    monkeypatch.setattr(host_spans, "load", lambda run: view)
    monkeypatch.setattr(host_spans, "note", lambda *a, **k: None)
    run = {"config": CONFIG, "peaks": PEAKS, "trace": {"x": 1},
           "cell": {"name": CELL},
           "serve": {"snap0": snap(0, 0), "snap1": snap(1000, 255500)}}
    got = cells.load_reader(BENCH, "kda.state_step.roofline_share")(run)
    assert got == pytest.approx(share, rel=1e-6) and got <= 1.0 + 1e-9
    busy = cells.load_reader(BENCH, "kda.state_step.busy_share")(run)
    assert busy == pytest.approx(0.5, rel=1e-4)


def test_prefill_share_reads_the_scope_by_program(monkeypatch):
    """The prefill's operations joined to `kda.prefill` through the
    program's own map: a third of the prefill program's time here."""
    from harness import host_spans

    view = view_of_steps(1e-3, steps=1)
    view["ops"][0][-1] = ("%fusion.1 = f32[1024,2560] fusion(%x)",
                          6e6, 6e6 + 30, "", 30.0)
    view["ops"][0].append(("%fusion.2 = f32[16,32,64,64] fusion(%y)",
                           6e6 + 30, 6e6 + 50, "", 20.0))
    view["modules"][0][-1] = ("jit_ling_prefill_c8192_b1024(4)", 6e6,
                              6e6 + 50)
    maps = {"jit_ling_prefill_c8192_b1024": {
        "fusion.1": "jit(x)/kda.conv/mul",
        "fusion.2": "jit(x)/kda.prefill/while/body/dot"},
        "jit_ling_decode_c8192_b256": {
        "fusion.9": "jit(x)/moe.experts/dot"}}
    monkeypatch.setattr(host_spans, "load", lambda run: view)
    monkeypatch.setattr(host_spans, "note", lambda *a, **k: None)
    monkeypatch.setattr(moe_work, "program_scopes", lambda: maps)
    got = cells.load_reader(BENCH, "kda.prefill.busy_share")({})
    assert got == pytest.approx(20e-9 / (2e-3 + 50e-9), rel=1e-6)


def test_the_cell_lists_what_it_reports():
    res = cells.resolve(CELL, REPO)
    assert res["cell"]["chips"] == 1
    assert res["cell"]["traffic"] == "longcontext-256"
    # itl_ms_p95 spread 8.8 % over four seeds on the chip, over half its
    # bound: the cell reports tokens/s, and no metric that moves itl
    assert {m["name"] for m in res["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in res["per_layer"]}
    assert set(NEW) <= names
    assert {"moe.experts.busy_share", "moe.experts.roofline_share",
            "moe.distinct_experts_per_layer_step",
            "moe.expert_load_max_over_mean", "engine.rows_per_step",
            "engine.pass_cadence_ms_p50", "setup.build_s",
            "compile_cache.setup_misses"} == names - set(NEW)
    for m in res["per_layer"]:
        assert m["moves"] in ("serve_tokens_per_s", "setup_s"), m["name"]
    bench = cells.load_benchmark(REPO)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == "KDA state" \
                and m["moves"] == "serve_tokens_per_s" \
                and m["unit"] == "share"
    assert CONFIG["serve"]["check"]["control"] == "float8"
    assert 0 < CONFIG["serve"]["check"]["logit_tol_std"] < 1
    assert math.isfinite(CONFIG["serve"]["check"]["logit_tol_std_p90"])
