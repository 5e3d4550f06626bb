"""What PR 35 added to the benchmark: `references/brumby.py` against hand
cases (the power retention against a loop over positions, grouped heads,
the control's rounding), the configuration's file against the catalog's row
and its own byte counts recomputed from `architecture`, the retention
state's work (harness/retention_work.py) against a hand count, and the
three new readers on an empty run and on a fixture.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (os.path.join(REPO, "benchmarks"),)
                if p not in sys.path]

from harness import cells, flops, peaks, retention_work  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
CELL = "brumby-14b-base.serve-longanswer-16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ref = cells.load_module(REPO, "benchmarks/references/brumby.py",
                        ("serve_logits",))
CONFIG = cells.load_json(os.path.join(BENCH, "configs",
                                      "brumby-14b-base.json"))
ARCH = CONFIG["architecture"]
PEAKS = peaks.device_peaks("TPU v5 lite")


# ------------------------------------------------------ the reference by hand
def test_reference_imports_nothing_of_the_system():
    with open(os.path.join(BENCH, "references", "brumby.py")) as fh:
        source = fh.read()
    assert "paddle_tpu" not in source
    assert "import paddle" not in source
    # the attention form alone: no state, no recurrence
    assert "scan" not in source and "fori_loop" not in source


@pytest.mark.parametrize("groups", [1, 5])
def test_power_retention_against_a_loop_over_positions(groups):
    """y_t = sum_s a_ts v_s / sum_s a_ts with a_ts = (q_t.k_s/sqrt(Dh))^2
    prod_{s<r<=t} g_r, by hand in float64; query head h on K/V head
    h // groups; causal: a later position moves no earlier output."""
    rng = np.random.RandomState(0)
    S, Hkv, Dh = 9, 2, 8
    H = Hkv * groups
    q = rng.standard_normal((1, S, H, Dh)).astype(np.float32)
    k = rng.standard_normal((1, S, Hkv, Dh)).astype(np.float32)
    v = rng.standard_normal((1, S, Hkv, Dh)).astype(np.float32)
    log_g = np.log(rng.uniform(0.2, 0.95, (1, S, Hkv))).astype(np.float32)
    got = np.asarray(ref.power_retention(*(jnp.asarray(a) for a in (
        q, k, v, log_g))))
    for t in range(S):
        for h in range(H):
            j = h // groups
            num, den = np.zeros(Dh), 0.0
            for s in range(t + 1):
                decay = np.exp(np.sum(log_g[0, s + 1:t + 1, j],
                                      dtype=np.float64))
                a = (np.dot(q[0, t, h].astype(np.float64), k[0, s, j])
                     / np.sqrt(Dh)) ** 2 * decay
                num, den = num + a * v[0, s, j], den + a
            np.testing.assert_allclose(got[0, t, h], num / den, rtol=2e-4,
                                       atol=2e-5)
    q2, k2 = q.copy(), k.copy()
    q2[:, 6:] += 1.0
    k2[:, 6:] -= 1.0
    again = np.asarray(ref.power_retention(*(jnp.asarray(a) for a in (
        q2, k2, v, log_g))))
    np.testing.assert_array_equal(again[:, :6], got[:, :6])
    # the first position sees itself alone: its own value, whatever q.k
    np.testing.assert_allclose(
        got[0, 0], np.repeat(v[0, 0], groups, axis=0), rtol=1e-5)


def test_the_control_rounds_every_products_operands():
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.standard_normal((1, 4, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    exact = np.asarray(ref._mm("bsd,de->bse", a, b, None))
    np.testing.assert_allclose(exact, np.asarray(a) @ np.asarray(b),
                               atol=1e-5)
    err = {p: np.abs(np.asarray(ref._mm("bsd,de->bse", a, b, p))
                     - exact).max() for p in ("bfloat16", "float8")}
    assert 0 < err["bfloat16"] < err["float8"]
    # a product the configuration states in float32 stays exact under
    # "bfloat16" (what the program does) and is rounded one step down,
    # to bfloat16, under "float8"
    same = np.asarray(ref._mm32("bsd,de->bse", a, b, "bfloat16"))
    np.testing.assert_array_equal(same, np.asarray(
        ref._mm32("bsd,de->bse", a, b, None)))
    below = np.asarray(ref._mm32("bsd,de->bse", a, b, "float8"))
    assert 0 < np.abs(below - exact).max() <= 2 * err["bfloat16"] + 1e-6


def test_serve_logits_runs_the_whole_block_in_order():
    """A one-layer model by hand: embedding, the retention operator on the
    normed stream, the MLP on the normed stream, the final norm, the
    untied head."""
    rng = np.random.RandomState(2)
    D, Dh, H, Hkv, F, V, S = 16, 8, 4, 2, 24, 11, 5
    arch = {"num_hidden_layers": 1, "num_attention_heads": H,
            "num_key_value_heads": Hkv, "head_dim": Dh,
            "rms_norm_eps": 1e-6, "rope_theta": 1e4}

    def w(*shape, scale=0.3):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    p = {"embed": w(V, D, scale=1.0), "0.operator_norm": 1 + w(D),
         "0.ffn_norm": 1 + w(D), "0.q_w": w(D, H * Dh),
         "0.k_w": w(D, Hkv * Dh), "0.v_w": w(D, Hkv * Dh),
         "0.g_w": w(D, Hkv), "0.o_w": w(H * Dh, D), "0.q_norm": 1 + w(Dh),
         "0.k_norm": 1 + w(Dh), "0.w1": w(D, F), "0.w3": w(D, F),
         "0.w2": w(F, D), "final_norm": 1 + w(D), "head": w(D, V)}
    ids = rng.randint(0, V, (2, S))
    got = np.asarray(ref.serve_logits(p, ids, {"architecture": arch}))
    x = p["embed"][ids]
    u = ref.rms_norm(x, p["0.operator_norm"], 1e-6)
    x = x + ref.retention_operator(
        u, p["0.q_w"], p["0.k_w"], p["0.v_w"], p["0.g_w"], p["0.o_w"],
        p["0.q_norm"], p["0.k_norm"], n_heads=H, n_kv=Hkv, head_dim=Dh,
        eps=1e-6, theta=1e4)
    x = x + ref.swiglu(ref.rms_norm(x, p["0.ffn_norm"], 1e-6), p["0.w1"],
                       p["0.w3"], p["0.w2"])
    want = ref.rms_norm(x, p["final_norm"], 1e-6) @ p["head"]
    assert got.shape == (2, S, V)
    np.testing.assert_allclose(got, want, atol=1e-4)
    # a later token moves no earlier position's logits
    ids2 = ids.copy()
    ids2[:, 3:] = (ids2[:, 3:] + 1) % V
    again = np.asarray(ref.serve_logits(p, ids2, {"architecture": arch}))
    np.testing.assert_array_equal(again[:, :3], got[:, :3])


# ------------------------------------------------ the configuration's file
def catalog_row():
    with open(CATALOG) as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "Brumby-14B-Base":
                return row
    pytest.skip("the catalog has no Brumby-14B-Base row here")


def test_the_file_holds_the_catalogs_row_but_for_what_it_lists():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    row = catalog_row()
    assert CONFIG["source"] == row["source_url"]
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "max_position_embeddings"}
    for key, value in row["config"].items():
        for where in (CONFIG, ARCH):    # the top level, and what the harness reads
            if key in reduced:
                assert where[key] != value, key
            else:
                assert where[key] == value, key
    assert set(CONFIG["reduced_how"]) == reduced
    # depth and positions alone: the guide's floors, every width uncut
    assert ARCH["num_hidden_layers"] == 8 >= 4
    assert CONFIG["published"]["num_hidden_layers"] == 40
    assert (ARCH["hidden_size"], ARCH["head_dim"], ARCH["vocab_size"],
            ARCH["intermediate_size"]) == (5120, 128, 151936, 17408)
    assert {"power", "gate", "score_scale", "qk_norm", "state_layout",
            "initializers"} <= set(CONFIG["assumed"])
    assert "bfloat16" in CONFIG["precision"] \
        and "float32" in CONFIG["precision"]
    assert ARCH["dtype"] == "bfloat16" and ARCH["state_dtype"] == "float32"
    assert "pipeline" in CONFIG["stands_for"]


def test_the_files_counts_are_the_architectures_arithmetic():
    """Parameters, state bytes and the chip's fill, recomputed."""
    a = ARCH
    D, Dh, F, V = a["hidden_size"], a["head_dim"], a["intermediate_size"], \
        a["vocab_size"]
    H, Hkv = a["num_attention_heads"], a["num_key_value_heads"]
    layer = (2 * D * H * Dh + 2 * D * Hkv * Dh + D * Hkv + 2 * Dh
             + 3 * D * F + 2 * D)
    assert layer == 330_352_896
    assert a["n_params"] == a["num_hidden_layers"] * layer + 2 * V * D + D \
        == 4_198_652_928
    counted = CONFIG["cache_bytes"]
    engine = CONFIG["serve"]["engine"]
    assert engine == {"slots": 16, "max_new_tokens_cap": 640}
    # the state as it is laid out: 8 x 8 tiles of the second power, and the
    # normaliser's square, float32
    tiles = Dh // 8
    assert a["state_rows"] == 32 * tiles * (tiles + 1) + Dh == 8832
    assert counted["state_row_layer"] == Hkv * a["state_rows"] * Dh * 4 \
        == 36_175_872
    assert counted["rows"] == engine["slots"] + 1
    assert counted["state"] == counted["rows"] * a["num_hidden_layers"] \
        * counted["state_row_layer"]
    # bfloat16 weights, the gate's projection in float32
    assert counted["weights"] == 2 * a["n_params"] \
        + 2 * a["num_hidden_layers"] * D * Hkv
    assert counted["total"] == counted["state"] + counted["weights"]
    fill = counted["total"] / counted["of_bytes"]
    assert 0.70 < fill < 0.85 and f"{100 * fill:.1f} %" in \
        CONFIG["stands_for"]
    # the bare second power the roofline counts is 94 % of what is moved
    assert retention_work.state_elements_per_row_layer(a) * 4 \
        == 34_080_768 < counted["state_row_layer"]
    traffic = cells.resolve(CELL, REPO)["traffic"]
    assert traffic["clients"] == 2 * engine["slots"]
    assert traffic["prompt_tokens"]["max"] + \
        traffic["output_tokens"]["max"] == 768 <= a["max_seq_len"]
    assert traffic["output_tokens"]["max"] <= engine["max_new_tokens_cap"]
    # what the check's logits cost: [4, 768, V] float32, beside the weights
    assert 4 * 768 * V * 4 < 2e9


# ---------------------------------------------- the retention state's work
def test_retention_work_against_a_hand_count():
    # a K/V head: 128 * 129 / 2 = 8,256 products by 128 values + 1
    assert retention_work.state_elements_per_row_layer(ARCH) \
        == 8 * 8256 * 129
    work = retention_work.step_work(16, ARCH)
    assert work["bytes"] == 16 * 8 * 8256 * 129 * 4 * 2 == 1_090_584_576
    assert work["flops"] == 16 * 8 * 8256 * 129 * (3 + 2 * 5)
    # bound by the state's bytes, by two orders of magnitude
    least = flops.roofline_seconds(work, PEAKS)
    assert least == pytest.approx(work["bytes"] / 819e9)
    assert work["flops"] / PEAKS["bf16_flops"] < least / 100
    # half the rows, half the work
    assert retention_work.step_work(8, ARCH)["bytes"] * 2 == work["bytes"]


# ---------------------------------------------- the readers, synthetic runs
NEW = ("retention.state_step.busy_share",
       "retention.state_step.roofline_share",
       "retention.state_bytes_per_token")


def snap(steps, rows, tokens, state_bytes):
    return {"steps_total": steps, "step_rows_total": rows,
            "tokens_out_total": tokens,
            "state_bytes_moved_total": state_bytes}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_empty_run_or_the_parents(name):
    read = cells.load_reader(BENCH, name)
    assert read({"setup": {"build_s": 1.0, "cache_misses": 0}, "spans": [],
                 "trace": None}) is None
    # a served run of a program without the counter and without a trace
    parent = {"serve": {"snap0": {"steps_total": 0, "tokens_out_total": 0},
                        "snap1": {"steps_total": 9, "tokens_out_total": 9}},
              "trace": None, "spans": [], "cell": {"name": CELL},
              "config": CONFIG, "peaks": PEAKS}
    assert read(parent) is None
    # a model that keeps no state: the counter is there and does not move
    stateless = dict(parent, serve={"snap0": snap(8, 64, 64, 0),
                                    "snap1": snap(80, 640, 640, 0)})
    assert read(stateless) is None


def test_bytes_per_token_on_a_window():
    row = CONFIG["cache_bytes"]["state_row_layer"] * 2 * 8
    run = {"serve": {"snap0": snap(10, 160, 170, 160 * row),
                     "snap1": snap(1010, 16160, 16170, 16160 * row)}}
    got = cells.load_reader(BENCH, "retention.state_bytes_per_token")(run)
    assert got == row == 578_813_952
    assert retention_work.counters_delta(run) == {
        "state_bytes": 16000 * row, "tokens": 16000, "rows": 16000,
        "steps": 1000}


def view_of_steps(seconds_a_layer_step, steps=3, layers=8):
    """A device on which each of `steps` decode steps runs `layers`
    `retention_step.N` kernels of `seconds_a_layer_step` each and a fusion
    as long again as all of them; and a custom-call of another name."""
    ns = seconds_a_layer_step * 1e9
    ops, t = [], 0.0
    for _ in range(steps):
        for layer in range(layers):
            ops.append((f"%retention_step.{8 + layer} = (f32[16,8,8,128], "
                        f"f32[17,8,8,8832,128]) custom-call(%a, %b)",
                        t, t + ns, "", ns))
            t += ns
        ops.append(("%fusion.72 = f32[16,151936] fusion(%p)", t,
                    t + layers * ns, "", layers * ns))
        t += layers * ns
    ops.append(("%decode_attn.4 = f32[8,1024] custom-call(%q)", t, t + 50,
                "", 50.0))
    return {"ops": {0: ops}, "modules": {0: []}, "spans": [],
            "window": (0.0, t + 50)}


def test_kernel_seconds_finds_the_kernel_by_its_name():
    view = view_of_steps(1e-3)
    got = retention_work.kernel_seconds(view)
    assert got["events"] == 24
    assert got["seconds"] == pytest.approx(24e-3)
    assert got["busy_s"] == pytest.approx(48e-3 + 50e-9)
    none = {"ops": {0: [("%fusion.1 = f32[8] fusion(%p)", 0.0, 9.0, "",
                         9.0)]}}
    assert retention_work.kernel_seconds(none) is None


@pytest.mark.parametrize("stretch, share", [(1.0, 1.0), (1.25, 0.8)])
def test_roofline_share_is_one_on_a_run_at_the_roofline(monkeypatch, stretch,
                                                        share):
    from harness import host_spans

    least = flops.roofline_seconds(retention_work.step_work(15.5, ARCH),
                                   PEAKS)
    view = view_of_steps(least * stretch)
    monkeypatch.setattr(host_spans, "load", lambda run: view)
    monkeypatch.setattr(host_spans, "note", lambda *a, **k: None)
    run = {"config": CONFIG, "peaks": PEAKS, "trace": {"x": 1},
           "cell": {"name": CELL},
           "serve": {"snap0": snap(0, 0, 0, 0),
                     "snap1": snap(1000, 15500, 15500, 1)}}
    got = cells.load_reader(BENCH, "retention.state_step.roofline_share")(
        run)
    assert got == pytest.approx(share, rel=1e-6) and got <= 1.0
    busy = cells.load_reader(BENCH, "retention.state_step.busy_share")(run)
    assert busy == pytest.approx(0.5, rel=1e-4)


def test_the_cell_lists_what_it_reports():
    res = cells.resolve(CELL, REPO)
    assert res["cell"]["chips"] == 1
    assert res["cell"]["traffic"] == "serve-longanswer-16"
    assert {m["name"] for m in res["end_to_end"]} == {
        "serve_tokens_per_s", "itl_ms_p95", "setup_s"}
    names = {m["name"] for m in res["per_layer"]}
    assert set(NEW) <= names
    assert {"engine.rows_per_step", "engine.decode_step_ms_p50",
            "engine.prefill_ms_p50", "engine.ttft_ms_p90",
            "engine.queue_wait_ms_p90", "engine.host_ms_per_step_p50",
            "engine.decode_step.mfu", "device.idle_share.serve",
            "device.idle_attributed_share.serve", "setup.build_s",
            "compile_cache.setup_misses"} <= names
    # no other cell reports the retention's metrics, and this one reports
    # no expert's or attention kernel's
    bench = cells.load_benchmark(REPO)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == \
                "retention state" and m["moves"] == "serve_tokens_per_s"
    assert not {n for n in names if n.startswith(("moe.", "lfm2.",
                                                  "flash_"))}
    check = CONFIG["serve"]["check"]
    assert check["control"] == "float8"
    probe = cells.load_module(REPO, CONFIG["serve"]["program_memory"],
                              ("program_temp_bytes",))

    class Engine:
        def program_memory(self):
            return {"temp_bytes": 7838720}

    assert probe.program_temp_bytes(Engine()) == 7838720


def test_built_by_holds_key_for_key():
    import importlib

    by = CONFIG["built_by"]
    module, attr = by["table"].split(":")
    preset = getattr(importlib.import_module(module), attr)[by["preset"]]
    assert by["preset"] == CONFIG["name"] == CONFIG["serve"]["preset"]
    assert len(by["sizes"]) >= 10
    for key, theirs in by["sizes"].items():
        assert ARCH[key] == getattr(preset, theirs), key
