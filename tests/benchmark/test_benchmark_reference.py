"""The GPT reference (benchmarks/references/gpt.py, loaded by path as the
harness loads it) against a two-layer case worked out by hand (loops
over positions and heads in numpy, float64), against the system's own
model at a tiny size, and the operations-and-bytes functions."""
import math
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks"), REPO]

from harness import cells, flops, peaks  # noqa: E402

reference = cells.load_module(REPO, "benchmarks/references/gpt.py",
                              ("serve_logits", "train_loss"))

L, D, H, V, S, B = 2, 8, 2, 11, 5, 2
EPS = 1e-5
# what the two entries read of a configuration's file
CONFIG = {"architecture": {"num_heads": H, "layer_norm_eps": EPS}}


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.normal(0, 0.5, shape)

    params = {"wte": w(V, D), "wpe": w(S, D),
              "ln1_w": 1 + w(L, D) * 0.1, "ln1_b": w(L, D) * 0.1,
              "qkv_w": w(L, D, 3 * D), "qkv_b": w(L, 3 * D) * 0.1,
              "out_w": w(L, D, D), "out_b": w(L, D) * 0.1,
              "ln2_w": 1 + w(L, D) * 0.1, "ln2_b": w(L, D) * 0.1,
              "fc1_w": w(L, D, 4 * D), "fc1_b": w(L, 4 * D) * 0.1,
              "fc2_w": w(L, 4 * D, D), "fc2_b": w(L, D) * 0.1,
              "lnf_w": 1 + w(D) * 0.1, "lnf_b": w(D) * 0.1}
    ids = rng.integers(0, V, (B, S))
    return params, ids, np.roll(ids, -1, axis=1)


def by_hand(p, ids):
    """GPT-2's equations one position and one head at a time."""
    def ln(x, w, b):
        mu = x.mean()
        return (x - mu) / math.sqrt(((x - mu) ** 2).mean() + EPS) * w + b

    hd = D // H
    out = np.zeros((len(ids), S, V))
    for b in range(len(ids)):
        h = np.stack([p["wte"][ids[b, t]] + p["wpe"][t] for t in range(S)])
        for l in range(L):
            y = np.stack([ln(h[t], p["ln1_w"][l], p["ln1_b"][l])
                          for t in range(S)])
            qkv = y @ p["qkv_w"][l] + p["qkv_b"][l]          # [S, 3D]
            att = np.zeros((S, D))
            for head in range(H):
                q, k, v = (qkv[:, i * D + head * hd:i * D + (head + 1) * hd]
                           for i in range(3))
                for t in range(S):
                    sc = np.array([q[t] @ k[u] / math.sqrt(hd)
                                   for u in range(t + 1)])
                    pr = np.exp(sc - sc.max())
                    pr /= pr.sum()
                    att[t, head * hd:(head + 1) * hd] = sum(
                        pr[u] * v[u] for u in range(t + 1))
            h = h + att @ p["out_w"][l] + p["out_b"][l]
            y = np.stack([ln(h[t], p["ln2_w"][l], p["ln2_b"][l])
                          for t in range(S)])
            a = y @ p["fc1_w"][l] + p["fc1_b"][l]
            g = 0.5 * a * (1 + np.tanh(math.sqrt(2 / math.pi)
                                       * (a + 0.044715 * a ** 3)))
            h = h + g @ p["fc2_w"][l] + p["fc2_b"][l]
        hf = np.stack([ln(h[t], p["lnf_w"], p["lnf_b"]) for t in range(S)])
        out[b] = hf @ p["wte"].T
    return out


def test_logits_match_the_hand_computed_two_layer_case(case):
    params, ids, _ = case
    got = np.asarray(reference.serve_logits(params, ids, CONFIG))
    np.testing.assert_allclose(got, by_hand(params, ids), rtol=2e-4,
                               atol=2e-4)


def test_loss_is_mean_token_cross_entropy(case):
    params, ids, labels = case
    lg = by_hand(params, ids)
    logp = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
    want = -np.mean([logp[b, t, labels[b, t]]
                     for b in range(B) for t in range(S)])
    for rows in (1, 2):
        got = reference.train_loss(params, ids, labels, CONFIG, rows=rows)
        assert got == pytest.approx(want, rel=1e-5)


def test_causal_padding_after_a_sequence_changes_nothing_before_it(case):
    """serve_driver pads prompt + answer to a fixed length."""
    params, ids, _ = case
    short = np.asarray(reference.serve_logits(params, ids[:, :3], CONFIG))
    full = np.asarray(reference.serve_logits(params, ids, CONFIG))
    np.testing.assert_allclose(short, full[:, :3], rtol=1e-5, atol=1e-5)


def test_reference_imports_nothing_of_the_system():
    with open(reference.__file__) as fh:
        src = fh.read()
    imports = re.findall(r"^\s*(?:import|from)\s+([\w.]+)", src, re.M)
    assert imports and not [m for m in imports
                            if m.split(".")[0] not in
                            ("__future__", "functools", "typing", "jax")]
    assert "pallas" not in src and "lax.scan(" not in src


def test_system_train_loss_matches_reference_at_tiny_size():
    """What the train cells check on the chip, here at gpt3-tiny: the
    compiled step's first loss against the reference on its weights."""
    import bench
    from paddle_tpu.models import PRESETS

    cfg = PRESETS["gpt3-tiny"]
    step, ids, labels, _ = bench.build_train_step("gpt3-tiny", 4, 64)
    want = reference.train_loss(
        step.state()[0], ids, labels,
        {"architecture": {"num_heads": cfg.num_heads,
                          "layer_norm_eps": cfg.layer_norm_eps}})
    got = float(step(ids, labels).numpy())
    assert abs(got - want) / want < 2e-3


def test_mfu_arithmetic():
    peak = peaks.device_peaks("TPU v5 lite")["bf16_flops"]
    assert peak == 197e12
    # PR 23's smoke: 22,326 tokens/s at 354,871,296 parameters -> 0.241
    assert flops.mfu(22326.0, 354871296, peak) == pytest.approx(0.2413,
                                                                abs=1e-4)
    with pytest.raises(LookupError):
        peaks.device_peaks("TPU v9")


def test_decode_step_mfu_arithmetic():
    """2·N for each real row of a step over the step's time and the peak;
    a sparse model's N is the parameters a token passes through."""
    peak = peaks.device_peaks("TPU v5 lite")["bf16_flops"]
    arch = {"n_params": 354871296}
    assert flops.serve_model_flops_per_token(arch) == 2 * 354871296
    # the issue's expectation for serve-decode: 8 rows in 80 ms
    assert flops.decode_step_mfu(8.0, 0.080, arch, peak) == pytest.approx(
        8 * 2 * 354871296 / 0.080 / 197e12)
    assert 3.5e-4 < flops.decode_step_mfu(8.0, 0.080, arch, peak) < 3.7e-4
    sparse = {"n_params": 7_000_000_000, "n_params_active": 1_000_000_000}
    assert flops.serve_model_flops_per_token(sparse) == 2e9
    with pytest.raises(KeyError, match="n_params"):
        flops.serve_model_flops_per_token({"hidden_size": 8})


def test_flash_attention_work():
    fwd = flops.flash_attention_fwd(8, 16, 1024, 1024, 64, causal=False)
    assert fwd["flops"] == 4 * 8 * 16 * 1024 * 1024 * 64
    assert fwd["bytes"] == 2 * 8 * 16 * 64 * 4 * 1024
    tri = flops.flash_attention_fwd(8, 16, 1024, 1024, 64, causal=True)
    assert tri["flops"] == fwd["flops"] * (1024 * 1025 / 2) / 1024 ** 2
    bwd = flops.flash_attention_bwd(8, 16, 1024, 1024, 64, causal=True)
    assert bwd["flops"] == 2.5 * tri["flops"]
    row = peaks.device_peaks("TPU v5 lite")
    # at S = 1024 and 64-wide heads the forward kernel is compute-bound
    assert flops.roofline_seconds(tri, row) == tri["flops"] / 197e12
    # one query row against a long cache is bandwidth-bound
    dec = flops.flash_attention_fwd(8, 16, 1, 1024, 64, causal=True)
    assert flops.roofline_seconds(dec, row) == dec["bytes"] / 819e9
