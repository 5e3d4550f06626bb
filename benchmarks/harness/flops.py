"""Operations and bytes the algorithms need, computed from shapes — the
yardstick's side of every utilization. Kept here so that a PR that claims
a gain cannot change what is divided by the time."""
from __future__ import annotations


def train_model_flops_per_token(n_params: int) -> float:
    """6·N: forward 2·N and backward 4·N multiply-adds' worth per token
    (Kaplan et al. 2020). Attention's own FLOPs and every recomputed
    operation (remat) are NOT counted: this is model FLOPs, so MFU falls
    when the program recomputes."""
    return 6.0 * n_params


def mfu(tokens_per_s_per_chip: float, n_params: int,
        peak_flops: float) -> float:
    return train_model_flops_per_token(n_params) * tokens_per_s_per_chip \
        / peak_flops


def serve_model_flops_per_token(architecture: dict) -> float:
    """2·N: one forward pass's multiply-adds' worth for one token, N the
    parameters a token passes through — `n_params_active` where the
    configuration's file gives it (sparse experts), else `n_params`.
    Attention's own FLOPs over the cache are NOT counted, as in training;
    nor is a padded row, a draft's pass or a recomputed prefix."""
    n = architecture.get("n_params_active", architecture.get("n_params"))
    if n is None:
        raise KeyError("architecture.n_params (or n_params_active): the "
                       "configuration's file does not give it")
    return 2.0 * float(n)


def decode_step_mfu(rows_per_step: float, step_seconds: float,
                    architecture: dict, peak_flops: float) -> float:
    """The share of the chip's peak that a whole decode step uses: the
    model FLOPs of its real rows over the step's time and the peak."""
    return serve_model_flops_per_token(architecture) * rows_per_step \
        / step_seconds / peak_flops


def flash_attention_fwd(batch: int, heads: int, seq_q: int, seq_k: int,
                        head_dim: int, causal: bool,
                        itemsize: int = 2) -> dict:
    """Reserved for `flash_attention.roofline_share` (PERF.md §7): the
    forward kernel's least work. FLOPs: QK^T and PV, 2·2·B·H·Sq·Sk·hd,
    halved under a causal mask (the kernel may skip masked blocks; the
    exact triangle is counted). Bytes: q, k, v read once and o written
    once — what a fused kernel cannot avoid."""
    pairs = seq_q * seq_k
    if causal:
        pairs = seq_q * (seq_k - seq_q) + seq_q * (seq_q + 1) // 2
    return {
        "flops": 4.0 * batch * heads * pairs * head_dim,
        "bytes": float(itemsize) * batch * heads * head_dim
        * (2 * seq_q + 2 * seq_k),
    }


def flash_attention_bwd(batch: int, heads: int, seq_q: int, seq_k: int,
                        head_dim: int, causal: bool,
                        itemsize: int = 2) -> dict:
    """The backward kernels' least work: five matmuls of the forward's
    two sizes (S recomputed, dV, dP, dQ, dK) — 2.5x the forward's FLOPs;
    q, k, v, o, do read and dq, dk, dv written."""
    fwd = flash_attention_fwd(batch, heads, seq_q, seq_k, head_dim, causal,
                              itemsize)
    return {
        "flops": 2.5 * fwd["flops"],
        "bytes": float(itemsize) * batch * heads * head_dim
        * (4 * seq_q + 4 * seq_k),
    }


def roofline_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(work["flops"] / peaks["bf16_flops"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
