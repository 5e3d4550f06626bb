"""Model FLOP/s utilization: 6·N·tokens/s per chip over the bf16 peak of
the benchmark's own table for this device kind; recomputation (remat) is
not counted as work. Moves train_tokens_per_s_per_chip, of which it is a
constant multiple."""
from harness import flops


def read(run):
    train = run.get("train")
    if not train:
        return None
    return flops.mfu(train["tokens_per_s_per_chip"], train["n_params"],
                     run["peaks"]["bf16_flops"])
