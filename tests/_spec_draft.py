"""A speculative-decode draft for the tests that agrees with its target
sometimes, by construction."""
import numpy as np

from paddle_tpu.models.gpt import GPTForCausalLM


def noisy_draft(target, seed=1, scale=0.1):
    """`target` with seeded noise (`scale` of each matrix's own standard
    deviation) on its last block's matrices: a draft of different weights
    whose greedy proposals the target accepts about half the time — two
    independently seeded random models never agree, and the target itself
    always does, so neither exercises both the accept and the reject
    path."""
    draft = GPTForCausalLM(target.cfg)
    draft.eval()
    src = dict(target.named_parameters())
    last = ".blocks.%d." % (target.cfg.num_layers - 1)
    rng = np.random.RandomState(seed)
    for name, p in draft.named_parameters():
        v = src[name].numpy()
        if last in name and v.ndim == 2:
            v = v + scale * v.std() * rng.standard_normal(
                v.shape).astype(v.dtype)
        p.set_value(v)
    return draft
