"""What a scanned block's remat keeps for the backward, chosen from what
the compiler reports the step to need.

A model names the values of its block that are worth keeping
(`jax.ad_checkpoint.checkpoint_name`) and prices each as a
`RematCandidate`; `remat_plan` keeps the most seconds a byte that fit the
device's memory the policy-free step leaves free, and `fit_remat_plan`
holds that choice to the chosen program's own report (a kept value can
cost twice its shape: 64-wide heads pad to 128 lanes, a second copy is
kept in its consumer's layout).
Nothing here touches jax: `TrainStep` supplies the compiles.
"""
from __future__ import annotations

import collections


class RematCandidate(collections.namedtuple(
        "RematCandidate", ("name", "layer_bytes", "layers", "seconds"))):
    """name: the `checkpoint_name` the value carries; layer_bytes: what
    keeping one layer's holds on the fullest device, by its shape;
    layers: how many the scan keeps; seconds: the second forward's time,
    the whole stack's, that the backward no longer spends when it is
    kept."""
    __slots__ = ()

    @property
    def bytes(self) -> int:
        return self.layer_bytes * self.layers


# left free of a device's memory beside the step (PERF.md §4's rule)
SPARE_BYTES = 1 << 30


def remat_plan(candidates, spare_bytes) -> tuple:
    """Names of the candidates kept: greedily by seconds a byte, each
    one whose bytes still fit `spare_bytes` beside those kept before it.
    The most valuable first; ties in the order given."""
    kept, room = [], spare_bytes
    for c in sorted(candidates, key=lambda c: -c.seconds / c.bytes):
        if c.bytes <= room:
            kept.append(c.name)
            room -= c.bytes
    return tuple(kept)


def fit_remat_plan(candidates, limit_bytes, need_of) -> dict:
    """The plan, held to the compiler's report. `need_of(names)` -> the
    bytes the step compiled under those names needs on its fullest
    device; `limit_bytes`: what it may need. While the chosen program
    reads over the limit its least valuable name goes and the step is
    compiled again: at most one compile a candidate after the policy-free
    one. -> the `remat_saved` report."""
    need = base = need_of(())
    names = remat_plan(candidates, limit_bytes - base)
    while names and (need := need_of(names)) > limit_bytes:
        names, need = names[:-1], base
    return saved_report(candidates, names, limit_bytes - base, need)


def saved_report(candidates, names=(), spare_bytes=None,
                 need_bytes=None) -> dict:
    """`remat_saved`: the names kept, their bytes by the candidates'
    count (a layer's, and the stack's) and what they were fitted to."""
    kept = [c for c in candidates if c.name in names]
    return {"names": list(names),
            "bytes_per_layer": sum(c.layer_bytes for c in kept),
            "bytes": sum(c.bytes for c in kept),
            "spare_bytes": spare_bytes, "need_bytes": need_bytes}
