"""The one general traffic generator: a traffic file's parameters and
`--seed` give the requests and their due times.

Standard library only — the load generator's process imports this and
never imports jax or numpy.

Every seed gets THE SAME SET of lengths and inter-arrival gaps, in another
order: a pool of `pool` evenly spaced quantiles of each distribution is
fixed by the traffic file alone, and the seed only permutes it (anew for
every pass through the pool), picks the token ids and the sampling seeds.
So two seeds offer the same work, and runs differ by order alone.

A traffic file of kind "serve":

  loop            "closed" (each of `clients` sends its next request when
                  its last one ended) or "open" (Poisson arrivals at
                  `rate_per_s`, whatever the server does)
  prompt_tokens   {"dist": "uniform" | "loguniform", "min": a, "max": b}
  output_tokens   the same
  sampling        fields sent with every request (temperature, top_p,
                  top_k); each request adds its own `seed`
  pool            quantiles per distribution (default 64)
  ramp_s          seconds of the same traffic before the window opens
  burst           optional {"period_s": p, "on_share": s}: arrivals keep
                  their mean rate but fall only into the first s of
                  every period of p seconds
  shared_prefix   optional {"groups": g, "tokens": n}: a request's first
                  n tokens are one of g fixed prefixes
"""
from __future__ import annotations

import math
import random
from typing import List


def quantiles(dist: dict, n: int) -> List[float]:
    """n evenly spaced quantiles ((i + 0.5) / n) of `dist`."""
    kind, lo, hi = dist["dist"], float(dist["min"]), float(dist["max"])
    if lo > hi or lo < 0:
        raise ValueError(f"bad range in {dist}")
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "uniform":
        return [lo + (hi - lo) * u for u in us]
    if kind == "loguniform":
        return [lo * (hi / lo) ** u for u in us]
    if kind == "exponential":      # mean (lo + hi) / 2; arrival gaps
        mean = (lo + hi) / 2.0
        return [-mean * math.log(1.0 - u) for u in us]
    raise ValueError(f"unknown distribution {kind!r}")


class Mix:
    """Request i of a traffic file under one seed: `lengths(i)`,
    `payload(i)` and, in an open loop, `due(i)` seconds after the start."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 rate_per_s: float | None = None):
        self.traffic = traffic
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.pool = int(traffic.get("pool", 64))
        self.prompt_pool = [int(round(x)) for x in quantiles(
            traffic["prompt_tokens"], self.pool)]
        self.output_pool = [int(round(x)) for x in quantiles(
            traffic["output_tokens"], self.pool)]
        self.rate = float(rate_per_s if rate_per_s is not None
                          else traffic.get("rate_per_s", 0.0))
        self._gaps: List[float] = []
        if traffic["loop"] == "open":
            if self.rate <= 0:
                raise ValueError("an open loop needs rate_per_s > 0")
            mean = 1.0 / self.rate
            gaps = quantiles(
                {"dist": "exponential", "min": mean, "max": mean}, self.pool)
            # the quantiles cut the tail: rescale so that a pass through
            # the pool lasts exactly pool / rate seconds
            scale = mean * self.pool / sum(gaps)
            self._gaps = [g * scale for g in gaps]
        self._perms: dict = {}
        self._due: List[float] = []
        sp = traffic.get("shared_prefix")
        self._prefixes = []
        if sp:
            rng = random.Random(f"{self.seed}:prefix")
            self._prefixes = [
                rng.choices(range(self.vocab), k=int(sp["tokens"]))
                for _ in range(int(sp["groups"]))]

    def _perm(self, what: str, cycle: int) -> List[int]:
        key = (what, cycle)
        if key not in self._perms:
            order = list(range(self.pool))
            random.Random(f"{self.seed}:{what}:{cycle}").shuffle(order)
            self._perms[key] = order
        return self._perms[key]

    def _pick(self, what: str, values: List, i: int):
        cycle, j = divmod(i, self.pool)
        return values[self._perm(what, cycle)[j]]

    def lengths(self, i: int) -> tuple:
        return (self._pick("prompt", self.prompt_pool, i),
                self._pick("output", self.output_pool, i))

    def due(self, i: int) -> float:
        """Seconds from the start of the schedule to arrival i."""
        while len(self._due) <= i:
            k = len(self._due)
            prev = self._due[-1] if self._due else 0.0
            self._due.append(prev + self._pick("gap", self._gaps, k))
        t = self._due[i]
        burst = self.traffic.get("burst")
        if burst:
            # squeeze every period's arrivals into its first `on_share`
            period, on = float(burst["period_s"]), float(burst["on_share"])
            whole, part = divmod(t, period)
            t = whole * period + part * on
        return t

    def payload(self, i: int) -> dict:
        n_prompt, n_out = self.lengths(i)
        rng = random.Random(f"{self.seed}:ids:{i}")
        ids = rng.choices(range(self.vocab), k=n_prompt)
        if self._prefixes:
            head = self._prefixes[rng.randrange(len(self._prefixes))]
            ids[:len(head)] = head[:n_prompt]
        body = {"input_ids": ids, "max_new_tokens": n_out, "stream": True,
                "seed": (self.seed * 7919 + i) % (2 ** 31)}
        body.update(self.traffic.get("sampling", {}))
        return body
