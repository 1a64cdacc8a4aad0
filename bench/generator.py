"""Inputs from the seed: request schedules for serving mixes, and the
JAX key that device-made inputs and weights come from.

A serving mix is an open-loop Poisson process: exponential gaps between
arrivals at ``rate_per_s`` and lognormal prompt and output lengths, each
drawn independently per request. One draw of it, fixed by the mix's
``schedule_seed``, is the schedule every run replays, clusters and all.
``--seed`` draws the prompts' token ids (and the weights), not the
schedule: drawing arrivals and lengths from ``--seed`` makes seeds differ
in the work they send, and the tail of 70 requests then swings between
draws by more than any bound could hold.

Mix keys read here (a mix file may add keys that its driver reads):

  rate_per_s      mean arrival rate
  schedule_seed   the draw of arrivals and lengths every run replays
  prompt_len      {"median", "sigma"} of a lognormal, then either
                  "round_up_to": [sizes...] (longer ones take the
                  largest) or "min"/"max" clipping
  output_len      the same, for the number of tokens to generate
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    arrival_s: float          # scheduled send time from the window's start
    prompt: np.ndarray        # (S,) int32
    max_new_tokens: int


def size(spec: dict, rng: np.random.Generator) -> int:
    """One lognormal draw of ``spec``, rounded or clipped as it says."""
    raw = math.exp(math.log(spec["median"]) + spec["sigma"] * rng.normal())
    if "round_up_to" in spec:
        sizes = sorted(spec["round_up_to"])
        return next((s for s in sizes if s >= raw), sizes[-1])
    return int(min(max(math.ceil(raw), spec.get("min", 1)),
                   spec.get("max", math.inf)))


def schedule(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The requests due in a window of ``seconds``, in arrival order. A
    shorter window gets a prefix of a longer one's schedule."""
    gaps, prompts, outputs = (np.random.default_rng(s) for s in
                              np.random.SeedSequence(
                                  int(mix["schedule_seed"])).spawn(3))
    tokens = np.random.default_rng(seed)
    rate = float(mix["rate_per_s"])
    reqs: List[Request] = []
    t = gaps.exponential(1.0 / rate)
    while t < seconds:
        n = size(mix["prompt_len"], prompts)
        reqs.append(Request(
            index=len(reqs), arrival_s=float(t),
            prompt=tokens.integers(0, vocab, n).astype(np.int32),
            max_new_tokens=size(mix["output_len"], outputs)))
        t += gaps.exponential(1.0 / rate)
    return reqs


def jax_key(seed: int):
    """A JAX PRNG key for any non-negative whole-number seed (all of its
    bits count; ``jax.random.PRNGKey`` alone keeps the low 32)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))
