"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix is a data file, `bench/traffic/<mix>.json`.  Its `loop` names the
kind of load; the rest are that kind's parameters.  Every request of a
run is drawn from `--seed` and the wave it belongs to, so a seed gives
the same prompts in every run, and every seed gives the same sizes.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

LOOPS = ("lockstep_waves",)


def seed_words(seed: int, *salt: int) -> list[int]:
    """Non-negative words for numpy's seeding from any whole number."""
    return [seed % 2 ** 64, *salt]


@dataclass(frozen=True)
class LockstepWaves:
    """Closed loop of waves: each wave is `batch` requests of `prompt_tokens`
    uniform token ids, prefilled in one call and decoded in lockstep for
    `output_tokens` greedy tokens (no stop token).  A wave is due when the
    previous one has delivered its last token."""
    batch: int
    prompt_tokens: int
    output_tokens: int
    token_ids_below: int
    seed: int

    @property
    def cache_tokens(self) -> int:
        """Positions each request feeds to the cache: the prompt and every
        token fed back (the last generated token is never fed)."""
        return self.prompt_tokens + self.output_tokens - 1

    def prompts(self, wave: int) -> np.ndarray:
        rng = np.random.default_rng(seed_words(self.seed, wave))
        return rng.integers(0, self.token_ids_below,
                            (self.batch, self.prompt_tokens), dtype=np.int32)


def generator(traffic: dict, token_ids_below: int, seed: int) -> LockstepWaves:
    loop = traffic["loop"]
    if loop != "lockstep_waves":
        raise ValueError(f"unknown traffic loop {loop!r}; have {LOOPS}")
    return LockstepWaves(batch=traffic["batch"],
                         prompt_tokens=traffic["prompt_tokens"],
                         output_tokens=traffic["output_tokens"],
                         token_ids_below=token_ids_below, seed=seed)
