"""What decides `correct`: the served tokens against the plain reference.

Once the window has closed, a sample of the requests it finished, drawn
from the seed, is run through the configuration's reference
(`bench/reference/<family>.py`, float32 at the highest matmul precision)
over each prompt with its served tokens fed back.  At every served
position the gap is how far the served token's reference logit lies below
the reference's best; the run is correct when the widest gap is within
the cell's limit (`bench/limits/<cell>.json`) and every served token is
in the vocabulary.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from reference.common import control_gaps, served_gaps
from traffic import seed_words

#: the name the compared number carries in the result line
GAP = "max_logit_gap"

#: served tokens the reference compares in a run, in whole requests
CHECK_TOKENS = 512


@dataclass
class Sample:
    tokens: np.ndarray      # (R, P + out - 1): prompt, served tokens fed back
    served: np.ndarray      # (R, out) served tokens


def finished_requests(win) -> list:
    return [(w, r) for w in win.waves if w.done
            for r in range(len(w.tokens[0]))]


def check_requests(gen) -> int:
    """Requests enough for `CHECK_TOKENS` served tokens."""
    return -(-CHECK_TOKENS // gen.output_tokens)


def draw_sample(win, gen, seed: int) -> Sample:
    """`check_requests(gen)` finished requests, drawn from the seed."""
    done = finished_requests(win)
    if not done:
        raise RuntimeError("no request finished inside the window")
    rng = np.random.default_rng(seed_words(seed, 0xC4EC))
    n = min(check_requests(gen), len(done))
    picked = [done[i] for i in sorted(rng.choice(len(done), n,
                                                 replace=False))]
    rows, served = [], []
    for w, r in picked:
        out = np.stack(w.tokens)[:, r]
        rows.append(np.concatenate([gen.prompts(w.index)[r], out[:-1]]))
        served.append(out)
    return Sample(np.stack(rows).astype(np.int32),
                  np.stack(served).astype(np.int32))


def program_gap(ref, config: dict, params, sample: Sample) -> float:
    """Widest gap of the served tokens under the reference."""
    logits = ref.last_logits(config, params, sample.tokens,
                             n_last=sample.served.shape[1])
    return float(jax.device_get(
        served_gaps(logits, sample.served).max()))


def control_gap(ref, config: dict, params, sample: Sample) -> float:
    """Widest gap, under the float32 reference, of the tokens that the
    reference computed in float8 puts first at the same positions."""
    n = sample.served.shape[1]
    logits = ref.last_logits(config, params, sample.tokens, n_last=n)
    low = ref.last_logits(config, params, sample.tokens, n_last=n,
                          mode="fp8")
    return float(jax.device_get(control_gaps(logits, low).max()))


def tokens_in_vocab(win, vocab: int) -> bool:
    return all(((t >= 0) & (t < vocab)).all()
               for w in win.waves for t in w.tokens)
