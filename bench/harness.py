"""The system under test, and the measured window that drives it.

The program's serving step programs (`repro.serve.engine.make_prefill` and
`make_decode_step` over `repro.models.model.Model` with the cell's pinned
plan) are jitted here with a greedy sampler on top: prefill returns each
row's first token, a decode step takes the tokens of the step before and
returns the next.  The cache is donated to both, as a server holding one
cache per batch does.  A wave is `batch` requests prefilled in one call,
then decoded in lockstep; one decode step stays in flight while the host
pulls the previous step's tokens.

Host spans (`jax.profiler.TraceAnnotation`) mark what the host does, so a
traced run can say what it was doing during each idle gap of the device.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field, is_dataclass
from typing import (Callable, Optional, get_args, get_origin,
                    get_type_hints)

import jax
import jax.numpy as jnp
import numpy as np

SPANS = ("wave_inputs", "prefill", "decode_dispatch", "token_pull", "slice")

#: decode steps a traced slice covers after its prefill
TRACE_DECODE_STEPS = 64


def build_model(config: dict):
    """The program's `Model` at the configuration's sizes and pinned plan."""
    from repro.configs.base import ArchConfig
    from repro.models.model import Model
    return Model(from_json(ArchConfig,
                           dict(config["arch"], plan=config["plan"])))


def from_json(cls, values: dict):
    """The dataclass `cls` from its fields as a JSON file spells them: a
    field typed as a dataclass (or an optional one) from its mapping, a
    tuple from its list, by the field's annotation.  So a configuration
    file can carry any section the program's config classes declare (an
    `ArchConfig`'s `moe`, its `layer_pattern`) with no edit here."""
    hints = get_type_hints(cls)
    return cls(**{k: _field_from_json(hints[k], v)
                  for k, v in values.items()})


def _field_from_json(hint, value):
    kinds = (hint, *get_args(hint))     # Optional[X]: X and None
    if isinstance(value, dict):
        for t in kinds:
            if is_dataclass(t):
                return from_json(t, value)
    if isinstance(value, list) and any(get_origin(t) is tuple
                                       for t in kinds):
        return tuple(value)
    return value


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a seed of up to 64 bits."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


@dataclass
class Steps:
    """The cell's jitted programs: cache allocation, prefill, decode."""
    new_cache: Callable
    prefill: Callable           # (params, tokens (B,P), cache) -> tok, cache
    decode: Callable            # (params, tok (B,), pos, cache) -> (tok, cache)


def cache_length(plan, tokens: int) -> int:
    """Cache positions that hold `tokens` fed tokens.  Past `attn_chunk`
    the program's decode attention scans the cache in chunks of that size,
    and in chunks of their greatest common divisor where the cache is no
    multiple of it (32 for 2080 slots), a path that served wrong tokens in
    the one chip run that took it; so the cache is whole chunks there, and
    whole sublanes of 8 below."""
    align = plan.attn_chunk if tokens > plan.attn_chunk else 8
    return -(-tokens // align) * align


def make_steps(model, batch: int, cache_tokens: int) -> Steps:
    from repro.serve.engine import make_decode_step, make_prefill
    prefill = make_prefill(model)
    decode = make_decode_step(model)

    def serve_prefill(params, tokens, cache):
        logits, cache = prefill(params, {"tokens": tokens}, cache)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def serve_decode(params, tokens, pos, cache):
        logits, cache = decode(params, {"tokens": tokens[:, None],
                                        "pos": pos}, cache)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    length = cache_length(model.plan, cache_tokens)

    def new_cache():
        return model.init_cache(batch, length)

    return Steps(new_cache=jax.jit(new_cache),
                 prefill=jax.jit(serve_prefill, donate_argnums=2),
                 decode=jax.jit(serve_decode, donate_argnums=3))


@dataclass
class Wave:
    index: int
    t_due: float                    # host clock: when the wave was due
    want: int                       # tokens each request asks for
    arrivals: list = field(default_factory=list)    # host clock per token
    tokens: list = field(default_factory=list)      # (B,) int32 per token

    @property
    def done(self) -> bool:
        return len(self.tokens) == self.want


@dataclass
class Window:
    start: float
    end: float
    waves: list                     # every wave begun inside the window


def _pos(p: int):
    return np.int32(p)


def run_wave(steps: Steps, params, gen, wave: Wave, stop_at: float,
             tracer: Optional["Tracer"] = None) -> None:
    """Serve one wave; stop once a token lands after `stop_at`.

    With a `tracer`, the wave's prefill and its first `TRACE_DECODE_STEPS`
    decode steps are traced, and the wave runs to its end."""
    out = gen.output_tokens
    ann = jax.profiler.TraceAnnotation
    tracing = tracer is not None
    if tracing:
        tracer.start(gen)
    with ann("wave_inputs"):
        tokens = jax.device_put(gen.prompts(wave.index))
        cache = steps.new_cache()
    with ann("prefill"):
        tok, cache = steps.prefill(params, tokens, cache)
    with ann("token_pull"):
        wave.tokens.append(np.asarray(tok))
    wave.arrivals.append(time.perf_counter())
    if wave.arrivals[-1] >= stop_at and not tracing:
        jax.block_until_ready(cache)
        return
    p0 = gen.prompt_tokens
    traced = min(out - 1, TRACE_DECODE_STEPS)
    for j in range(out - 1):
        with ann("decode_dispatch"):
            nxt, cache = steps.decode(params, tok, _pos(p0 + j), cache)
        if j > 0:
            with ann("token_pull"):
                wave.tokens.append(np.asarray(tok))
            wave.arrivals.append(time.perf_counter())
            if wave.arrivals[-1] >= stop_at and not tracing:
                jax.block_until_ready((nxt, cache))
                return
        tok = nxt
        if tracing and j + 1 == traced:
            with ann("token_pull"):
                jax.block_until_ready(tok)
            tracer.stop()
            tracing = False
    with ann("token_pull"):
        wave.tokens.append(np.asarray(tok))
    wave.arrivals.append(time.perf_counter())
    jax.block_until_ready(cache)


def run_window(steps: Steps, params, gen, seconds: float,
               tracer: Optional["Tracer"] = None) -> Window:
    """Closed loop of waves for `seconds`; the first wave is traced when a
    `tracer` is given."""
    start = time.perf_counter()
    stop_at = start + seconds
    win = Window(start=start, end=stop_at, waves=[])
    t = start
    while t < stop_at:
        wave = Wave(index=len(win.waves), t_due=t, want=gen.output_tokens)
        win.waves.append(wave)
        run_wave(steps, params, gen, wave, stop_at,
                 tracer if wave.index == 0 else None)
        t = wave.arrivals[-1]
    return win


def warm_up(steps: Steps, params, gen) -> None:
    """Run every program the window runs, at its shapes, once; then move
    every object set-up made out of the collector's reach, as a server
    does after warm-up: a full collection over JAX's hundreds of
    thousands of objects takes ~0.1 s, and would land at random in the
    window."""
    cache = steps.new_cache()
    tokens = jax.device_put(gen.prompts(0))
    tok, cache = steps.prefill(params, tokens, cache)
    tok, cache = steps.decode(params, tok, _pos(gen.prompt_tokens), cache)
    jax.block_until_ready((tok, cache))
    np.asarray(tok)
    gc.collect()
    gc.freeze()


class GcPauses:
    """Collections of the host's garbage collector and their pauses."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class Tracer:
    """Runs `jax.profiler` over one slice, marked by the host span `slice`,
    and keeps the (program, position) of the calls made inside it."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.calls: list = []
        self._span = None

    def start(self, gen) -> None:
        jax.profiler.start_trace(self.log_dir)
        self._span = jax.profiler.TraceAnnotation("slice")
        self._span.__enter__()
        p0 = gen.prompt_tokens
        n_dec = min(gen.output_tokens - 1, TRACE_DECODE_STEPS)
        self.calls = [("prefill", p0)] + [("decode", p0 + j)
                                          for j in range(n_dec)]

    def stop(self) -> None:
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def wave_stats(win: Window, batch: int) -> dict:
    """End-to-end numbers of a window, from the host clock.

    ttft: per request, first token on the host minus the wave's due time.
    tpot: per request that finished, (last token - first token) / (n - 1).
    output tokens: every token that reached the host inside the window."""
    ttft, tpot = [], []
    tokens = 0
    for w in win.waves:
        inside = [a for a in w.arrivals if a < win.end]
        tokens += batch * len(inside)
        if inside:
            ttft.extend([inside[0] - w.t_due] * batch)
        if w.done and w.arrivals[-1] < win.end and len(w.arrivals) > 1:
            per = (w.arrivals[-1] - w.arrivals[0]) / (len(w.arrivals) - 1)
            tpot.extend([per] * batch)
    # the requests of a wave share its times: a tail rests on waves
    return {"ttft_s": ttft, "tpot_s": tpot, "output_tokens": tokens,
            "requests_started": batch * len(win.waves),
            "requests_finished": len(tpot),
            "ttft_waves": len(ttft) // batch,
            "tpot_waves": len(tpot) // batch}


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 95))
