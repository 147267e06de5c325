"""Stack assembler: scan-over-layers transformer with mixed layer kinds.

The stack is expressed as ``n_full`` repetitions of a *unit* (the arch's
repeating layer pattern — e.g. ("rec","rec","attn") for RecurrentGemma,
("attn",) for dense archs) scanned with ``lax.scan`` over stacked params,
plus an unrolled tail for non-divisible depths.  Scanning keeps HLO size and
GSPMD compile time flat in depth — essential for the 512-device dry-run.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, PlanConfig
from repro.models import layers as L
from repro.models import rglru as R
from repro.models import ssm as S
from repro.parallel.sharding import ShardingRules, constrain


def unit_structure(cfg: ArchConfig) -> tuple[tuple[str, ...], int, tuple[str, ...]]:
    """(unit_kinds, n_full, tail_kinds)."""
    kinds = cfg.layer_kinds()
    if cfg.family == "hybrid" and cfg.layer_pattern:
        unit = tuple(cfg.layer_pattern)
    else:
        unit = (kinds[0],)
    n_full = len(kinds) // len(unit)
    tail = tuple(kinds[n_full * len(unit):])
    return unit, n_full, tail


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ArchConfig, kind: str):
    ks = jax.random.split(key, 3)
    if kind == "ssm":
        p = {"norm1": L.init_norm(cfg), "mixer": S.init_mamba2(ks[0], cfg)}
        if cfg.d_ff:        # a hybrid's SSM layer with an MLP of its own
            p["norm2"] = L.init_norm(cfg)
            p["mlp"] = L.init_mlp(ks[1], cfg)
        return p
    p = {"norm1": L.init_norm(cfg), "norm2": L.init_norm(cfg)}
    if kind == "attn":
        p["mixer"] = L.init_attention(ks[0], cfg)
        if cfg.moe is not None and cfg.family == "moe":
            p["moe"] = L.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "rec":
        p["mixer"] = R.init_rglru_block(ks[0], cfg)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    else:
        raise ValueError(kind)
    return p


def apply_layer(params, x, kind: str, cfg: ArchConfig, plan: PlanConfig,
                positions, cache, decode: bool,
                rules: Optional[ShardingRules], mlp_layer=None):
    """Returns (x, new_cache, aux_loss).  With ``mlp_layer`` the layer's
    ``mlp`` weights are the scan's stacks and ``mlp_layer`` its index in
    them (`stacked_mlps`)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(params["norm1"], x, cfg)
    if kind == "attn":
        window = cfg.local_window if cfg.family == "hybrid" else 0
        with jax.named_scope("attn"):
            mix, new_cache = L.run_attention(params["mixer"], h, cfg, plan,
                                             positions, cache, decode, window)
    elif kind == "rec":
        with jax.named_scope("rec"):
            mix, new_cache = R.run_rglru_block(params["mixer"], h, cfg, plan,
                                               cache, decode)
    elif kind == "ssm":
        with jax.named_scope("ssm"):
            mix, new_cache = S.run_mamba2(params["mixer"], h, cfg, plan,
                                          cache, decode)
    else:
        raise ValueError(kind)
    x = _residual(x, mix, cfg)
    if "norm2" in params:       # all but an SSM layer with no MLP
        h = L.apply_norm(params["norm2"], x, cfg)
        if "moe" in params:
            with jax.named_scope("moe"):
                ff, aux = L.run_moe(params["moe"], h, cfg, plan)
        else:
            with jax.named_scope("mlp"):
                ff = L.run_mlp(params["mlp"], h, cfg, plan, mlp_layer)
        x = _residual(x, ff, cfg)
    if rules is not None:
        x = constrain(x, rules, "batch", "seq_sharded", "act_embed")
    return x, new_cache, aux


def _residual(x, y, cfg: ArchConfig):
    """x plus a sublayer's output y, scaled by ``cfg.residual_scale``."""
    if cfg.residual_scale != 1.0:
        y = y * cfg.residual_scale
    return x + y


def init_layer_cache(cfg: ArchConfig, kind: str, batch: int, seq_len: int,
                     cache_dtype=None):
    if cache_dtype is None:
        cache_dtype = jnp.dtype(cfg.plan.kv_cache_dtype)
    if kind == "attn":
        window = cfg.local_window if cfg.family == "hybrid" else 0
        t = min(window, seq_len) if window else seq_len
        shp = (batch, t, cfg.n_kv_heads, cfg.d_head)
        out = {"k": jnp.zeros(shp, cache_dtype),
               "v": jnp.zeros(shp, cache_dtype),
               "kpos": jnp.full((t,), -1, jnp.int32)}
        if cache_dtype == jnp.int8:
            sshp = (batch, t, cfg.n_kv_heads, 1)
            out["k_scale"] = jnp.zeros(sshp, jnp.float32)
            out["v_scale"] = jnp.zeros(sshp, jnp.float32)
        return out
    if kind == "rec":
        return R.init_rglru_cache(cfg, batch)
    if kind == "ssm":
        return S.init_ssm_cache(cfg, batch)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Full-stack init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig):
    unit, n_full, tail = unit_structure(cfg)
    d, v = cfg.d_model, cfg.vocab_size
    dt = L.pdtype(cfg.plan)
    k_embed, k_scan, k_tail, k_head, k_front = jax.random.split(key, 5)

    params: dict[str, Any] = {
        "embed": L._normal(k_embed, (v, d), dt, 0.02),
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L._normal(k_head, (d, v), dt, 1 / math.sqrt(d))
    if cfg.frontend == "audio_frames":
        params["frontend"] = L._normal(k_front, (d, d), dt, 1 / math.sqrt(d))

    def unit_params(k):
        ks = jax.random.split(k, len(unit))
        return {f"l{i}": init_layer(ks[i], cfg, kind)
                for i, kind in enumerate(unit)}

    if n_full:
        # one vmapped init per unit, born stacked: the same values as
        # stacking per-layer inits, without a second copy of every layer
        # and with a program whose size does not grow with depth
        params["scan"] = jax.vmap(unit_params)(
            jax.random.split(k_scan, n_full))
    if tail:
        ks = jax.random.split(k_tail, len(tail))
        params["tail"] = {f"t{i}": init_layer(ks[i], cfg, kind)
                          for i, kind in enumerate(tail)}
    return params


def init_cache(cfg: ArchConfig, batch: int, seq_len: int):
    unit, n_full, tail = unit_structure(cfg)

    def unit_cache():
        return {f"l{i}": init_layer_cache(cfg, kind, batch, seq_len)
                for i, kind in enumerate(unit)}

    cache: dict[str, Any] = {}
    if n_full:
        trees = [unit_cache() for _ in range(n_full)]
        cache["scan"] = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    if tail:
        cache["tail"] = {f"t{i}": init_layer_cache(cfg, kind, batch, seq_len)
                         for i, kind in enumerate(tail)}
    return cache


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _remat_wrap(fn, plan: PlanConfig):
    if plan.remat == "none":
        return fn
    if plan.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def stacked_mlps(sp, cfg: ArchConfig, plan: PlanConfig, cache,
                 rules: Optional[ShardingRules]) -> dict:
    """The unit's MLP weight stacks, by unit slot, that the swiglu kernel
    reads by layer index; empty where the scan slices each layer's MLP
    weights out of ``sp`` (the stacked unit params) instead.

    A custom call takes each operand whole, so a sliced panel is a copy
    of it.  Serving calls (``cache`` given) on one device (no ``rules``)
    with the Pallas swiglu MLP take the stacks, when the weights are
    stored in the compute dtype: a cast would copy a whole stack.
    Training keeps the slices, whose backward gives per-layer gradients
    with no scatter into a whole stack.
    """
    if (cache is None or rules is not None or cfg.act != "swiglu"
            or plan.mlp_impl != "pallas"
            or L.pdtype(plan) != L.cdtype(plan)):
        return {}
    return {k: p["mlp"] for k, p in sp.items() if "mlp" in p}


def embed_inputs(params, batch: dict, cfg: ArchConfig, plan: PlanConfig,
                 rules=None):
    dt = L.cdtype(plan)
    if cfg.frontend == "audio_frames":
        h = jnp.einsum("bsd,de->bse", batch["features"].astype(dt),
                       params["frontend"].astype(dt))
        return h
    if rules is not None:
        # one-hot matmul: keeps a TP-sharded vocab table sharded (a gather
        # would make GSPMD all-gather the whole table per device)
        oh = jax.nn.one_hot(batch["tokens"], cfg.vocab_size, dtype=dt)
        h = jnp.einsum("bsv,vd->bsd", oh, params["embed"].astype(dt))
    else:
        h = params["embed"].astype(dt)[batch["tokens"]]
    if cfg.frontend == "vision_patches" and "patch_embeds" in batch:
        s = jnp.arange(h.shape[1])[None, :, None]
        pe = batch["patch_embeds"].astype(dt)
        npatch = pe.shape[1]
        pe_full = jnp.pad(pe, ((0, 0), (0, h.shape[1] - npatch), (0, 0)))
        h = jnp.where(s < npatch, pe_full, h)
    return h


def forward(params, batch: dict, cfg: ArchConfig, plan: PlanConfig,
            cache=None, decode: bool = False,
            rules: Optional[ShardingRules] = None):
    """Returns (logits, new_cache, aux_loss).

    train:   cache=None, decode=False  -> logits (B,S,V)
    prefill: cache=tree, decode=False  -> logits (B,S,V) + filled cache
    decode:  cache=tree, decode=True   -> logits (B,1,V) + updated cache
    """
    unit, n_full, tail = unit_structure(cfg)
    with jax.named_scope("embed"):
        h = embed_inputs(params, batch, cfg, plan, rules)
        if cfg.embed_scale != 1.0:
            h = h * cfg.embed_scale
    b, s = h.shape[0], h.shape[1]

    if decode:
        positions = batch["pos"][None].astype(jnp.int32)     # (1,)
    else:
        positions = jnp.arange(s, dtype=jnp.int32)
    if rules is not None:
        h = constrain(h, rules, "batch", "seq_sharded", "act_embed")

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}

    stacks = (stacked_mlps(params["scan"], cfg, plan, cache, rules)
              if n_full and plan.scan_layers else {})

    def unit_body(carry, xs):
        hh, aux = carry
        uparams, ucache = xs[:2]
        li = xs[2] if stacks else None      # the layer's index in `stacks`
        ncache = {}
        for i, kind in enumerate(unit):
            c = ucache.get(f"l{i}") if ucache is not None else None
            lp = uparams[f"l{i}"]
            if f"l{i}" in stacks:
                lp = {**lp, "mlp": stacks[f"l{i}"]}
            with jax.named_scope("layer"):
                hh, nc, a = apply_layer(lp, hh, kind, cfg, plan, positions,
                                        c, decode, rules, li)
            aux = aux + a
            if nc is not None:
                ncache[f"l{i}"] = nc
        return (hh, aux), (ncache if ncache else 0)

    body = _remat_wrap(unit_body, plan)

    # the scan's slicing of each layer's weights and cache, and its
    # write-back, land in `layers`; the layers' own ops in `layer`
    with jax.named_scope("layers"):
        if n_full:
            if plan.scan_layers:
                xs = (params["scan"], cache.get("scan") if cache else None)
                if stacks:
                    # the MLP weights stay out of the scanned slices
                    sp = {k: {n: w for n, w in p.items() if n != "mlp"}
                          for k, p in params["scan"].items()}
                    xs = (sp, xs[1], jnp.arange(n_full))
                (h, aux_total), scan_cache = lax.scan(body, (h, aux_total), xs)
                if cache is not None:
                    new_cache["scan"] = scan_cache
            else:
                sp = params["scan"]
                for li in range(n_full):
                    up = jax.tree.map(lambda a, li=li: a[li], sp)
                    uc = (jax.tree.map(lambda a, li=li: a[li], cache["scan"])
                          if cache else None)
                    (h, aux_total), nc = body((h, aux_total), (up, uc))
                    if cache is not None:
                        new_cache.setdefault("_scan_list", []).append(nc)
                if cache is not None:
                    ncs = new_cache.pop("_scan_list")
                    new_cache["scan"] = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *ncs)

        for i, kind in enumerate(tail):
            c = cache["tail"][f"t{i}"] if cache else None
            with jax.named_scope("layer"):
                h, nc, a = apply_layer(params["tail"][f"t{i}"], h, kind, cfg,
                                       plan, positions, c, decode, rules)
            aux_total = aux_total + a
            if nc is not None:
                new_cache.setdefault("tail", {})[f"t{i}"] = nc

    with jax.named_scope("head"):
        h = L.apply_norm(params["final_norm"], h, cfg)
        if cfg.logit_scale != 1.0:
            # on the matmul's input: a prefill's slice of its last
            # position still reaches the matmul, and a power of two
            # scales the logits exactly
            h = h * cfg.logit_scale
        wout = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", h, wout.astype(h.dtype))
        if rules is not None:
            # vocab gets the model axis (loss reductions stay sharded)
            logits = constrain(logits, rules, "batch", None, "vocab")
    return logits, (new_cache if cache is not None else None), aux_total
