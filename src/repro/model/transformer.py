"""Full decoder model: run-grouped layer stack, training + serving entry points.

The config's per-layer specs are grouped into *runs* of equal structure
(``ModelConfig.runs()``); each run's parameters are stacked on a leading
axis and executed with ``lax.scan`` (+ per-layer remat) — this keeps HLO
size and compile time bounded for the 61-layer/671B configs while leaving
heterogeneous stacks (gemma-2 local/global alternation, hymba's three
global layers, xlstm's sLSTM positions) exactly representable.

Entry points:
  ``init``          → (params, axes)
  ``forward``       → logits   [B, S, vocab]            (training)
  ``loss_fn``       → scalar + metrics                  (training)
  ``init_cache``    → per-run stacked caches            (serving, dense)
  ``init_paged_cache`` → per-run page-pool caches       (serving, paged)
  ``prefill``       → (last-token logits, caches)       (serving; dense
                      mini-cache or straight into pages via block_tables/
                      slot_ids; length-bucketed via true_len)
  ``decode_step``   → (logits, caches)                  (serving)
  ``decode_loop``   → fused multi-step decode           (serving)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.model import attention as attn_mod
from repro.model import moe as moe_mod
from repro.model import ssm as ssm_mod
from repro.model.layers import (
    Runtime, _init, apply_norm, embed, embedding_init, mlp, mlp_init,
    norm_init, softcap, unembed,
)

Params = Any


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------

def layer_init(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    ks = iter(jax.random.split(key, 8))
    params, axes = {}, {}

    def add(name, pa):
        params[name], axes[name] = pa

    add("ln1", norm_init(cfg.d_model, cfg.norm, dtype))
    if spec.attn == "gqa":
        add("attn", attn_mod.gqa_init(next(ks), cfg, dtype))
    elif spec.attn == "mla":
        add("attn", attn_mod.mla_init(next(ks), cfg, dtype))
    if spec.ssm == "mamba":
        add("ssm", ssm_mod.mamba_init(next(ks), cfg, dtype))
    elif spec.ssm == "mlstm":
        add("ssm", ssm_mod.mlstm_init(next(ks), cfg, dtype))
    elif spec.ssm == "slstm":
        add("ssm", ssm_mod.slstm_init(next(ks), cfg, dtype))
    if cfg.post_norm and (spec.attn != "none" or spec.ssm is not None):
        add("post1", norm_init(cfg.d_model, cfg.norm, dtype))
    if spec.mlp != "none":
        add("ln2", norm_init(cfg.d_model, cfg.norm, dtype))
        if spec.mlp == "dense":
            add("mlp", mlp_init(next(ks), cfg.d_model, cfg.d_ff, dtype))
        else:
            add("moe", moe_mod.moe_init(next(ks), cfg, dtype))
        if cfg.post_norm:
            add("post2", norm_init(cfg.d_model, cfg.norm, dtype))
    return params, axes


def layer_forward(p, x, cfg: ModelConfig, spec: LayerSpec, rt: Runtime):
    """Training / prefill-shape layer. x: [B, S, d]."""
    h = apply_norm(p["ln1"], x, cfg.norm)
    parts = []
    if spec.attn == "gqa":
        parts.append(attn_mod.gqa_forward(p["attn"], h, cfg, spec, rt))
    elif spec.attn == "mla":
        parts.append(attn_mod.mla_forward(p["attn"], h, cfg, spec, rt))
    if spec.ssm == "mamba":
        parts.append(ssm_mod.mamba_forward(p["ssm"], h, cfg, rt))
    elif spec.ssm == "mlstm":
        parts.append(ssm_mod.mlstm_forward(p["ssm"], h, cfg, rt))
    elif spec.ssm == "slstm":
        parts.append(ssm_mod.slstm_forward(p["ssm"], h, cfg, rt))
    y = parts[0] if len(parts) == 1 else \
        sum(parts) / len(parts)                      # hymba: mean-fuse
    if "post1" in p:
        y = apply_norm(p["post1"], y, cfg.norm)
    x = x + y
    x = rt.shard_activation(x, ("batch", "seq", "embed"))
    if spec.mlp != "none":
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        if spec.mlp == "dense":
            y2 = mlp(p["mlp"], h2, cfg.mlp_act, rt)
        else:
            y2 = moe_mod.moe_ffn(p["moe"], h2, cfg, rt)
        if "post2" in p:
            y2 = apply_norm(p["post2"], y2, cfg.norm)
        x = x + y2
        x = rt.shard_activation(x, ("batch", "seq", "embed"))
    return x


def layer_init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype) -> dict:
    cache = {}
    if spec.attn == "gqa":
        cache["attn"] = attn_mod.gqa_init_cache(cfg, spec, batch, max_len,
                                                dtype)
    elif spec.attn == "mla":
        cache["attn"] = attn_mod.mla_init_cache(cfg, batch, max_len, dtype)
    if spec.ssm == "mamba":
        cache["ssm"] = ssm_mod.mamba_init_state(cfg, batch, dtype)
    elif spec.ssm == "mlstm":
        cache["ssm"] = ssm_mod.mlstm_init_state(cfg, batch, dtype)
    elif spec.ssm == "slstm":
        cache["ssm"] = ssm_mod.slstm_init_state(cfg, batch, dtype)
    return cache


def layer_decode(p, x, cache, kv_len, cfg: ModelConfig, spec: LayerSpec,
                 rt: Runtime, block_tables: Optional[dict] = None,
                 layer=None):
    """One-token decode. x: [B, 1, d]; kv_len includes the current token.
    With ``block_tables`` (paged layout — {"full"/"w<N>": [B, W] int32})
    attention layers read/write the page pools through their table:
    ``cache["attn"]`` then holds the pools of the whole run, stacked over
    its layers, and this layer is ``layer`` of them (see
    :func:`_layer_loop`)."""
    h = apply_norm(p["ln1"], x, cfg.norm)
    parts = []
    new_cache = dict(cache)
    if spec.attn == "gqa":
        if block_tables is not None:
            y, new_cache["attn"] = attn_mod.gqa_decode_paged(
                p["attn"], h, cache["attn"],
                block_tables[attn_mod.paged_cache_key(spec)], kv_len, cfg,
                spec, rt, layer)
        else:
            y, new_cache["attn"] = attn_mod.gqa_decode(
                p["attn"], h, cache["attn"], kv_len, cfg, spec, rt)
        parts.append(y)
    elif spec.attn == "mla":
        if block_tables is not None:
            y, new_cache["attn"] = attn_mod.mla_decode_paged(
                p["attn"], h, cache["attn"], block_tables["full"], kv_len,
                cfg, spec, rt, layer)
        else:
            y, new_cache["attn"] = attn_mod.mla_decode(
                p["attn"], h, cache["attn"], kv_len, cfg, spec, rt)
        parts.append(y)
    if spec.ssm == "mamba":
        y, new_cache["ssm"] = ssm_mod.mamba_step(
            p["ssm"], h, cache["ssm"], cfg, rt)
        parts.append(y)
    elif spec.ssm == "mlstm":
        y, new_cache["ssm"] = ssm_mod.mlstm_step(
            p["ssm"], h, cache["ssm"], cfg, rt)
        parts.append(y)
    elif spec.ssm == "slstm":
        y, new_cache["ssm"] = ssm_mod.slstm_step(
            p["ssm"], h, cache["ssm"], cfg, rt)
        parts.append(y)
    y = parts[0] if len(parts) == 1 else sum(parts) / len(parts)
    if "post1" in p:
        y = apply_norm(p["post1"], y, cfg.norm)
    x = x + y
    if spec.mlp != "none":
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        if spec.mlp == "dense":
            y2 = mlp(p["mlp"], h2, cfg.mlp_act, rt)
        else:
            y2 = moe_mod.moe_ffn(p["moe"], h2, cfg, rt)
        if "post2" in p:
            y2 = apply_norm(p["post2"], y2, cfg.norm)
        x = x + y2
    return x, new_cache


def layer_verify(p, x, cache, kv_len, span, cfg: ModelConfig,
                 spec: LayerSpec, rt: Runtime,
                 block_tables: Optional[dict] = None, layer=None):
    """P-position speculative verify through one layer (the chain
    analogue of :func:`layer_decode`, same ``layer`` contract; x:
    [B, P, d]).  Attention-only:
    SSM layers carry recurrent state that cannot be rolled back by page
    surgery, so the engine never enables speculation for them."""
    if spec.ssm is not None:
        raise ValueError("speculative verify does not support SSM layers")
    h = apply_norm(p["ln1"], x, cfg.norm)
    new_cache = dict(cache)
    if spec.attn == "gqa":
        if block_tables is not None:
            y, new_cache["attn"] = attn_mod.gqa_verify_paged(
                p["attn"], h, cache["attn"],
                block_tables[attn_mod.paged_cache_key(spec)], kv_len, span,
                cfg, spec, rt, layer)
        else:
            y, new_cache["attn"] = attn_mod.gqa_verify(
                p["attn"], h, cache["attn"], kv_len, span, cfg, spec, rt)
    elif spec.attn == "mla":
        if block_tables is not None:
            y, new_cache["attn"] = attn_mod.mla_verify_paged(
                p["attn"], h, cache["attn"], block_tables["full"], kv_len,
                span, cfg, spec, rt, layer)
        else:
            y, new_cache["attn"] = attn_mod.mla_verify(
                p["attn"], h, cache["attn"], kv_len, span, cfg, spec, rt)
    else:
        raise ValueError(f"layer has no attention to verify: {spec}")
    if "post1" in p:
        y = apply_norm(p["post1"], y, cfg.norm)
    x = x + y
    if spec.mlp != "none":
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        if spec.mlp == "dense":
            y2 = mlp(p["mlp"], h2, cfg.mlp_act, rt)
        else:
            y2 = moe_mod.moe_ffn(p["moe"], h2, cfg, rt)
        if "post2" in p:
            y2 = apply_norm(p["post2"], y2, cfg.norm)
        x = x + y2
    return x, new_cache


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, key, rt: Runtime = Runtime()):
    """Returns (params, axes). Run params are stacked on a leading axis."""
    dtype = rt.param_dtype
    keys = jax.random.split(key, len(cfg.runs()) + 3)
    params: dict = {}
    axes: dict = {}

    params["embed"], axes["embed"] = embedding_init(
        keys[0], cfg.vocab, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["unembed"], axes["unembed"] = embedding_init(
            keys[1], cfg.vocab, cfg.d_model, dtype)
    if cfg.frontend != "tokens":
        params["frontend_proj"] = {
            "w": _init(keys[2], (cfg.d_model, cfg.d_model),
                       1 / math.sqrt(cfg.d_model), dtype)}
        axes["frontend_proj"] = {"w": ("embed", "embed")}

    runs_p, runs_a = [], []
    for i, (pattern, reps) in enumerate(cfg.runs()):
        pos_p, pos_a = [], []
        for j, spec in enumerate(pattern):
            rk = jax.random.split(
                jax.random.fold_in(key, 1000 + 16 * i + j), reps)
            if reps == 1:
                p, a = layer_init(rk[0], cfg, spec, dtype)
            else:
                p = jax.vmap(
                    lambda kk: layer_init(kk, cfg, spec, dtype)[0])(rk)
                a = layer_init(rk[0], cfg, spec, dtype)[1]
                a = jax.tree.map(lambda ax: ("layers", *ax), a,
                                 is_leaf=lambda t: isinstance(t, tuple))
            pos_p.append(p)
            pos_a.append(a)
        runs_p.append(pos_p)
        runs_a.append(pos_a)
    params["runs"] = runs_p
    axes["runs"] = runs_a

    params["final_norm"], axes["final_norm"] = norm_init(
        cfg.d_model, cfg.norm, dtype)

    if cfg.n_mtp:
        mtp_p, mtp_a = [], []
        for j in range(cfg.n_mtp):
            spec = cfg.layer_specs()[-1]
            p, a = layer_init(jax.random.fold_in(key, 2000 + j), cfg, spec,
                              dtype)
            mtp_p.append(p)
            mtp_a.append(a)
        params["mtp"] = mtp_p
        axes["mtp"] = mtp_a
    return params, axes


def _embed_inputs(cfg: ModelConfig, params, batch: dict, rt: Runtime):
    dtype = rt.activation_dtype
    if cfg.frontend == "tokens":
        x = embed(params["embed"], batch["inputs"], dtype)
    else:
        # modality stub: precomputed frame/patch embeddings [B, S, d]
        x = batch["inputs"].astype(dtype) @ params["frontend_proj"]["w"].astype(dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return rt.shard_activation(x, ("batch", "seq", "embed"))


def _run_forward(params_run, x, cfg, pattern, reps, rt):
    """Apply one (pattern, reps) run: scan over reps of the pattern."""
    def apply_pattern(ps, h):
        for spec_j, p_j in zip(pattern, ps):
            h = layer_forward(p_j, h, cfg, spec_j, rt)
        return h

    if reps == 1:
        return jax.checkpoint(apply_pattern)(tuple(params_run), x)

    if rt.unroll_runs:
        # dry-run fidelity mode: XLA's cost_analysis does not multiply
        # while-loop trip counts, so roofline FLOPs need unrolled layers.
        for i in range(reps):
            ps = tuple(jax.tree.map(lambda a: a[i], p_j)
                       for p_j in params_run)
            x = jax.checkpoint(apply_pattern)(ps, x)
        return x

    def body(h, ps):
        return apply_pattern(ps, h), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, tuple(params_run))
    return x


def forward(cfg: ModelConfig, params, batch: dict,
            rt: Runtime = Runtime()) -> jnp.ndarray:
    """Training-shape forward. Returns logits [B, S, vocab]."""
    x = _embed_inputs(cfg, params, batch, rt)
    for (pattern, reps), p_run in zip(cfg.runs(), params["runs"]):
        x = _run_forward(p_run, x, cfg, pattern, reps, rt)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(head, x)
    logits = rt.shard_activation(logits, ("batch", "seq", "vocab"))
    logits = softcap(logits, cfg.final_softcap)
    return logits


def loss_fn(cfg: ModelConfig, params, batch: dict,
            rt: Runtime = Runtime()):
    """Causal LM loss (next-token xent) + optional MTP losses."""
    logits = forward(cfg, params, batch, rt)
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt_logit = jnp.take_along_axis(
        logits.astype(jnp.float32), targets[..., None], axis=-1)[..., 0]
    nll = (lse - tgt_logit) * mask
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    metrics = {"loss": loss, "tokens": jnp.sum(mask)}

    if cfg.n_mtp and "mtp_targets" in batch:
        # DeepSeek-style multi-token prediction: each extra head applies one
        # more transformer layer to the trunk output and predicts t+1+j.
        x = _embed_inputs(cfg, params, batch, rt)
        for (pattern, reps), p_run in zip(cfg.runs(), params["runs"]):
            x = _run_forward(p_run, x, cfg, pattern, reps, rt)
        head = params["embed"] if cfg.tie_embeddings else params["unembed"]
        spec = cfg.layer_specs()[-1]
        mtp_loss = 0.0
        for j, p_mtp in enumerate(params["mtp"]):
            x = layer_forward(p_mtp, x, cfg, spec, rt)
            lg = softcap(unembed(
                head, apply_norm(params["final_norm"], x, cfg.norm)),
                cfg.final_softcap)
            tj = batch["mtp_targets"][..., j]
            lse_j = jax.nn.logsumexp(lg.astype(jnp.float32), axis=-1)
            tl_j = jnp.take_along_axis(
                lg.astype(jnp.float32), tj[..., None], axis=-1)[..., 0]
            mtp_loss = mtp_loss + jnp.sum((lse_j - tl_j) * mask) / \
                jnp.maximum(jnp.sum(mask), 1.0)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.1 * mtp_loss
    metrics["total_loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Per-run, per-pattern-position caches (stacked over repeats)."""
    caches = []
    for pattern, reps in cfg.runs():
        pos = []
        for spec in pattern:
            c1 = layer_init_cache(cfg, spec, batch, max_len, dtype)
            if reps > 1:
                c1 = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (reps, *a.shape)).copy(),
                    c1)
            pos.append(c1)
        caches.append(pos)
    return caches


def layer_init_paged_cache(cfg: ModelConfig, spec: LayerSpec, slots: int,
                           num_pages: dict, page_size: int, dtype,
                           kv_dtype: str | None = None) -> dict:
    """Paged counterpart of :func:`layer_init_cache`: attention K/V live in
    page pools (``num_pages`` keyed like the block tables — "full" /
    "w<window>"); SSM state stays per-slot dense (it is O(1) per slot).
    ``kv_dtype`` ("fp8_e4m3" | "int8" | None) stores the pools quantized
    with parallel fp16 scale pools — see the attention init helpers."""
    cache = {}
    if spec.attn == "gqa":
        cache["attn"] = attn_mod.gqa_init_paged_cache(
            cfg, num_pages[attn_mod.paged_cache_key(spec)], page_size,
            dtype, kv_dtype=kv_dtype)
    elif spec.attn == "mla":
        cache["attn"] = attn_mod.mla_init_paged_cache(
            cfg, num_pages["full"], page_size, dtype, kv_dtype=kv_dtype)
    if spec.ssm == "mamba":
        cache["ssm"] = ssm_mod.mamba_init_state(cfg, slots, dtype)
    elif spec.ssm == "mlstm":
        cache["ssm"] = ssm_mod.mlstm_init_state(cfg, slots, dtype)
    elif spec.ssm == "slstm":
        cache["ssm"] = ssm_mod.slstm_init_state(cfg, slots, dtype)
    return cache


def init_paged_cache(cfg: ModelConfig, slots: int, num_pages: dict,
                     page_size: int, dtype, kv_dtype: str | None = None):
    """Per-run paged caches mirroring :func:`init_cache`'s tree structure
    (stacked over repeats).  The decode and verify layer loops carry each
    pattern position's stacked pools whole and update a layer's rows in
    place (:func:`_layer_loop`); prefill scans over them per layer like
    the dense caches.  Every layer owns its own page storage; the block
    tables (one per capacity class, shared by all layers of the class) are
    managed host-side by :class:`repro.serving.kv_cache.PagedKVCache` and
    passed per dispatch.

    Device sharding note: arrays are created unplaced; ``PagedKVCache``
    device_puts them with ``sharding.paged_cache_shardings`` when the
    pool is mesh-sharded (head/rank axis split, page axis complete per
    device) — the tree shape here is what that sharding walk keys on
    (leaf names ``k_pages``/``v_pages``/``ckv_pages``/``krope_pages``),
    and the per-shard write masks live in the attention layer's
    ``shard_map`` paths, not here."""
    caches = []
    for pattern, reps in cfg.runs():
        pos = []
        for spec in pattern:
            c1 = layer_init_paged_cache(cfg, spec, slots, num_pages,
                                        page_size, dtype, kv_dtype)
            if reps > 1:
                c1 = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (reps, *a.shape)).copy(),
                    c1)
            pos.append(c1)
        caches.append(pos)
    return caches


def copy_cache_pages(cfg: ModelConfig, caches, key: str, src: int,
                     dst: int):
    """Device-side page copy ``pages[dst] = pages[src]`` for every
    attention layer in capacity class ``key`` (COW for shared-prefix
    pages — see :mod:`repro.serving.kv_cache`).  ``caches`` must come from
    :func:`init_paged_cache`; stacked runs carry a leading repeats axis,
    so the page axis is located per run.  Returns the rebuilt tree."""
    from repro.model.attention import paged_cache_key

    out = []
    for (pattern, reps), cache_run in zip(cfg.runs(), caches):
        pos = []
        for spec, c1 in zip(pattern, cache_run):
            matches = (spec.attn == "gqa"
                       and paged_cache_key(spec) == key) or \
                      (spec.attn == "mla" and key == "full")
            if matches and "attn" in c1:
                c1 = dict(c1)
                if reps > 1:
                    c1["attn"] = {k: a.at[:, dst].set(a[:, src])
                                  for k, a in c1["attn"].items()}
                else:
                    c1["attn"] = {k: a.at[dst].set(a[src])
                                  for k, a in c1["attn"].items()}
            pos.append(c1)
        out.append(pos)
    return out


def cache_axes(cfg: ModelConfig):
    """Structural logical-axes tree mirroring ``init_cache`` output."""
    def layer_axes(spec: LayerSpec) -> dict:
        ax = {}
        if spec.attn == "gqa":
            ax["attn"] = {"k": ("batch", "kv_heads", None, None),
                          "v": ("batch", "kv_heads", None, None)}
        elif spec.attn == "mla":
            ax["attn"] = {"ckv": ("batch", None, None),
                          "krope": ("batch", None, None)}
        if spec.ssm == "mamba":
            ax["ssm"] = {"h": ("batch", "inner", None),
                         "conv": ("batch", None, "inner")}
        elif spec.ssm == "mlstm":
            ax["ssm"] = {"c": ("batch", "heads", None, None),
                         "n": ("batch", "heads", None),
                         "m": ("batch", "heads"),
                         "conv": ("batch", None, "inner")}
        elif spec.ssm == "slstm":
            ax["ssm"] = {k: ("batch", "embed") for k in ("c", "n", "m", "h")}
        return ax

    axes = []
    for pattern, reps in cfg.runs():
        pos = []
        for spec in pattern:
            a = layer_axes(spec)
            if reps > 1:
                a = jax.tree.map(lambda t: ("layers", *t), a,
                                 is_leaf=lambda t: isinstance(t, tuple))
            pos.append(a)
        axes.append(pos)
    return axes


def _layer_loop(cfg: ModelConfig, params, x, caches, rt: Runtime,
                paged: bool, step):
    """Run ``x`` through every layer for one decode or verify step:
    ``step(p, h, cache, spec, layer) -> (h, new_cache)`` is one layer.

    Dense caches (``paged`` False) scan each stacked run with its per-layer
    cache slices as the scan's xs/ys.  Paged, each pattern position's page
    pools (``cache["attn"]``, stacked over the run's repeats) ride in the
    loop *carry* instead, and every layer writes and reads its rows of
    them at index ``layer`` in place: slicing a layer's pool out as an xs
    and stacking the updated one as a ys would copy every layer's pool per
    step.  Everything else (params, SSM state) stays xs/ys.  A run of one
    repeat holds unstacked pools, the ``L = 1`` case (``pool[None]``,
    layer 0)."""
    def split(c):
        if not paged or "attn" not in c:
            return None, c
        return c["attn"], {k: v for k, v in c.items() if k != "attn"}

    def call(p, h, pool, rest, spec, layer):
        c = rest if pool is None else {**rest, "attn": pool}
        h, c = step(p, h, c, spec, layer)
        pool, rest = split(c)
        return h, pool, rest

    new_caches = []
    for (pattern, reps), p_run, cache in zip(cfg.runs(), params["runs"],
                                             caches):
        pools, rests = map(list, zip(*[split(c) for c in cache]))
        if reps == 1:
            for j, (spec_j, p_j) in enumerate(zip(pattern, p_run)):
                lifted = jax.tree.map(lambda a: a[None], pools[j])
                x, pool, rests[j] = call(p_j, x, lifted, rests[j], spec_j, 0)
                pools[j] = jax.tree.map(lambda a: a[0], pool)
        elif rt.unroll_runs:
            outs = [[] for _ in pattern]
            for i in range(reps):
                for j, (spec_j, p_j, rest) in enumerate(
                        zip(pattern, p_run, rests)):
                    p_i = jax.tree.map(lambda a: a[i], p_j)
                    r_i = jax.tree.map(lambda a: a[i], rest)
                    x, pools[j], r_i = call(p_i, x, pools[j], r_i, spec_j, i)
                    outs[j].append(r_i)
            rests = [jax.tree.map(lambda *xs: jnp.stack(xs), *o)
                     for o in outs]
        else:
            def body(carry, xs):
                h, pools = carry
                i, ps, rests = xs
                pools_out, rests_out = [], []
                for spec_j, p_j, pool, rest in zip(pattern, ps, pools,
                                                   rests):
                    h, pool, rest = call(p_j, h, pool, rest, spec_j, i)
                    pools_out.append(pool)
                    rests_out.append(rest)
                return (h, tuple(pools_out)), tuple(rests_out)

            (x, pools), rests = jax.lax.scan(
                body, (x, tuple(pools)),
                (jnp.arange(reps, dtype=jnp.int32), tuple(p_run),
                 tuple(rests)))
        new_caches.append([rest if pool is None else {**rest, "attn": pool}
                           for pool, rest in zip(pools, rests)])
    return x, new_caches


def decode_step(cfg: ModelConfig, params, token_or_embed, caches,
                kv_len: jnp.ndarray, rt: Runtime = Runtime(),
                block_tables: Optional[dict] = None):
    """One decode step for the whole batch.

    token_or_embed: [B, 1] int tokens or [B, 1, d] embeddings.
    kv_len: [B] sequence length *including* the current token.
    ``block_tables`` selects the paged cache layout (see
    :func:`layer_decode`; the layer loop carries the page pools, see
    :func:`_layer_loop`); None decodes against dense caches.
    Returns (logits [B, vocab], new_caches).
    """
    batch = {"inputs": token_or_embed}
    x = _embed_inputs(cfg, params, batch, rt)

    def step(p, h, c, spec, layer):
        return layer_decode(p, h, c, kv_len, cfg, spec, rt, block_tables,
                            layer)

    x, new_caches = _layer_loop(cfg, params, x, caches, rt,
                                block_tables is not None, step)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(head, x[:, 0])
    logits = rt.shard_activation(logits, ("batch", "vocab"))
    logits = softcap(logits, cfg.final_softcap)
    return logits, new_caches


def verify_step(cfg: ModelConfig, params, tokens, caches,
                kv_len: jnp.ndarray, span: jnp.ndarray,
                rt: Runtime = Runtime(),
                block_tables: Optional[dict] = None):
    """Score a P-token draft chain in one fused dispatch.

    tokens: [B, P] int — chain position 0 is the model's own next token
    (the base decode step), positions 1..P-1 the speculative drafts.
    kv_len: [B] cache length *including* chain position 0; position j
    occupies kv_len - 1 + j and attends causally to keys < kv_len + j.
    span: [B] real chain positions per row (writes/outputs beyond it are
    dropped/ignored).  Returns (logits [B, P, vocab], new_caches) —
    logits[:, j] is what :func:`decode_step` would return after
    committing the chain prefix tokens[:, :j+1]: the attention reads are
    bit-exact vs the single-token kernels (same split geometry; see
    ``kernels.ops.fusemax_decode``), and the surrounding [B, P, d]
    projection/MLP matmuls match the [B, 1, d] path to float32
    reduction-order tolerance — greedy argmax, which is all the accept
    rule consumes, agrees (asserted end-to-end across layouts in
    ``tests/test_speculative.py``)."""
    batch = {"inputs": tokens}
    x = _embed_inputs(cfg, params, batch, rt)

    def step(p, h, c, spec, layer):
        return layer_verify(p, h, c, kv_len, span, cfg, spec, rt,
                            block_tables, layer)

    x, new_caches = _layer_loop(cfg, params, x, caches, rt,
                                block_tables is not None, step)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(head, x)                            # [B, P, vocab]
    logits = rt.shard_activation(logits, ("batch", "seq", "vocab"))
    logits = softcap(logits, cfg.final_softcap)
    return logits, new_caches


def speculative_step(cfg: ModelConfig, params, last_logits, drafts, caches,
                     kv_len: jnp.ndarray, remaining: jnp.ndarray,
                     rt: Runtime = Runtime(),
                     block_tables: Optional[dict] = None):
    """One fused speculate→verify→accept step (greedy).

    last_logits: [B, vocab] — each slot's logits over its last committed
    token (the base loop's sampling state).  drafts: [B, P-1] proposer
    guesses for the tokens *after* the model's next one.  kv_len: [B]
    committed lengths (NOT counting the to-be-committed next token);
    remaining: [B] tokens each slot may still emit (0 = spent).

    The chain fed to :func:`verify_step` is [argmax(last_logits), drafts]
    — position 0 is the ordinary decode step, so even a fully rejected
    draft commits one token and the loop always advances.  A draft prefix
    is accepted while each draft equals the argmax of the *previous*
    position's verify logits; by induction the committed stream is
    bit-identical to running :func:`decode_step` token by token (verify
    logits match the single-token path bit-for-bit on the jnp kernels,
    and every committed token is still the model's own argmax).

    Returns (tokens [P, B], advance [B], kv_len, remaining, last_logits,
    new_caches): ``tokens[:advance[i], i]`` is slot i's committed chain;
    post-state equals ``advance[i]`` iterations of the base loop."""
    b, vocab = last_logits.shape
    p_minus_1 = drafts.shape[1]
    p_total = p_minus_1 + 1
    active = remaining > 0
    nxt = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    nxt = jnp.where(active, nxt, 0)
    fed = jnp.concatenate([nxt[:, None], drafts.astype(jnp.int32)], axis=1)
    span = jnp.where(active, jnp.minimum(p_total, remaining), 0)
    kv0 = kv_len + active.astype(kv_len.dtype)           # incl. position 0

    logits, new_caches = verify_step(
        cfg, params, fed, caches, kv0, span, rt, block_tables)

    guess = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, P]
    ok = (fed[:, 1:] == guess[:, :-1]) & \
        (jnp.arange(1, p_total)[None] < span[:, None])
    acc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    advance = jnp.where(active, 1 + acc.sum(axis=1), 0)

    new_last = logits[jnp.arange(b), jnp.maximum(advance - 1, 0)]
    last_logits = jnp.where(active[:, None], new_last, last_logits)
    kv_len = kv_len + advance.astype(kv_len.dtype)
    remaining = remaining - advance.astype(remaining.dtype)
    return (fed.T, advance, kv_len, remaining, last_logits, new_caches)


def prefill(cfg: ModelConfig, params, batch: dict, caches,
            rt: Runtime = Runtime(), kv_offset: int = 0,
            true_len: Optional[jnp.ndarray] = None,
            block_tables: Optional[dict] = None,
            slot_ids: Optional[jnp.ndarray] = None,
            cached_len: Optional[jnp.ndarray] = None):
    """Process a prompt (or prompt chunk), filling caches.  Returns
    (logits_last, caches).

    Implemented as repeated full-sequence layer forwards plus cache writes:
    K/V (or latent / SSM state) are recomputed per layer in prefill shape
    and written into the cache slots [kv_offset, kv_offset+S).  Ring caches
    for windowed layers receive the last ``window`` positions.

    ``kv_offset`` (a static int) enables *chunked* prefill: positions
    [0, kv_offset) must already be cached, and the chunk's queries attend
    the cached history (full caches via q_offset; ring caches via a
    gathered band).  SSM state continues from the cache automatically.

    ``true_len`` ([B] int32, length-bucketed batches): each row's real
    prompt length inside the padded bucket.  Cache writes and SSM stepping
    past a row's true length are masked, and the returned logits are
    gathered at each row's last real token *within this chunk* (rows whose
    last token lies in another chunk return garbage — the caller selects).

    ``block_tables`` + ``slot_ids`` switch to the *paged* layout: caches
    must come from :func:`init_paged_cache`, attention K/V scatter into
    page pools through ``block_tables[...][slot_ids]``, and SSM states live
    in the slot rows ``slot_ids`` of the full [slots, ...] state arrays
    (reset at kv_offset == 0 — admission semantics).  No dense mini-cache
    is materialized.

    ``cached_len`` ([B] int32, paged layout only): each row's
    shared-prefix length — positions below it are served by pages mapped
    from the prefix index, which this prefill must *read but never
    rewrite*.  Page writes below a row's ``cached_len`` are masked
    (dropped), independently of the static ``kv_offset`` the dispatch was
    grouped under.
    """
    x = _embed_inputs(cfg, params, batch, rt)
    s_len = x.shape[1]
    if slot_ids is not None and true_len is None:
        true_len = jnp.full((x.shape[0],), kv_offset + s_len, jnp.int32)
    new_caches = []
    for (pattern, reps), p_run, cache in zip(cfg.runs(), params["runs"],
                                             caches):
        if reps == 1:
            cs = []
            for spec_j, p_j, c_j in zip(pattern, p_run, cache):
                x, c_new = _prefill_layer(p_j, x, c_j, cfg, spec_j, rt,
                                          s_len, kv_offset, true_len,
                                          block_tables, slot_ids,
                                          cached_len)
                cs.append(c_new)
            new_caches.append(cs)
            continue

        if rt.unroll_runs:
            outs = [[] for _ in pattern]
            for i in range(reps):
                for j, (spec_j, p_j, c_j) in enumerate(
                        zip(pattern, p_run, cache)):
                    p_i = jax.tree.map(lambda a: a[i], p_j)
                    c_i = jax.tree.map(lambda a: a[i], c_j)
                    x, c_new = _prefill_layer(p_i, x, c_i, cfg, spec_j, rt,
                                              s_len, kv_offset, true_len,
                                              block_tables, slot_ids,
                                              cached_len)
                    outs[j].append(c_new)
            new_caches.append([
                jax.tree.map(lambda *xs: jnp.stack(xs), *o) for o in outs])
            continue

        def body(h, pc):
            ps, cs_in = pc
            cs_out = []
            for spec_j, p_j, c_j in zip(pattern, ps, cs_in):
                h, c_new = _prefill_layer(p_j, h, c_j, cfg, spec_j, rt,
                                          s_len, kv_offset, true_len,
                                          block_tables, slot_ids,
                                          cached_len)
                cs_out.append(c_new)
            return h, tuple(cs_out)

        x, c = jax.lax.scan(body, x, (tuple(p_run), tuple(cache)))
        new_caches.append(list(c))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    if true_len is None:
        last = x[:, -1]
    else:
        idx = jnp.clip(true_len - 1 - kv_offset, 0, s_len - 1)
        last = x[jnp.arange(x.shape[0]), idx]
    logits = unembed(head, last)
    logits = rt.shard_activation(logits, ("batch", "vocab"))
    logits = softcap(logits, cfg.final_softcap)
    return logits, new_caches


def _prefill_layer(p, x, cache, cfg, spec, rt, s_len, kv_offset=0,
                   true_len=None, block_tables=None, slot_ids=None,
                   cached_len=None):
    """Layer forward that also populates the serving cache.  With
    ``kv_offset > 0`` (chunked-prefill continuation) attention layers
    attend the cached history via the ``*_prefill_chunk`` paths; SSM
    layers continue from the cached state either way.  ``true_len`` masks
    writes/stepping for padded bucket tails; ``block_tables``/``slot_ids``
    select the paged layout (see :func:`prefill`)."""
    paged = slot_ids is not None
    h = apply_norm(p["ln1"], x, cfg.norm)
    parts = []
    new_cache = dict(cache)
    if spec.attn == "gqa":
        if paged:
            bt_rows = block_tables[attn_mod.paged_cache_key(spec)][slot_ids]
            y, new_cache["attn"] = attn_mod.gqa_prefill_paged(
                p["attn"], h, cache["attn"], bt_rows, kv_offset, cfg, spec,
                rt, true_len, cached_len)
        elif kv_offset:
            y, new_cache["attn"] = attn_mod.gqa_prefill_chunk(
                p["attn"], h, cache["attn"], kv_offset, cfg, spec, rt,
                true_len)
        else:
            y = attn_mod.gqa_forward(p["attn"], h, cfg, spec, rt)
            positions = jnp.broadcast_to(
                jnp.arange(s_len), (h.shape[0], s_len))
            _, k_new, v_new = attn_mod._proj_qkv(p["attn"], h, cfg,
                                                 positions, rt)
            slots = cache["attn"]["k"].shape[2]
            if spec.window is not None and true_len is not None:
                # ring + bucket padding: shared masked ring scatter keeps
                # the last min(true_len, window) real positions per row
                kc, vc = attn_mod.ring_write_masked(
                    cache["attn"]["k"], cache["attn"]["v"], k_new, v_new,
                    0, true_len)
            elif slots >= s_len:
                kc = cache["attn"]["k"].at[:, :, :s_len].set(k_new)
                vc = cache["attn"]["v"].at[:, :, :s_len].set(v_new)
            else:  # ring: keep the trailing `slots` positions
                tail_k = k_new[:, :, s_len - slots:]
                tail_v = v_new[:, :, s_len - slots:]
                # place at slot = pos % slots
                pos = jnp.arange(s_len - slots, s_len) % slots
                kc = cache["attn"]["k"].at[:, :, pos].set(tail_k)
                vc = cache["attn"]["v"].at[:, :, pos].set(tail_v)
            new_cache["attn"] = {"k": kc, "v": vc}
        parts.append(y)
    elif spec.attn == "mla":
        if paged:
            y, new_cache["attn"] = attn_mod.mla_prefill_paged(
                p["attn"], h, cache["attn"], block_tables["full"][slot_ids],
                kv_offset, cfg, spec, rt, true_len, cached_len)
        elif kv_offset:
            y, new_cache["attn"] = attn_mod.mla_prefill_chunk(
                p["attn"], h, cache["attn"], kv_offset, cfg, spec, rt)
        else:
            y = attn_mod.mla_forward(p["attn"], h, cfg, spec, rt)
            positions = jnp.broadcast_to(
                jnp.arange(s_len), (h.shape[0], s_len))
            _, _, ckv_new, krope_new = attn_mod._mla_qkv_latent(
                p["attn"], h, cfg, positions)
            new_cache["attn"] = {
                "ckv": cache["attn"]["ckv"].at[:, :s_len].set(ckv_new),
                "krope": cache["attn"]["krope"].at[:, :s_len].set(krope_new),
            }
        parts.append(y)
    if spec.ssm is not None:
        if paged:
            # paged layout keeps SSM state in the slot rows of the full
            # [slots, ...] arrays: gather the admitted rows (fresh state at
            # admission), step them, scatter back
            state = jax.tree.map(lambda a: a[slot_ids], cache["ssm"])
            if kv_offset == 0:
                n = x.shape[0]
                dtype = state["conv"].dtype if "conv" in state \
                    else jnp.float32
                if spec.ssm == "mamba":
                    state = ssm_mod.mamba_init_state(cfg, n, dtype)
                elif spec.ssm == "mlstm":
                    state = ssm_mod.mlstm_init_state(cfg, n, dtype)
                else:
                    state = ssm_mod.slstm_init_state(cfg, n, dtype)
            y, st = _prefill_ssm(p["ssm"], h, state, cfg, spec, rt,
                                 true_len, kv_offset)
            new_cache["ssm"] = jax.tree.map(
                lambda a, r: a.at[slot_ids].set(r.astype(a.dtype)),
                cache["ssm"], st)
        else:
            y, st = _prefill_ssm(p["ssm"], h, cache["ssm"], cfg, spec, rt,
                                 true_len, kv_offset)
            new_cache["ssm"] = st
        parts.append(y)
    y = parts[0] if len(parts) == 1 else sum(parts) / len(parts)
    if "post1" in p:
        y = apply_norm(p["post1"], y, cfg.norm)
    x = x + y
    if spec.mlp != "none":
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        y2 = mlp(p["mlp"], h2, cfg.mlp_act, rt) if spec.mlp == "dense" \
            else moe_mod.moe_ffn(p["moe"], h2, cfg, rt)
        if "post2" in p:
            y2 = apply_norm(p["post2"], y2, cfg.norm)
        x = x + y2
    return x, new_cache


def _prefill_ssm(p, h, state, cfg, spec, rt, true_len=None, kv_offset=0):
    """Run the SSM over the prompt sequentially via its step function —
    exact state handoff (the chunked trainer path has no state output).

    ``true_len`` enables *masked stepping* for length-bucketed batches:
    rows whose real prompt ended before global position kv_offset + t keep
    their state frozen through the padded tail, so the handed-off state is
    exactly the state after the last real token."""
    if spec.ssm == "mamba":
        step = functools.partial(ssm_mod.mamba_step, p, cfg=cfg, rt=rt)
    elif spec.ssm == "mlstm":
        step = functools.partial(ssm_mod.mlstm_step, p, cfg=cfg, rt=rt)
    else:
        step = functools.partial(ssm_mod.slstm_step, p, cfg=cfg, rt=rt)

    def body(st, xs):
        t, ht = xs
        y, st_new = step(ht[:, None], st)
        if true_len is not None:
            keep = (kv_offset + t) < true_len            # [B]
            st_new = jax.tree.map(
                lambda new, old: jnp.where(
                    keep.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                st_new, st)
        return st_new, y[:, 0]

    st, ys = jax.lax.scan(
        body, state, (jnp.arange(h.shape[1]), jnp.moveaxis(h, 0, 1)))
    return jnp.moveaxis(ys, 0, 1), st


def scatter_cache_slots(cfg: ModelConfig, caches, sub, slot_ids):
    """Write ``sub`` (a batch=N cache tree from :func:`init_cache`) into
    batch rows ``slot_ids`` ([N] int32) of ``caches``.

    The batch axis position varies per leaf (stacked runs carry a leading
    "layers" axis) — it is located via :func:`cache_axes`.  jit-safe; used
    by the serving engine to land batched prefills in their slots.
    """
    axes = cache_axes(cfg)
    is_ax = lambda t: isinstance(t, tuple)
    leaves_c, treedef = jax.tree.flatten(caches)
    leaves_s = jax.tree.leaves(sub)
    leaves_a = jax.tree.leaves(axes, is_leaf=is_ax)
    if not (len(leaves_c) == len(leaves_s) == len(leaves_a)):
        raise ValueError("cache / sub-cache / axes trees do not match")

    def put(dst, src, ax):
        b = ax.index("batch")
        d = jnp.moveaxis(dst, b, 0)
        s = jnp.moveaxis(src, b, 0)
        return jnp.moveaxis(d.at[slot_ids].set(s.astype(d.dtype)), 0, b)

    out = [put(c, s, a) for c, s, a in zip(leaves_c, leaves_s, leaves_a)]
    return jax.tree.unflatten(treedef, out)


def decode_loop(cfg: ModelConfig, params, caches, kv_len, last_logits,
                remaining, key, *, n_steps: int, rt: Runtime = Runtime(),
                temperature: float = 0.0,
                block_tables: Optional[dict] = None):
    """Fused multi-step decode: one dispatch advances every slot by up to
    ``n_steps`` tokens, sampling on-device.

    Per step (matching the engine's per-token order): sample the next token
    from ``last_logits``, advance ``kv_len`` for active slots, run
    :func:`decode_step`, and decrement ``remaining``.  Slots with
    ``remaining <= 0`` are masked — their kv_len, logits and token stream
    freeze (cache rows may be clobbered but are reset at re-admission).

    Returns ``(tokens [n_steps, B], caches, kv_len, last_logits, remaining,
    key, steps)`` where ``steps`` is the number of iterations actually
    executed — a ``lax.while_loop`` exits early once every slot's budget is
    spent, so ``n_steps`` can be a generous (jit-key-stable) upper bound
    without paying for masked tail steps.  Greedy (``temperature <= 0``)
    token streams are bit-identical to per-token :func:`decode_step`
    calls; sampled streams draw one key per step via ``jax.random.split``.

    ``block_tables`` (paged layout) is loop-invariant: the engine reserves
    pages covering every slot's worst-case growth for the chunk *before*
    dispatching, so no allocation can be needed mid-loop.  The loop
    carries the whole cache tree; on the paged layout each step's layer
    loop carries the stacked page pools in turn and writes each layer's
    new rows into them in place, so no step copies a pool.
    """
    b = kv_len.shape[0]
    toks0 = jnp.zeros((n_steps, b), jnp.int32)

    def cond(state):
        i, _, _, _, remaining, _, _ = state
        return (i < n_steps) & jnp.any(remaining > 0)

    def body(state):
        i, caches, kv_len, logits, remaining, key, toks = state
        active = remaining > 0
        if temperature <= 0.0:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(
                sub, logits / temperature, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, 0)
        toks = jax.lax.dynamic_update_index_in_dim(toks, nxt, i, 0)
        kv_new = kv_len + active.astype(jnp.int32)
        new_logits, caches = decode_step(cfg, params, nxt[:, None], caches,
                                         kv_new, rt, block_tables)
        logits = jnp.where(active[:, None],
                           new_logits.astype(logits.dtype), logits)
        return (i + 1, caches, kv_new, logits,
                remaining - active.astype(jnp.int32), key, toks)

    steps, caches, kv_len, logits, remaining, key, toks = \
        jax.lax.while_loop(
            cond, body,
            (jnp.asarray(0, jnp.int32), caches, kv_len, last_logits,
             remaining, key, toks0))
    return toks, caches, kv_len, logits, remaining, key, steps


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
