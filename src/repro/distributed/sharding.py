"""Logical-axis sharding rules: DP / FSDP / TP / EP / SP over named meshes.

Models annotate every parameter with logical axis names (see
``repro.model.layers``); this module maps those to mesh PartitionSpecs.
Three rule sets cover the deployment envelope:

  ``tp``        tensor-parallel params over "model", replicated over data —
                right for ≤13B dense archs (params fit per-DP-replica).
  ``fsdp_tp``   TP over "model" *plus* ZeRO-3-style parameter sharding of
                the remaining large axis over ("pod","data") — required for
                llama4-400B / deepseek-671B.
  ``serve``     TP over "model", batch over ("pod","data") — inference.

Activation rules shard batch over DP axes and heads/mlp/experts over
"model" (sequence-parallel variants switch "seq" onto "model" between
attention/MLP blocks — used by the long-context perf configs).

Rules compose hierarchically for multi-pod meshes: the "pod" axis stacks
onto the data axis everywhere (gradient all-reduce becomes hierarchical:
reduce-scatter intra-pod over ICI, all-reduce across pods over DCN).

Paged-pool sharding (:class:`KVShard`): the serving tier's page pools
(``repro.serving.kv_cache``) shard along the *kv-head* axis of every page
array (GQA ``k_pages/v_pages`` — head axis; MLA ``ckv_pages/krope_pages``
— the latent-rank axis, MLA's analogue of the head axis for storage),
while the page dimension itself stays complete on every device.  Page ids
are therefore global: block tables, free lists, and the prefix index stay
replicated host-side and all admission / growth / preemption / COW logic
is unchanged.  :func:`validate_kv_shard` rejects head/rank counts the
mesh axis does not divide — an uneven split would silently replicate (the
``_divisible`` rule) and report wrong per-device memory, so it is an
error instead.

Compute follows storage differently per attention kind.  GQA decode is
head-parallel: each device runs the paged kernel on its own kv-head slice
and outputs all-gather on the head axis.  MLA decode cannot split on its
storage axis (every absorbed-form score contracts the full latent rank),
so under ``shard_map`` it parallelizes *split-K* instead: the sweep is
fixed at one split per block-table page, each device computes the
(RM, RD, RNV) partials for a contiguous 1/tp strip of pages, and the
page-ordered partial stacks all-gather before a replicated associative
combine (see ``repro.model.attention.mla_decode_paged``) — per-device
decode FLOPs are 1/tp with streams bit-identical to the unsharded sweep.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class KVShard:
    """Device sharding of the paged KV pool: pages split along the kv-head
    (GQA) / latent-rank (MLA) axis over one mesh axis.  Threaded through
    ``Runtime.kv_shard`` into the paged attention ops, which run their
    page reads/writes and per-head decode under ``shard_map`` and
    all-gather head outputs so downstream math is replicated — greedy
    token streams stay bit-identical to the unsharded paged path."""
    mesh: Mesh
    axis: str = "model"

    @property
    def size(self) -> int:
        return int(self.mesh.shape[self.axis])

    def spec(self, ndim: int, dim: int) -> P:
        """PartitionSpec sharding dimension ``dim`` of an ``ndim`` array
        over this shard's mesh axis (negative ``dim`` ok)."""
        parts = [None] * ndim
        parts[dim] = self.axis
        return P(*parts)

    @property
    def replicated(self) -> P:
        return P()


def validate_kv_shard(cfg, tp: int) -> None:
    """Reject configs whose paged-pool shard axes the mesh does not
    divide.  GQA pages shard on ``n_kv_heads`` (query heads follow: Hq =
    Hkv x group); MLA latent pages shard on ``kv_lora_rank`` and
    ``rope_dim``.  Raising here beats the silent alternative — an uneven
    axis would fall back to replication and per-device residency would be
    tp x the promised bytes."""
    if tp <= 1:
        return
    problems = []
    attns = {spec.attn for spec in cfg.layer_specs()}
    if "gqa" in attns and cfg.n_kv_heads % tp:
        problems.append(
            f"n_kv_heads={cfg.n_kv_heads} is not divisible by tp={tp}")
    if "mla" in attns:
        if cfg.mla.kv_lora_rank % tp:
            problems.append(
                f"mla.kv_lora_rank={cfg.mla.kv_lora_rank} is not "
                f"divisible by tp={tp}")
        if cfg.mla.rope_dim % tp:
            problems.append(
                f"mla.rope_dim={cfg.mla.rope_dim} is not divisible by "
                f"tp={tp}")
    if problems:
        raise ValueError(
            "cannot shard the paged KV pool over "
            f"{tp} devices: " + "; ".join(problems) +
            " — pick a tp that divides the kv-head/latent axes, or serve "
            "this config unsharded (mesh=None)")


#: paged-cache leaf name → the dimension (from the right) that shards:
#: GQA page arrays are head-major [..., P, Hkv, page_size, dh] (head axis
#: at -3); MLA latent pages are [..., P, page_size, r] (rank axis at -1).
#: Quantized-pool GQA scale pools [..., P, Hkv, page_size] shard on the
#: head axis (-2); MLA scale pools [..., P, page_size] carry one scalar
#: per full latent vector — no shardable axis — so they stay replicated
#: by falling through to the default branch below.
_PAGED_SHARD_DIMS = {"k_pages": -3, "v_pages": -3,
                     "ckv_pages": -1, "krope_pages": -1,
                     "k_scale": -2, "v_scale": -2}


def paged_cache_shardings(caches, shard: KVShard):
    """NamedShardings for an ``init_paged_cache`` tree: page arrays shard
    per :data:`_PAGED_SHARD_DIMS` (counting from the right, so stacked
    runs' leading repeats axis needs no special-casing); everything else
    (SSM slot state) is replicated."""
    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        dim = _PAGED_SHARD_DIMS.get(name)
        if dim is None:
            return NamedSharding(shard.mesh, P())
        return NamedSharding(shard.mesh, shard.spec(leaf.ndim, dim))

    return jax.tree_util.tree_map_with_path(one, caches)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name → mesh axis (or tuple of mesh axes, or None)."""
    rules: tuple

    def lookup(self, name: Optional[str]):
        for k, v in self.rules:
            if k == name:
                return v
        return None


def _data_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_rules(mesh: Mesh, mode: str = "tp",
               seq_shard: bool = False) -> dict:
    """Build (param_rules, act_rules) for a mesh + parallelism mode."""
    dp = _data_axes(mesh)
    if mode == "tp":
        param = ShardingRules((
            ("heads", "model"), ("kv_heads", "model"), ("mlp", "model"),
            ("vocab", "model"), ("experts", "model"), ("inner", "model"),
            ("embed", None), ("expert_mlp", None), ("layers", None),
            ("latent", None), ("state", None), ("head_dim", None),
        ))
    elif mode == "fsdp_tp":
        # TP over model; FSDP of the big remaining axis over the data axes.
        param = ShardingRules((
            ("heads", "model"), ("kv_heads", "model"), ("mlp", "model"),
            ("vocab", "model"), ("experts", "model"), ("inner", "model"),
            ("embed", dp), ("expert_mlp", dp), ("latent", dp),
            ("layers", None), ("state", None), ("head_dim", None),
        ))
    elif mode == "serve":
        param = ShardingRules((
            ("heads", "model"), ("kv_heads", "model"), ("mlp", "model"),
            ("vocab", "model"), ("experts", "model"), ("inner", "model"),
            ("embed", None), ("expert_mlp", None), ("layers", None),
            ("latent", None), ("state", None), ("head_dim", None),
        ))
    else:
        raise ValueError(mode)
    act = ShardingRules((
        ("batch", dp),
        ("seq", "model" if seq_shard else None),
        ("heads", "model"), ("kv_heads", "model"),
        ("mlp", "model"), ("expert_mlp", None),
        ("experts", "model"), ("vocab", "model"),
        ("embed", None), ("head_dim", None),
    ))
    return {"param": param, "act": act}


def _spec_for(axes: Sequence, rules: ShardingRules, shape=None) -> P:
    """Turn a logical-axes tuple into a PartitionSpec, dropping any mesh
    axis already used (a mesh axis may appear at most once per array) and
    any assignment that does not divide the dimension."""
    used: set = set()
    parts = []
    for i, name in enumerate(axes):
        v = rules.lookup(name)
        if v is None:
            parts.append(None)
            continue
        vt = (v,) if isinstance(v, str) else tuple(v)
        vt = tuple(a for a in vt if a not in used)
        if not vt:
            parts.append(None)
            continue
        parts.append(vt if len(vt) > 1 else vt[0])
        used.update(vt)
    return P(*parts)


def _divisible(shape, spec: P, mesh: Mesh) -> P:
    """Drop assignments that do not divide the array dimension."""
    parts = []
    for dim, part in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if part is None:
            parts.append(None)
            continue
        axes = (part,) if isinstance(part, str) else tuple(part)
        total = int(np.prod([mesh.shape[a] for a in axes]))
        parts.append(part if dim % total == 0 else None)
    return P(*parts)


def param_shardings(axes_tree, params_tree, mesh: Mesh, rules) -> Any:
    """NamedShardings for a params pytree from its logical-axes pytree."""
    pr = rules["param"]

    def one(axes, leaf):
        if axes is None:
            return NamedSharding(mesh, P())
        spec = _spec_for(axes, pr)
        spec = _divisible(leaf.shape, spec, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        one, axes_tree, params_tree,
        is_leaf=lambda t: isinstance(t, tuple) or t is None)


def act_sharder(mesh: Mesh, rules):
    """Returns f(x, logical_axes) → with_sharding_constraint(x, spec)."""
    ar = rules["act"]

    def f(x, axes):
        if axes is None or len(axes) != x.ndim:
            return x
        spec = _divisible(x.shape, _spec_for(axes, ar), mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return f


def batch_shardings(batch_specs: dict, mesh: Mesh) -> dict:
    """Shard batch inputs: leading (batch) dim over the DP axes."""
    dp = _data_axes(mesh)
    out = {}
    for k, v in batch_specs.items():
        spec = [None] * len(v.shape)
        if len(v.shape) >= 1 and v.shape[0] % int(
                np.prod([mesh.shape[a] for a in dp])) == 0:
            spec[0] = dp if len(dp) > 1 else dp[0]
        out[k] = NamedSharding(mesh, P(*spec))
    return out


def cache_shardings(cache_axes_tree, cache_tree, mesh: Mesh,
                    seq_shard_fallback: bool = True) -> Any:
    """NamedShardings for serving caches from their structural axes tree
    (see ``repro.model.transformer.cache_axes``).

    When the kv-head count does not divide the model axis (gemma2: 8 kv
    heads on a 16-way TP axis), the cache would replicate 16×; instead the
    *sequence-slot* dimension shards over "model" — decode then evaluates
    as distributed split-K over the Cascade-5 associative combine (each
    chip computes partial (RM, RD, RNV) over its KV shard; the correction
    algebra of Eqs. 48-52 merges them with an O(B·H·G) collective).
    """
    ar = ShardingRules((
        ("batch", _data_axes(mesh)),
        ("kv_heads", "model"),
        ("heads", "model"),
        ("inner", "model"),
        ("layers", None),
    ))

    def one(axes, leaf):
        spec = _divisible(leaf.shape, _spec_for(axes, ar), mesh)
        if (seq_shard_fallback and "kv_heads" in axes
                and "model" not in jax.tree.leaves(tuple(spec))):
            # kv_heads didn't shard → shard the slots dim (second-to-last)
            slot_dim = len(axes) - 2
            if leaf.shape[slot_dim] % mesh.shape["model"] == 0:
                parts = list(tuple(spec) + (None,) * (leaf.ndim - len(spec)))
                parts[slot_dim] = "model"
                spec = P(*parts)
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, cache_axes_tree, cache_tree,
                        is_leaf=lambda t: isinstance(t, tuple) or t is None)


def replica_device_groups(dp: int, tp: int = 1,
                          devices: Optional[Sequence] = None) -> list:
    """Partition ``devices`` (default: ``jax.devices()``) into ``dp``
    contiguous groups of ``tp`` for data-parallel serving replicas —
    replica i owns devices [i*tp, (i+1)*tp).  Contiguous slices keep each
    replica's TP collectives on neighbouring chips (ICI-local on TPU
    slices) while replicas never communicate — routing is host-side.

    The device count must cover every group.  The one exception is
    ``tp == 1`` on CPU devices, where the groups wrap round-robin (CPU
    smoke: every replica shares device 0 — correctness and routing
    behaviour are unchanged, only true parallel speedup is lost); on an
    accelerator, stacking replicas onto one chip would silently divide
    its memory, so that raises too.
    """
    if dp < 1 or tp < 1:
        raise ValueError(f"need dp >= 1 and tp >= 1, got dp={dp} tp={tp}")
    devs = list(devices) if devices is not None else list(jax.devices())
    need = dp * tp
    if len(devs) < need:
        if tp > 1 or devs[0].platform != "cpu":
            raise ValueError(
                f"dp={dp} tp={tp} needs {need} {devs[0].platform} "
                f"devices, have {len(devs)}")
        return [[devs[i % len(devs)]] for i in range(dp)]
    return [devs[i * tp:(i + 1) * tp] for i in range(dp)]
