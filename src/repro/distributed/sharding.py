"""Per-tensor sharding rules: FSDP + TP + EP + SP on a (pod, data, model) mesh.

Parameters are named with '/'-joined paths; rules are keyed on the leaf name
and tensor rank. The same rules serve the single-pod ("data", "model") and
multi-pod ("pod", "data", "model") meshes: the batch / FSDP axis is
``("pod", "data")`` when a pod axis exists.

Design (see DESIGN.md §5):
  * TP  : attention heads, MLP hidden, vocab        -> "model"
  * EP  : MoE expert dim                            -> "model"
  * FSDP: the non-TP major dim of every weight      -> "data" (+"pod")
  * DP  : batch                                     -> ("pod","data")
  * SP  : long-context KV cache sequence dim        -> "model" (when kv heads
          cannot fill the model axis, e.g. MQA)
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mesh_axes(mesh: Mesh):
    names = mesh.axis_names
    dp = tuple(n for n in names if n in ("pod", "data"))
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    mp = "model" if "model" in names else None
    return dp, mp


def _divisible(dim: int, mesh: Mesh, axis) -> bool:
    if axis is None or dim <= 0:
        return False
    if isinstance(axis, tuple):
        size = 1
        for a in axis:
            size *= mesh.shape[a]
    else:
        size = mesh.shape[axis]
    return dim % size == 0


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# (regex on the param path, spec builder). Leading scan axis (layers) is
# handled by prepending None when the tensor has the extra rank.
# Builders receive (shape, dp, mp) for *unstacked* rank.
_RULES = [
    # token / positional embeddings: vocab|positions over model, d over fsdp
    (r"tok_embed$",        lambda s: ("model", "data")),
    (r"pos_embed$",        lambda s: (None, "data")),
    (r"label_embed$",      lambda s: (None, "data")),
    # attention projections
    (r"attn/wq$",          lambda s: ("data", "model")),
    (r"attn/wk$",          lambda s: ("data", "model")),
    (r"attn/wv$",          lambda s: ("data", "model")),
    (r"attn/wo$",          lambda s: ("model", "data")),
    # dense mlp
    (r"mlp/w(i|g)$",       lambda s: ("data", "model")),
    (r"mlp/wo$",           lambda s: ("model", "data")),
    # MoE: experts over model (EP), d_model over fsdp
    (r"moe/gate$",         lambda s: ("data", None)),
    (r"moe/w(i|g)$",       lambda s: ("model", "data", None)),
    (r"moe/wo$",           lambda s: ("model", None, "data")),
    # output head
    (r"head/w$",           lambda s: ("data", "model")),
    (r"head/b$",           lambda s: ("model",)),
    # DiT conditioning / modulation
    (r"adaln/w$",          lambda s: ("data", "model")),
    (r"adaln/b$",          lambda s: ("model",)),
    (r"t_embed/w\d$",      lambda s: ("data", "model") if s[-1] > s[0] else ("model", "data")),
    # patchify / conv stems: shard output channels over model
    (r"patch/w$",          lambda s: (None, None, "data", "model")),
    (r"patch/b$",          lambda s: ("model",)),
    (r"conv/w$",           lambda s: (None, None, "data", "model")),
    (r"dwconv/w$",         lambda s: (None, None, None, "model")),
    # norms / scalars / biases: replicated
    (r"(scale|bias|b|cls|dist)$", lambda s: tuple(None for _ in s)),
]


def spec_for_param(path: str, shape: tuple, mesh: Mesh,
                   stacked: bool = False) -> P:
    """PartitionSpec for one parameter leaf.

    ``stacked`` indicates a leading layer-stacking axis (scan over layers).
    Falls back to replicated when no rule matches or a dim is indivisible.
    """
    dp, mp = mesh_axes(mesh)
    rank = len(shape) - (1 if stacked else 0)
    base_shape = shape[1:] if stacked else shape
    spec: Optional[tuple] = None
    for pat, builder in _RULES:
        if re.search(pat, path):
            cand = builder(base_shape)
            if len(cand) == rank:
                spec = cand
                break
    if spec is None:
        spec = tuple(None for _ in range(rank))
    # map logical names to mesh axes, drop indivisible axes
    out = []
    for dim, ax in zip(base_shape, spec):
        if ax == "data":
            ax = dp
        elif ax == "model":
            ax = mp
        if ax is not None and not _divisible(dim, mesh, ax):
            ax = None
        out.append(ax)
    if stacked:
        out = [None] + out
    return P(*out)


def param_shardings(params_shape: Any, mesh: Mesh, scan_layers: bool = True):
    """Pytree of NamedSharding matching a pytree of ShapeDtypeStruct."""

    def visit(path, leaf):
        pstr = "/".join(_key_str(k) for k in path)
        stacked = scan_layers and "/layers/" in ("/" + pstr + "/")
        return NamedSharding(mesh, spec_for_param(pstr, leaf.shape, mesh,
                                                  stacked=stacked))

    return jax.tree_util.tree_map_with_path(visit, params_shape)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


# ---------------------------------------------------------------------------
# Activation rules
# ---------------------------------------------------------------------------

def act_spec(mesh: Mesh, kind: str) -> P:
    """Common activation shardings."""
    dp, mp = mesh_axes(mesh)
    if kind == "tokens":          # (B, S)
        return P(dp, None)
    if kind == "hidden":          # (B, S, D)
        return P(dp, None, None)
    if kind == "hidden_sp":       # (B, S, D) sequence-parallel region
        return P(dp, mp, None)
    if kind == "ffn":             # (B, S, F) TP-sharded hidden/head width
        return P(dp, None, mp)
    if kind == "heads":           # (B, S, H, dh)
        return P(dp, None, mp, None)
    if kind == "scores":          # (B, H, Sq, Sk)
        return P(dp, mp, None, None)
    if kind == "kv_cache":        # (B, S, KV, dh): SP over sequence
        return P(dp, mp, None, None)
    if kind == "kv_cache_heads":  # (B, S, KV, dh): shard kv heads
        return P(dp, None, mp, None)
    if kind == "logits":          # (B, S, V)
        return P(dp, None, mp)
    if kind == "images":          # (B, H, W, C)
        return P(dp, None, None, None)
    if kind == "replicated":
        return P()
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Sharded ingest rules (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# The multi-stream ingest megastep stacks per-stream tensors along a
# leading STREAM axis and shards only that axis over the 1-D ("data",)
# ingest mesh (`launch.mesh.make_ingest_mesh`). Every device owns a
# contiguous device-major block of stream slots — its streams' ClusterState
# rows live on it for the whole run, so the hot path moves no cluster
# state between devices; only the small per-stream (j, matched) and
# fold rows cross to the host at the designed fold boundary.


def stream_spec(mesh: Mesh, extra_rank: int) -> P:
    """P(data, None * extra_rank) for a stream-major stacked tensor:
    (S, ...) with S = streams padded to a multiple of the mesh size."""
    dp, _ = mesh_axes(mesh)
    return P(dp, *[None] * extra_rank)


def ingest_batch_spec(mesh: Mesh) -> P:
    """Stacked bucket-padded crop batch (S, B, R, R, 3)."""
    return stream_spec(mesh, 4)


def cluster_state_specs(mesh: Mesh) -> tuple:
    """Per-stream ClusterState placement, stream-major stacked:
    centroids (S, M, D), counts (S, M), n (S,)."""
    return (stream_spec(mesh, 2), stream_spec(mesh, 1), stream_spec(mesh, 0))


def ingest_shardings(mesh: Mesh) -> dict:
    """The NamedShardings the sharded ingest pipeline places data with —
    built ONCE at pipeline construction (never per step; the per-step
    rebuild was the old MultiStreamRunner hot-path bug)."""
    cen, cnt, n = cluster_state_specs(mesh)
    return {
        "crops": NamedSharding(mesh, ingest_batch_spec(mesh)),
        "n_real": NamedSharding(mesh, stream_spec(mesh, 0)),
        "rows": NamedSharding(mesh, stream_spec(mesh, 1)),      # (S, B)
        "centroids": NamedSharding(mesh, cen),
        "counts": NamedSharding(mesh, cnt),
        "n": NamedSharding(mesh, n),
        "replicated": NamedSharding(mesh, P()),
    }


def constrain(x, mesh: Optional[Mesh], kind: str):
    """with_sharding_constraint if a mesh is given, else no-op (CPU tests).
    A spec that does not fit ``x`` raises: returning ``x`` unconstrained
    would hide a wrong layout on the device."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, act_spec(mesh, kind)))
