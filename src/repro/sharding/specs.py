"""Partitioning rules: param/cache/batch pytrees -> PartitionSpec pytrees.

Strategy (baseline; §Perf iterates on top of this):
 * TP over "model": vocab (embed/lm_head), attention flat feature dims, MLP
   hidden, MoE experts (or expert-ff when n_experts isn't divisible), mamba
   projections.
 * FSDP over ("pod","data") for >= FSDP_THRESHOLD-param archs: weights are
   additionally sharded on the first remaining divisible dim; XLA
   all-gathers at use and reduce-scatters gradients.
 * Optimizer state is ALWAYS FSDP-sharded (ZeRO) regardless of param FSDP.
 * Small archs (< TP_THRESHOLD) replicate everything (pure DP).

All rules are divisibility-checked against the actual mesh axis sizes; a dim
that doesn't divide falls back to the next candidate (ultimately replicated),
so every (arch x mesh) combination lowers.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig

TP_THRESHOLD = 1_000_000_000      # < 1B params: replicate (pure DP)
FSDP_THRESHOLD = 8_000_000_000    # >= 8B params: FSDP the weights too

MODEL_AXIS = "model"


def mesh_axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return int(mesh.shape[name])


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Data-parallel axes: ("pod","data") when pod exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    return dim % mesh_axis_size(mesh, axes) == 0


def _first_fit(shape, used_dims, mesh, axes) -> Optional[int]:
    """First dim (skipping used) divisible by the axis product; prefers the
    largest dim for better balance."""
    order = sorted((i for i in range(len(shape)) if i not in used_dims),
                   key=lambda i: -shape[i])
    for i in order:
        if shape[i] > 1 and _fits(shape[i], mesh, axes):
            return i
    return None


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _param_spec(cfg: ArchConfig, mesh: Mesh, name: str, shape,
                fsdp: bool) -> P:
    """Rule table keyed on the leaf name suffix."""
    tp_on = cfg.n_params() >= TP_THRESHOLD and MODEL_AXIS in mesh.shape
    spec = [None] * len(shape)
    used: set = set()

    leaf = name.split("/")[-1]
    stacked = "blocks" in name  # leading group-stack dim
    base = 1 if stacked else 0

    def put(dim, axes):
        spec[dim] = axes
        used.add(dim)

    if tp_on:
        if leaf in ("embedding", "lm_head"):
            if _fits(shape[0], mesh, MODEL_AXIS):
                put(0, MODEL_AXIS)
        elif leaf in ("wq", "wk", "wv", "w_gate", "w_up", "w_in",
                      "wz", "wx", "wdt"):
            d = len(shape) - 1
            if _fits(shape[d], mesh, MODEL_AXIS):
                put(d, MODEL_AXIS)
        elif leaf in ("wo", "w_down", "w_out", "out_proj"):
            d = len(shape) - 2
            if d >= 0 and _fits(shape[d], mesh, MODEL_AXIS):
                put(d, MODEL_AXIS)
        elif leaf in ("bq", "bk", "bv"):
            d = len(shape) - 1
            if _fits(shape[d], mesh, MODEL_AXIS):
                put(d, MODEL_AXIS)
        elif leaf in ("conv_x_w", "conv_x_b"):
            # the x-stream conv shards with the heads; B/C convs replicate
            d = len(shape) - 1
            if _fits(shape[d], mesh, MODEL_AXIS):
                put(d, MODEL_AXIS)
        elif leaf == "router":
            d = len(shape) - 1
            if _fits(shape[d], mesh, MODEL_AXIS):
                put(d, MODEL_AXIS)
        # norms / A_log / D / dt_bias / norm_scale: replicated

    # MoE expert stacks: prefer sharding the expert dim over "model"
    if tp_on and leaf in ("w_gate", "w_up", "w_down", "w_in", "w_out") \
            and len(shape) == 4:
        # (G, E, d, f) or (G, E, f, d)
        spec = [None] * len(shape)
        used = set()
        if _fits(shape[1], mesh, MODEL_AXIS):
            put(1, MODEL_AXIS)
        else:  # expert-internal TP (e.g. grok E=8): shard the ff dim
            d = len(shape) - 1 if leaf in ("w_gate", "w_up", "w_in") \
                else len(shape) - 2
            if _fits(shape[d], mesh, MODEL_AXIS):
                put(d, MODEL_AXIS)

    if fsdp and int(np.prod(shape)) >= (1 << 20):
        for axes in (dp_axes(mesh), ("data",)):
            if not all(a in mesh.shape for a in axes):
                continue
            dim = _first_fit(shape, used, mesh, axes)
            if dim is not None:
                put(dim, axes if len(axes) > 1 else axes[0])
                break

    return P(*spec)


def param_specs(cfg: ArchConfig, mesh: Mesh, params_tree,
                fsdp: Optional[bool] = None):
    """PartitionSpec pytree matching ``params_tree`` (arrays or
    ShapeDtypeStructs)."""
    if fsdp is None:
        fsdp = cfg.n_params() >= FSDP_THRESHOLD

    def rule(path, leaf):
        return _param_spec(cfg, mesh, _leaf_name(path), leaf.shape, fsdp)

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def opt_state_specs(cfg: ArchConfig, mesh: Mesh, params_tree):
    """ZeRO: optimizer moments always FSDP-sharded."""
    return param_specs(cfg, mesh, params_tree, fsdp=True)


def batch_specs(cfg: ArchConfig, mesh: Mesh, batch_tree):
    """Shard the batch dim as widely as divisibility allows.

    TP archs keep "model" for tensor parallelism; DP-only archs (< 1B) fold
    "model" into the batch axes so no mesh dimension idles.
    """
    dp = dp_axes(mesh)
    tp_on = cfg.n_params() >= TP_THRESHOLD and MODEL_AXIS in mesh.shape
    candidates = []
    if not tp_on and MODEL_AXIS in mesh.shape:
        candidates.append(dp + (MODEL_AXIS,))
        candidates.append(("data", MODEL_AXIS))
    candidates.extend([dp, ("data",)])

    def rule(path, leaf):
        b = leaf.shape[0] if leaf.ndim >= 1 else 0
        for axes in candidates:
            if not all(a in mesh.shape for a in axes):
                continue
            if b and b % mesh_axis_size(mesh, axes) == 0:
                ax = axes if len(axes) > 1 else axes[0]
                return P(ax, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def cache_specs(cfg: ArchConfig, mesh: Mesh, cache_tree):
    """KV cache: batch over dp if divisible; otherwise shard the sequence
    (attention) / heads (mamba) over everything available.

    Layouts: k/v (G, B, S, KV, hd); h (G, B, H, N, P); conv (G, B, K-1, C).
    """
    dp = dp_axes(mesh)
    dp_size = mesh_axis_size(mesh, dp)
    tp_on = MODEL_AXIS in mesh.shape

    def rule(path, leaf):
        name = _leaf_name(path).split("/")[-1]
        spec = [None] * leaf.ndim
        B = leaf.shape[1]
        batch_sharded = B % dp_size == 0
        if batch_sharded:
            spec[1] = dp if len(dp) > 1 else dp[0]
        if name in ("k", "v"):
            S = leaf.shape[2]
            if batch_sharded:
                if tp_on and S % mesh.shape[MODEL_AXIS] == 0:
                    spec[2] = MODEL_AXIS
            else:
                axes = (dp + (MODEL_AXIS,)) if tp_on else dp
                if S % mesh_axis_size(mesh, axes) == 0:
                    spec[2] = axes
        elif name == "h":
            H = leaf.shape[2]
            if tp_on and H % mesh.shape[MODEL_AXIS] == 0:
                spec[2] = MODEL_AXIS
        elif name == "conv":
            C = leaf.shape[3]
            if tp_on and C % mesh.shape[MODEL_AXIS] == 0:
                spec[3] = MODEL_AXIS
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, cache_tree)


def shardings(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# -- ISLA cell-axis sharding (route="mesh") ---------------------------------

ISLA_CELL_AXIS = "cells"


def isla_cell_specs(mesh: Mesh) -> Dict[str, P]:
    """PartitionSpecs for the ISLA mesh tier (``core.moment_store.
    MeshDeviceStack`` / ``core.distributed.mesh_tick_fn``,
    ``mesh_tick_dense_fn`` and ``mesh_solve_fn``), keyed by operand
    family:

      cells      (N,)   per-cell vectors (ledger, sketch0, inv_scale,
                        quota rows) — sharded on the cell axis
      cell_rows  (N, k) per-cell matrices (moments, totals, per-cell
                        cuts, an optional HLL register plane, dense
                        block panes) — sharded on dim 0
      replicated (...)  sample streams / tags / small anchor tables —
                        every shard holds a copy
      stat_rows  (G, 9) psum'd group-stat rows — replicated output
      active_cells (M,) zone-pruned compacted-launch scatter indices
                        (each shard's LOCAL cell / ledger targets,
                        shard-major, pads out-of-bounds) — sharded on
                        the cell axis like the compact panes they route

    The axis name comes from the mesh itself so a caller-built mesh with
    a different first-axis name still shards correctly.
    """
    ax = mesh.axis_names[0]
    return {
        "cells": P(ax),
        "cell_rows": P(ax, None),
        "replicated": P(),
        "stat_rows": P(None, None),
        "active_cells": P(ax),
    }


def activation_constraint(cfg: ArchConfig, mesh: Mesh):
    """Between-block residual-stream constraint used in the train path:
    shard sequence over "model" (Megatron-SP style) so the remat-saved scan
    carries are 1/tp of the naive size."""
    if not cfg.seq_shard_activations or MODEL_AXIS not in mesh.shape:
        return None
    dp = dp_axes(mesh)
    ax = dp if len(dp) > 1 else dp[0]

    def constrain(x):
        if x.ndim != 3 or x.shape[1] % mesh.shape[MODEL_AXIS] != 0:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(ax, MODEL_AXIS, None)))

    return constrain
