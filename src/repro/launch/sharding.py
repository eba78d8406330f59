"""Sharding rules: logical param axes -> mesh axes (MaxText-style).

Rules are plain data so §Perf hillclimbing edits tables, not model code.
Every rule is divisibility-checked against the actual dim size; a dim that
does not divide falls back to replication (compile-success guarantee — the
dry-run must never fail on an awkward head count).

Train layout (DP/FSDP x TP, 2-D sharded params — required to fit 104B +
Adam in 16 GB/chip):   embed-ish dims -> 'data' (FSDP), wide dims -> 'model'.
Decode layout: params TP over 'model', replicated over 'data' (batch over
'data'); FSDP would force per-step all-gathers on the latency path.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import leading_axis_spec
from repro.models.spec import P as SpecP, is_spec

# logical axis -> mesh axis (axis tuples allowed), per step kind
TRAIN_RULES: Dict[str, Optional[str]] = {
    "vocab": "model",
    "embed": "data",      # FSDP shard over data
    "mlp": "model",
    "heads": "model",
    "kv": "model",
    "expert": "model",    # EP
    "layers": None,
}

DECODE_RULES: Dict[str, Optional[str]] = {
    "vocab": "model",
    "embed": None,
    "mlp": "model",
    "heads": "model",
    "kv": "model",
    "expert": "model",
    "layers": None,
}


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def spec_to_pspec(spec: SpecP, mesh: Mesh, rules: Dict[str, Optional[str]]) -> P:
    """Logical axes -> PartitionSpec with divisibility fallback."""
    used = set()
    out = []
    for dim, ax in zip(spec.shape, spec.axes):
        mesh_axis = rules.get(ax) if ax is not None else None
        if mesh_axis is None or mesh_axis in used:
            out.append(None)
            continue
        if dim % _axis_size(mesh, mesh_axis) != 0:
            out.append(None)  # replicate: non-divisible (e.g. 40 heads / 16)
            continue
        used.add(mesh_axis)
        out.append(mesh_axis)
    return P(*out)


def param_shardings(specs, mesh: Mesh, rules=None):
    """NamedSharding tree for a spec tree."""
    rules = rules or TRAIN_RULES
    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_to_pspec(s, mesh, rules)),
        specs, is_leaf=is_spec)


def param_pspecs(specs, mesh: Mesh, rules=None):
    rules = rules or TRAIN_RULES
    return jax.tree.map(lambda s: spec_to_pspec(s, mesh, rules), specs,
                        is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Batch / cache shardings
# ---------------------------------------------------------------------------


def _dp(mesh: Mesh):
    names = mesh.axis_names
    axes = tuple(a for a in ("pod", "data") if a in names)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def batch_pspec(mesh: Mesh, batch_specs: dict) -> dict:
    """Token batches: leading (batch) dim over DP axes when divisible."""
    dp = _dp(mesh)
    dp_size = _axis_size(mesh, dp)

    def one(s):
        b = s.shape[0]
        lead = dp if (dp is not None and b % dp_size == 0) else None
        return leading_axis_spec(lead, len(s.shape))

    return jax.tree.map(one, batch_specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def cache_pspec(mesh: Mesh, cache_specs: dict, cfg) -> dict:
    """Decode caches.

    KV caches (L, B, S, KVH, HD): batch over DP; head_dim over 'model'
    (always 128-divisible) — scores contract HD with a small psum, keeping
    the big cache tensors fully sharded even when KVH < mesh model size.
    SSM states (L, B, H, N, P): batch over DP, heads over 'model'.
    """
    dp = _dp(mesh)
    dp_size = _axis_size(mesh, dp)
    tp = "model" if "model" in mesh.axis_names else None
    tp_size = _axis_size(mesh, tp)

    def one(path, s):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        b = s.shape[1]
        bax = dp if (dp is not None and b % dp_size == 0) else None
        if name in ("k_scale", "v_scale"):
            kv_ok = tp and s.shape[-1] % tp_size == 0
            seq_ok = tp and s.shape[2] % tp_size == 0
            if kv_ok:
                return P(None, bax, None, tp)
            if seq_ok:
                return P(None, bax, tp, None)
            return P(None, bax, None, None)
        if name in ("k", "v"):
            kv_ok = tp and s.shape[-2] % tp_size == 0
            seq_ok = tp and s.shape[2] % tp_size == 0
            if kv_ok:
                return P(None, bax, None, tp, None)  # head-sharded: no comms
            if seq_ok:
                # kv heads don't divide TP: shard the SEQUENCE dim. The
                # attention contraction over S turns into a small psum of
                # (B,H)-sized partials; head-dim sharding instead forces
                # involuntary replicate-repartition of the whole cache per
                # layer (measured 59 GiB temp on qwen decode_32k, §Perf).
                return P(None, bax, tp, None, None)
            return P(None, bax, None, None, None)
        if name == "ssm":
            h_ok = tp and s.shape[2] % tp_size == 0
            return P(None, bax, tp if h_ok else None, None, None)
        if name == "conv":
            c_ok = tp and s.shape[-1] % tp_size == 0
            return P(None, bax, None, tp if c_ok else None)
        return P(*(None,) * len(s.shape))

    return jax.tree_util.tree_map_with_path(one, cache_specs)


def with_dp_constraint(x, mesh: Mesh):
    """Activation constraint: batch dim over DP axes."""
    dp = _dp(mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, leading_axis_spec(dp, x.ndim)))
