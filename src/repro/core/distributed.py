"""Distributed dynamic sparse matrices (paper §V-E, DESIGN.md §5).

The paper's MPI design, mapped to JAX SPMD:

  * the global matrix is row-partitioned into P contiguous slabs, one per
    shard of a (possibly multi-axis) mesh partition;
  * each shard's rows split into a **local** square block (columns it owns —
    the regular part) and a **remote** rectangular block (columns owned by
    neighbours — the irregular part), each an independently-formatted
    dynamic matrix (the paper's key distributed observation);
  * the local block optionally splits further into **interior** rows (no
    live remote entry — their results never touch the halo) and
    **boundary** rows (the classic MPI overlap decomposition): the
    interior SpMV is the compute the scheduler can run while the halo
    collective is in flight, because *nothing* in it waits on the
    exchange;
  * SpMV = interior SpMV + boundary SpMV + remote SpMV over halo values
    obtained by ``ExchangeHalo`` — here a ``ppermute`` neighbour exchange
    (slab partitions: stencil matrices) or an ``all_gather`` (general
    fallback), issued *before* the interior SpMV so the collective
    overlaps compute;
  * per-shard format selection ("Multi-Format") uses ``SwitchDynamicMatrix``:
    one SPMD program, ``lax.switch`` on a per-shard format id.

Architecture (the PR-2 plan/execute split, applied end-to-end):

  * ``plan_partition`` (symbolic) scans the global triplets once — counts,
    halo reach — and emits a :class:`DistPlan` of static host metadata
    (slab size, halo width/mode, per-shard capacities, and once computed,
    the per-format :class:`SwitchPlan`\\ s).
  * ``partition_execute`` (numeric) is jit-able with the plan static: each
    entry's rank within its (shard, local/remote) group scatters it into
    its shard-local slot of the stacked, uniform-capacity local/remote COO
    containers. Zero device->host transfers.
  * conversion/selection are batched: ``plan_switch_batch`` produces one
    shared plan per candidate format, ``convert_execute_batch`` vmaps the
    numeric phase over the shard axis, and ``FormatPolicy.select_batch``
    featurises every shard in one device pass — build cost no longer has a
    Python-loop factor of P.

Containers are *stacked*: every array gains a leading P axis which is
sharded over the mesh partition axes; inside ``shard_map`` each shard sees
its own slab (leading dim 1) and unstacks it. A uniform-mode DIA or COO
part is *row-sharded* instead: one container over every shard's rows,
sharded along its row axis, so each shard sees exactly its own container
with no axis of length 1 to strip. A DIA part's table is lane-tiled,
``(ndiag, P * ceil(mp/128), 128)`` sharded along the row-tile axis, its
offsets ``(P * ndiag,)``; a COO part's arrays are ``(P * cap,)``. A shard
then hands the Pallas DIA kernel its table as stored, with no copy or
relayout of it in the solve.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.convert import (SwitchPlan, _planned_pull,
                                convert_execute_batch, plan_switch_batch)
from repro.core import ops as _ops
from repro.core.dynamic import SwitchDynamicMatrix
from repro.core.formats import COO, DIA, Format, tile_dia_table
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

AxisNames = Union[str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# Stacking / unstacking shard containers
# ---------------------------------------------------------------------------


def leading_axis_spec(axis, ndim: int) -> PartitionSpec:
    """``P(axis, None, ...)`` — shard the leading axis, replicate the rest.

    The one spec every stacked shard container and batch tensor uses; shared
    with ``repro.launch.sharding`` so the distributed layer and the model
    launcher agree on the convention.
    """
    return PartitionSpec(axis, *(None,) * (ndim - 1))


def _row_sharded(part) -> bool:
    """Whether ``part`` is a row-sharded DIA or COO container, told from a
    stacked one by its arrays' rank: row-sharded offsets and COO arrays
    are 1-D, stacked ones carry the leading shard axis."""
    if isinstance(part, DIA):
        return part.offsets.ndim == 1
    return isinstance(part, COO) and part.row.ndim == 1


def _row_shard(part):
    """A stacked uniform-mode part as it is stored row-sharded: a DIA
    table lane-tiled and laid shard after shard along its row-tile axis,
    COO arrays end to end. Other containers stay stacked. Runs once, at
    build; for P = 1 each step is a reshape."""
    if isinstance(part, DIA):
        tiles = tile_dia_table(part.data)              # (P, nd, Mt, 128)
        nshards, nd, mt, lanes = tiles.shape
        data = jnp.moveaxis(tiles, 0, 1).reshape(nd, nshards * mt, lanes)
        return DIA(part.offsets.reshape(-1), data, part.shape, part.nnz)
    if isinstance(part, COO):
        return jax.tree.map(lambda a: a.reshape(-1), part)
    return part


def _unstack(part):
    """Inside shard_map: a shard's own container. A stacked one has its
    leading (length-1) shard axis stripped; a row-sharded one is already
    the shard's container."""
    if _row_sharded(part):
        return part
    return jax.tree.map(lambda a: a[0], part)


def _part_spec(t, axis: AxisNames):
    """PartitionSpec tree of shard containers on ``axis``: the leading
    shard axis of a stacked container, the row axis of a row-sharded one
    (axis 1 of a lane-tiled DIA table)."""
    def spec(part):
        if _row_sharded(part):
            return jax.tree.map(
                lambda a: (PartitionSpec(axis) if a.ndim == 1
                           else PartitionSpec(None, axis, None)), part)
        return jax.tree.map(lambda a: leading_axis_spec(axis, a.ndim), part)

    return jax.tree.map(spec, t, is_leaf=lambda n: isinstance(n, (COO, DIA)))


# ---------------------------------------------------------------------------
# The distributed container
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class DistSparseMatrix:
    """Row-partitioned sparse matrix with local/remote split per shard.

    ``local``/``remote`` are row-sharded DIA or COO containers, or
    stacked ones of the other formats (stacked SwitchDynamicMatrix for
    Multi-Format); see the module docstring. ``halo_mode`` is ``"neighbor"``
    (remote columns renumbered into a [prev_tail | next_head] halo of width
    ``hw`` per side) or ``"gather"`` (remote columns are global ids).
    ``remote_empty`` marks a statically block-diagonal partition: the
    remote part carries no entries, so SpMV skips both the exchange and
    the remote term entirely.

    With the overlap split (``build_dist_matrix(split=...)``), ``local``
    holds only the **interior** rows (no live remote entry) and
    ``boundary`` holds the rest of the local block — both (mp, mp), their
    entry sets disjoint and together exactly the unsplit local block.
    ``boundary is None`` means the matrix is unsplit and ``local`` is the
    whole local block.
    """

    def __init__(self, local, remote, *, nshards: int, mp: int, shape,
                 axis: AxisNames, halo_mode: str, hw: int,
                 remote_empty: bool = False, boundary=None):
        self.local = local
        self.remote = remote
        self.boundary = boundary
        self.nshards = nshards
        self.mp = mp
        self.shape = tuple(shape)
        self.axis = axis
        self.halo_mode = halo_mode
        self.hw = hw
        self.remote_empty = remote_empty

    @property
    def split(self) -> bool:
        """True when local is interior-only and ``boundary`` carries the
        halo-coupled rows (the overlap decomposition)."""
        return self.boundary is not None

    def tree_flatten(self):
        meta = (self.nshards, self.mp, self.shape, self.axis, self.halo_mode,
                self.hw, self.remote_empty)
        return (self.local, self.remote, self.boundary), meta

    @classmethod
    def tree_unflatten(cls, meta, children):
        nshards, mp, shape, axis, halo_mode, hw, remote_empty = meta
        return cls(children[0], children[1], boundary=children[2],
                   nshards=nshards, mp=mp,
                   shape=shape, axis=axis, halo_mode=halo_mode, hw=hw,
                   remote_empty=remote_empty)

    def _replace_parts(self, local, remote, boundary=None) -> "DistSparseMatrix":
        return DistSparseMatrix(
            local, remote, boundary=self.boundary if boundary is None else boundary,
            nshards=self.nshards, mp=self.mp, shape=self.shape,
            axis=self.axis, halo_mode=self.halo_mode, hw=self.hw,
            remote_empty=self.remote_empty)

    def __repr__(self):
        lf = type(self.local).__name__
        rf = type(self.remote).__name__
        halo = "empty" if self.remote_empty else f"{self.halo_mode}:{self.hw}"
        parts = f"local={lf}"
        if self.split:
            parts += f", boundary={type(self.boundary).__name__}"
        return (f"DistSparseMatrix(shape={self.shape}, P={self.nshards}, "
                f"{parts}, remote={rf}, halo={halo})")


# ---------------------------------------------------------------------------
# Halo exchange (the paper's ExchangeHalo)
# ---------------------------------------------------------------------------


def _exchange_neighbor(x_blk, hw: int, axis: AxisNames, nshards: int):
    """[prev shard's last hw | next shard's first hw] via ppermute."""
    fwd = [(i, i + 1) for i in range(nshards - 1)]
    bwd = [(i + 1, i) for i in range(nshards - 1)]
    prev_tail = jax.lax.ppermute(x_blk[-hw:], axis, fwd)   # from p-1
    next_head = jax.lax.ppermute(x_blk[:hw], axis, bwd)    # from p+1
    return jnp.concatenate([prev_tail, next_head])


def _exchange_halo(x_blk, hw: int, axis: AxisNames, nshards: int,
                   halo_mode: str):
    """The halo a shard's remote part reads, under the ``dist.halo`` scope:
    the neighbours' boundary rows (``neighbor``) or the whole vector
    (``gather``)."""
    with jax.named_scope("dist.halo"):
        if halo_mode == "neighbor":
            return _exchange_neighbor(x_blk, hw, axis, nshards)
        if halo_mode == "gather":
            return jax.lax.all_gather(x_blk, axis, tiled=True)
    raise ValueError(halo_mode)


def _shard_spmv(local, remote, x_blk, hw: int, axis: AxisNames, nshards: int,
                halo_mode: str, backend: str, remote_empty: bool, cfg=None,
                boundary=None):
    """Per-shard SpMV body: y = A_local x_local + A_remote x_halo.

    The halo collective is issued *before* the local SpMV: it has no data
    dependency on it, so XLA's latency-hiding scheduler overlaps the
    exchange with the local compute (the paper's communication/computation
    overlap). A statically-empty remote part skips both entirely.

    With the interior/boundary split (``boundary is not None``), ``local``
    is the interior part: its entire SpMV — compute *and* result rows — is
    independent of the collective, so the scheduler has a dependency-free
    region exactly as wide as the interior work to hide the exchange in.
    The boundary and remote terms, whose result rows genuinely wait on the
    halo, are summed last. Each part runs in a scope of its own:
    ``dist.halo`` (the exchange), ``dist.interior`` (``dist.local`` when
    unsplit), ``dist.boundary`` and ``dist.remote``. A statically-empty
    remote part leaves the local work in the caller's scope.
    """
    if remote_empty:
        y = _ops.spmv(local, x_blk, backend=backend, cfg=cfg)
        if boundary is not None:
            y = y + _ops.spmv(boundary, x_blk, backend=backend, cfg=cfg)
        return y
    halo = _exchange_halo(x_blk, hw, axis, nshards, halo_mode)
    y = _local_spmv(local, boundary, x_blk, backend, cfg)
    return y + _scoped_spmv("dist.remote", remote, halo, backend, cfg)


def _local_spmv(local, boundary, x_blk, backend: str, cfg):
    """A shard's local block times its own slab of x: the interior part
    under ``dist.interior`` and the boundary part under ``dist.boundary``,
    or an unsplit local block under ``dist.local``."""
    if boundary is None:
        return _scoped_spmv("dist.local", local, x_blk, backend, cfg)
    y = _scoped_spmv("dist.interior", local, x_blk, backend, cfg)
    return y + _scoped_spmv("dist.boundary", boundary, x_blk, backend, cfg)


def _scoped_spmv(scope: str, A, x, backend: str, cfg):
    """One part's SpMV under its ``dist.*`` scope."""
    with jax.named_scope(scope):
        return _ops.spmv(A, x, backend=backend, cfg=cfg)


def dist_spmv(A: DistSparseMatrix, x, mesh: Mesh, backend: str = "auto",
              cfg=None):
    """Global SpMV. ``x`` is the global vector sharded P(axis).

    ``backend="auto"`` flows *into* the shard bodies unresolved: every
    shard-local per-format SpMV routes itself through the measured
    kernel-config cache (``repro.core.ops.kernel_route``), so a
    multiformat distributed matrix inherits each format's tuned Pallas
    tiles where they beat the reference path — per (format, shard-shape
    bucket), not one coarse process-wide pick. The routing is a
    trace-time host lookup; inside ``shard_map`` all shards share one
    program, so the decision is identical across shards of the same
    format branch. An explicit ``cfg`` (kernel tile-config dict) applies
    uniformly to every shard's SpMVs instead.

    A split matrix (``A.boundary is not None``) runs the overlap schedule:
    halo collective issued first, interior SpMV (``A.local``) while it is
    in flight, boundary + remote last.
    """
    axis = A.axis
    if not A.remote_empty:
        # Exchange accounting. ``dist_spmv`` may run under an outer jit, in
        # which case this host-side bookkeeping executes once at trace time
        # (per compilation), not per device call — documented semantics of
        # the ``halo.bytes`` counter.
        itemsize = jnp.dtype(getattr(x, "dtype", jnp.float32)).itemsize
        halo_elems = (2 * A.hw if A.halo_mode == "neighbor"
                      else A.shape[1])
        _metrics.inc("halo.bytes", A.nshards * halo_elems * itemsize)

    if A.split:
        def body(local_s, boundary_s, remote_s, x_blk):
            return _shard_spmv(_unstack(local_s), _unstack(remote_s), x_blk,
                               A.hw, axis, A.nshards, A.halo_mode, backend,
                               A.remote_empty, cfg=cfg,
                               boundary=_unstack(boundary_s))
        in_specs = (_part_spec(A.local, axis), _part_spec(A.boundary, axis),
                    _part_spec(A.remote, axis), leading_axis_spec(axis, 1))
        operands = (A.local, A.boundary, A.remote, x)
    else:
        def body(local_s, remote_s, x_blk):
            return _shard_spmv(_unstack(local_s), _unstack(remote_s), x_blk,
                               A.hw, axis, A.nshards, A.halo_mode, backend,
                               A.remote_empty, cfg=cfg)
        in_specs = (_part_spec(A.local, axis), _part_spec(A.remote, axis),
                    leading_axis_spec(axis, 1))
        operands = (A.local, A.remote, x)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=leading_axis_spec(axis, 1))
    return fn(*operands)


def dist_spmv_phase(A: DistSparseMatrix, x, mesh: Mesh, phase: str = "full",
                    backend: str = "auto", cfg=None):
    """Phase-decomposed distributed SpMV — the overlap diagnostic.

    ``phase``:
      * ``"full"``      the production path (:func:`dist_spmv`);
      * ``"local"``     local SpMV only (interior + boundary when split) —
                        no halo collective is issued;
      * ``"exchange"``  halo exchange + remote SpMV only — no local SpMV;
      * ``"interior"``  interior rows only (split matrices);
      * ``"boundary"``  boundary rows only (split matrices).

    Timing the phases independently and comparing ``t_local + t_exchange``
    against ``t_full`` measures how much of the exchange XLA's scheduler
    actually hid behind local compute (``hidden = local + exchange -
    full``). The ``interior``/``boundary`` phases further attribute the
    local side of a split matrix: the interior term is the overlap
    window's width.
    """
    if phase == "full":
        return dist_spmv(A, x, mesh, backend=backend, cfg=cfg)
    if phase not in ("local", "exchange", "interior", "boundary"):
        raise ValueError(f"phase {phase!r} not in ('full', 'local', "
                         f"'exchange', 'interior', 'boundary')")
    if phase in ("interior", "boundary") and not A.split:
        raise ValueError(f"phase {phase!r} needs a split matrix "
                         "(build_dist_matrix(split=True))")
    axis = A.axis

    def body(local_s, boundary_s, remote_s, x_blk):
        local, remote = _unstack(local_s), _unstack(remote_s)
        boundary = _unstack(boundary_s) if boundary_s is not None else None
        if phase == "interior":
            return _scoped_spmv("dist.interior", local, x_blk, backend, cfg)
        if phase == "boundary":
            return _scoped_spmv("dist.boundary", boundary, x_blk, backend,
                                cfg)
        if phase == "local":
            return _local_spmv(local, boundary, x_blk, backend, cfg)
        if A.remote_empty:
            return jnp.zeros_like(x_blk)
        halo = _exchange_halo(x_blk, A.hw, axis, A.nshards, A.halo_mode)
        return _scoped_spmv("dist.remote", remote, halo, backend, cfg)

    if A.split:
        def body3(local_s, boundary_s, remote_s, x_blk):
            return body(local_s, boundary_s, remote_s, x_blk)
        in_specs = (_part_spec(A.local, axis), _part_spec(A.boundary, axis),
                    _part_spec(A.remote, axis), leading_axis_spec(axis, 1))
        fn = jax.shard_map(body3, mesh=mesh, in_specs=in_specs,
                              out_specs=leading_axis_spec(axis, 1))
        return fn(A.local, A.boundary, A.remote, x)

    def body2(local_s, remote_s, x_blk):
        return body(local_s, None, remote_s, x_blk)
    fn = jax.shard_map(
        body2, mesh=mesh,
        in_specs=(_part_spec(A.local, axis), _part_spec(A.remote, axis),
                  leading_axis_spec(axis, 1)),
        out_specs=leading_axis_spec(axis, 1))
    return fn(A.local, A.remote, x)


def distribute_vector(x, mesh: Mesh, axis: AxisNames):
    return jax.device_put(jnp.asarray(x),
                          NamedSharding(mesh, leading_axis_spec(axis, 1)))


# ---------------------------------------------------------------------------
# The partition plan (symbolic phase — static host metadata only)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DistPlan:
    """Static metadata of a slab partition — the distributed symbolic phase.

    Everything here is small host data (ints, strings, plan tuples):
    hashable, so the numeric phases (``partition_execute``,
    ``convert_execute_batch``) ride through ``jax.jit`` as static
    arguments. ``local_plans``/``remote_plans`` memoise the per-candidate
    :class:`SwitchPlan`\\ s once a multiformat build has computed them, so
    a rebuild (e.g. after a numeric update with the same pattern) performs
    zero symbolic device->host pulls.
    """

    nshards: int
    mp: int                       # rows per slab
    hw: int                       # halo width per side (0: remote empty)
    halo_mode: str                # "neighbor" | "gather"
    shape: Tuple[int, int]
    local_cap: int                # shared local COO capacity across shards
    remote_cap: int               # shared remote COO capacity across shards
    remote_empty: bool = False
    candidates: Optional[Tuple[Format, ...]] = None
    local_plans: Optional[Tuple[SwitchPlan, ...]] = None
    remote_plans: Optional[Tuple[SwitchPlan, ...]] = None
    # live-pattern fingerprint: the memoised format plans above are valid
    # only for triplets with the same live (val != 0) pattern; the builder
    # drops them and re-plans when the fingerprint no longer matches.
    pattern_sig: Optional[str] = None
    # overlap split: shared capacities of the interior/boundary halves of
    # the local block (live entries only), plus their memoised per-candidate
    # format plans. None until a split build computes them.
    interior_cap: Optional[int] = None
    boundary_cap: Optional[int] = None
    interior_plans: Optional[Tuple[SwitchPlan, ...]] = None
    boundary_plans: Optional[Tuple[SwitchPlan, ...]] = None

    @property
    def remote_width(self) -> int:
        if self.remote_empty:
            return 1  # inert 1-column placeholder part
        return 2 * self.hw if self.halo_mode == "neighbor" else self.shape[1]

    @property
    def local_shape(self) -> Tuple[int, int]:
        return (self.mp, self.mp)

    @property
    def remote_shape(self) -> Tuple[int, int]:
        return (self.mp, self.remote_width)

    # -- persistence (the ``distplan:`` SelectionCache namespace) ----------

    def to_json(self) -> str:
        """Serialise the whole plan — partition caps, split caps, memoised
        per-candidate SwitchPlans, pattern fingerprint — to one JSON
        string, so a restarted job rebuilds with zero symbolic work."""
        import json

        doc = {"nshards": self.nshards, "mp": self.mp, "hw": self.hw,
               "halo_mode": self.halo_mode, "shape": list(self.shape),
               "local_cap": self.local_cap, "remote_cap": self.remote_cap,
               "remote_empty": self.remote_empty,
               "pattern_sig": self.pattern_sig,
               "interior_cap": self.interior_cap,
               "boundary_cap": self.boundary_cap}
        if self.candidates is not None:
            doc["candidates"] = [Format(f).name for f in self.candidates]
        for name in ("local_plans", "remote_plans", "interior_plans",
                     "boundary_plans"):
            plans = getattr(self, name)
            if plans is not None:
                doc[name] = [p.to_json() for p in plans]
        return json.dumps(doc, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "DistPlan":
        import json

        doc = json.loads(s)
        kw = {k: doc[k] for k in ("nshards", "mp", "hw", "halo_mode",
                                  "local_cap", "remote_cap", "remote_empty",
                                  "pattern_sig", "interior_cap",
                                  "boundary_cap")}
        kw["shape"] = tuple(doc["shape"])
        if "candidates" in doc:
            kw["candidates"] = tuple(Format[n] for n in doc["candidates"])
        for name in ("local_plans", "remote_plans", "interior_plans",
                     "boundary_plans"):
            if name in doc:
                kw[name] = tuple(SwitchPlan.from_json(p) for p in doc[name])
        return cls(**kw)


def plan_partition(row, col, val, shape, nshards: int,
                   halo_mode: str = "auto") -> DistPlan:
    """Symbolic phase of the slab partitioner: one vectorised host scan.

    Rows are divided into ``nshards`` equal slabs (M must divide evenly;
    pad upstream with identity rows otherwise). The halo mode is chosen
    automatically: ``neighbor`` when every remote column lies within one
    slab-width of the owning slab (stencil matrices), else ``gather``; a
    block-diagonal matrix (no remote entries at all) gets ``hw=0`` and a
    statically-empty remote part — no exchange is ever issued for it.
    """
    m, n = shape
    if nshards <= 0 or m % nshards or m != n:
        raise ValueError(
            f"square matrix with M % P == 0 required, got {shape} / {nshards}")
    mp = m // nshards
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)

    shard = row // mp
    local_mask = (col // mp) == shard
    remote_mask = ~local_mask
    remote_empty = not bool(remote_mask.any())
    # maximum reach of remote columns beyond slab boundaries
    reach_lo = np.where(remote_mask, shard * mp - col, 0).max(initial=0)
    reach_hi = np.where(remote_mask, col - ((shard + 1) * mp - 1), 0).max(initial=0)
    reach = int(max(reach_lo, reach_hi))
    if halo_mode == "auto":
        halo_mode = "neighbor" if reach <= mp else "gather"
    if halo_mode == "neighbor":
        if reach > mp:
            raise ValueError("neighbor halo violated; use halo_mode='gather'")
        hw = 0 if remote_empty else max(1, reach)
    elif halo_mode == "gather":
        hw = 0 if remote_empty else mp
    else:
        raise ValueError(halo_mode)

    lcounts = np.bincount(shard[local_mask], minlength=nshards)
    rcounts = np.bincount(shard[remote_mask], minlength=nshards)
    return DistPlan(nshards=nshards, mp=mp, hw=hw, halo_mode=halo_mode,
                    shape=(m, n), local_cap=max(1, int(lcounts.max())),
                    remote_cap=max(1, int(rcounts.max())),
                    remote_empty=remote_empty)


def group_ranks(key, nkeys: int):
    """Stable rank of every entry among the entries that share its key
    (jit-able). Keys outside ``[0, nkeys)`` get rank 0.

    One masked running count per key: O(nkeys * len(key)) elementwise
    work, where a stable sort by key would pay a comparison sort of every
    entry — at nnz = 3e7 and a handful of keys, 0.05 s per key against
    7 s for the sort on a CPU host.
    """
    rank = jnp.zeros(key.shape, jnp.int32)
    for k in range(nkeys):
        hit = key == k
        rank = jnp.where(hit, jnp.cumsum(hit, dtype=jnp.int32) - 1, rank)
    return rank


def partition_execute(row, col, val, plan: DistPlan,
                      dtype=jnp.float32) -> Tuple[COO, COO]:
    """Numeric phase of the slab partitioner (jit-able, ``plan`` static).

    Every entry's stable rank within its (shard, local/remote) group
    (:func:`group_ranks`) drops it into its slot of the stacked
    uniform-capacity containers in one scatter. Local columns are
    renumbered shard-relative, remote columns halo-relative (neighbor
    mode) or kept global (gather mode). Zero device->host transfers.
    """
    nshards, mp, hw = plan.nshards, plan.mp, plan.hw
    row = jnp.asarray(row).astype(jnp.int32)
    col = jnp.asarray(col).astype(jnp.int32)
    val = jnp.asarray(val).astype(dtype)

    p = row // mp
    rem = (col // mp) != p
    rank = group_ranks(p * 2 + rem.astype(jnp.int32), 2 * nshards)

    lrow = row - p * mp
    lcol = col - p * mp
    if plan.halo_mode == "neighbor" and not plan.remote_empty:
        below = col < p * mp
        rcol = jnp.where(below, col - (p * mp - hw), hw + (col - (p + 1) * mp))
    else:
        rcol = col

    def scatter(select, cap, cols, vals):
        # in-capacity entries land at p*cap + rank; everything else (the
        # other part's entries, or overflow under a stale plan) goes to a
        # dropped guard slot past the end.
        ok = select & (rank < cap)
        dest = jnp.where(ok, p * cap + jnp.minimum(rank, cap - 1),
                         nshards * cap)
        out = []
        for x in (lrow, cols, vals):
            buf = jnp.zeros((nshards * cap + 1,), x.dtype).at[dest].set(
                jnp.where(ok, x, jnp.zeros((), x.dtype)))
            out.append(buf[:nshards * cap].reshape(nshards, cap))
        return out

    lr, lc, lv = scatter(~rem, plan.local_cap, lcol, val)
    rr, rc, rv = scatter(rem, plan.remote_cap, rcol, val)
    local = COO(lr, lc, lv, plan.local_shape, plan.local_cap)
    remote = COO(rr, rc, rv, plan.remote_shape, plan.remote_cap)
    return local, remote


# One process-wide trace cache: rebuilds with the same plan/shapes are pure
# dispatch (jit wrappers created per call would retrace every build).
partition_execute_jit = jax.jit(partition_execute,
                                static_argnames=("plan", "dtype"))


# ---------------------------------------------------------------------------
# Interior/boundary overlap split of the local block
# ---------------------------------------------------------------------------


def _split_caps(row, col, val, mp: int, nshards: int) -> Tuple[int, int]:
    """Shared (interior, boundary) capacities — one vectorised host scan.

    A row is *boundary* when it has at least one live remote entry (its
    SpMV result waits on the halo); every other local row is *interior*.
    Counting is over live (val != 0) local entries, matching the device
    split, which drops dead entries.
    """
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)
    live = np.asarray(val) != 0
    shard = row // mp
    local_mask = (col // mp) == shard
    brow = np.zeros((mp * nshards,), bool)
    brow[row[live & ~local_mask]] = True
    loc_live = live & local_mask
    is_b = brow[row] & loc_live
    icounts = np.bincount(shard[loc_live & ~is_b], minlength=nshards)
    bcounts = np.bincount(shard[is_b], minlength=nshards)
    return (max(1, int(icounts.max(initial=0))),
            max(1, int(bcounts.max(initial=0))))


def split_local_execute(local: COO, remote: COO, mp: int, icap: int,
                        bcap: int) -> Tuple[COO, COO]:
    """Numeric phase of the overlap split (jit-able, caps static).

    One extra stacked scatter over the already-partitioned local block:
    per shard, rows with a live remote entry are flagged (one scatter-max
    over the remote triplets), then every live local entry lands in the
    interior or boundary container by a rank-within-mask scatter — the
    same guard-slot pattern as :func:`partition_execute`. Dead (val == 0)
    entries are dropped; both outputs keep the (mp, mp) local shape. Zero
    device->host transfers.
    """
    def one(lrow, lcol, lval, rrow, rdata):
        bflag = jnp.zeros((mp,), bool).at[rrow].max(rdata != 0)
        live = lval != 0
        outs = []
        for mask, cap in (((~bflag[lrow]) & live, icap),
                          (bflag[lrow] & live, bcap)):
            rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
            ok = mask & (rank < cap)
            dest = jnp.where(ok, jnp.minimum(rank, cap - 1), cap)
            for x in (lrow, lcol, lval):
                buf = jnp.zeros((cap + 1,), x.dtype).at[dest].set(
                    jnp.where(ok, x, jnp.zeros((), x.dtype)))
                outs.append(buf[:cap])
        return tuple(outs)

    ir, ic, iv, br, bc, bv = jax.vmap(one)(local.row, local.col, local.data,
                                           remote.row, remote.data)
    return (COO(ir, ic, iv, (mp, mp), icap), COO(br, bc, bv, (mp, mp), bcap))


split_local_execute_jit = jax.jit(split_local_execute,
                                  static_argnames=("mp", "icap", "bcap"))


def plan_dist_formats(local: COO, remote: COO, plan: DistPlan,
                      candidates: Sequence[Format],
                      boundary: Optional[COO] = None) -> DistPlan:
    """Attach the per-candidate :class:`SwitchPlan`\\ s to a DistPlan.

    One :func:`plan_switch_batch` pass per candidate per part; a plan that
    already carries matching format plans is returned unchanged (rebuilds
    perform no symbolic pulls at all). With ``boundary`` (the overlap
    split), ``local`` is the interior part and the plan memoises
    ``interior_plans``/``boundary_plans`` instead of ``local_plans`` —
    per-split multiformat selection needs per-split conversion plans.
    """
    candidates = tuple(Format(c) for c in candidates)
    if boundary is None:
        if plan.candidates == candidates and plan.local_plans is not None:
            return plan
        with _trace.span("plan.dist_formats",
                         candidates=",".join(f.name for f in candidates)):
            lplans = tuple(plan_switch_batch(local, f) for f in candidates)
            rplans = tuple(plan_switch_batch(remote, f) for f in candidates)
        return dataclasses.replace(plan, candidates=candidates,
                                   local_plans=lplans, remote_plans=rplans)
    if plan.candidates == candidates and plan.interior_plans is not None:
        return plan
    with _trace.span("plan.dist_formats", split=True,
                     candidates=",".join(f.name for f in candidates)):
        iplans = tuple(plan_switch_batch(local, f) for f in candidates)
        bplans = tuple(plan_switch_batch(boundary, f) for f in candidates)
        rplans = tuple(plan_switch_batch(remote, f) for f in candidates)
    return dataclasses.replace(plan, candidates=candidates,
                               interior_plans=iplans, boundary_plans=bplans,
                               remote_plans=rplans)


def _pattern_sig(row, col, val) -> str:
    """Fingerprint of the *live* sparsity pattern (host, one O(nnz) pass)."""
    import hashlib

    live = np.asarray(val) != 0
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(np.asarray(row, np.int64)[live]).tobytes())
    h.update(np.ascontiguousarray(np.asarray(col, np.int64)[live]).tobytes())
    return h.hexdigest()


def _check_plan_fits(row, col, plan: DistPlan, val=None) -> None:
    """A reused plan must still fit the triplets.

    ``partition_execute``'s guard-slot scatter silently drops entries whose
    rank exceeds the planned capacity, and a halo reach beyond the planned
    width would store out-of-range remote columns — both would corrupt the
    matrix with no error. One vectorised host scan (same cost class as
    ``plan_partition``) turns a stale plan into a loud failure instead.
    With ``val`` and a plan carrying split capacities, the
    interior/boundary scatter of :func:`split_local_execute` is validated
    the same way (its counting is live-entry based, hence the values).
    """
    if val is not None and plan.interior_cap is not None:
        icap, bcap = _split_caps(row, col, val, plan.mp, plan.nshards)
        if icap > plan.interior_cap or bcap > plan.boundary_cap:
            raise ValueError(
                f"stale DistPlan: split capacities (interior "
                f"{plan.interior_cap}, boundary {plan.boundary_cap}) too "
                f"small for these triplets (need {icap}/{bcap}); re-plan "
                f"with plan_partition")
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)
    mp = plan.mp
    shard = row // mp
    local_mask = (col // mp) == shard
    remote_mask = ~local_mask
    lmax = int(np.bincount(shard[local_mask], minlength=plan.nshards).max(initial=0))
    rmax = int(np.bincount(shard[remote_mask], minlength=plan.nshards).max(initial=0))
    if lmax > plan.local_cap or rmax > plan.remote_cap:
        raise ValueError(
            f"stale DistPlan: capacities (local {plan.local_cap}, remote "
            f"{plan.remote_cap}) too small for these triplets (need "
            f"{lmax}/{rmax}); re-plan with plan_partition")
    if rmax and plan.remote_empty:
        raise ValueError("stale DistPlan: marked remote-empty but the "
                         "triplets have remote entries; re-plan")
    if plan.halo_mode == "neighbor" and not plan.remote_empty:
        reach_lo = np.where(remote_mask, shard * mp - col, 0).max(initial=0)
        reach_hi = np.where(remote_mask, col - ((shard + 1) * mp - 1), 0).max(initial=0)
        if int(max(reach_lo, reach_hi)) > plan.hw:
            raise ValueError(
                f"stale DistPlan: halo width {plan.hw} smaller than the "
                f"triplets' reach {int(max(reach_lo, reach_hi))}; re-plan")


# ---------------------------------------------------------------------------
# Legacy host partitioner (reference implementation, kept for tooling)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PartitionedCOO:
    """Host-side per-shard COO triplets (reference symbolic product).

    The batched device path (``plan_partition`` + ``partition_execute``)
    supersedes this for building; it remains the easy-to-inspect oracle.
    """

    local: list  # [(row, col, val)] per shard, columns shard-local
    remote: list  # [(row, col, val)] per shard, columns halo-renumbered
    mp: int
    hw: int
    halo_mode: str
    shape: Tuple[int, int]
    remote_empty: bool = False


def partition_coo(row, col, val, shape, nshards: int,
                  halo_mode: str = "auto") -> PartitionedCOO:
    """Split global COO triplets into per-shard local/remote host triplets.

    Reference (per-shard loop) counterpart of :func:`partition_execute`;
    halo-mode selection and capacities come from :func:`plan_partition`.
    """
    plan = plan_partition(row, col, val, shape, nshards, halo_mode=halo_mode)
    mp, hw = plan.mp, plan.hw
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)
    val = np.asarray(val)
    shard = row // mp
    local_mask = (col // mp) == shard

    locals_, remotes = [], []
    for p in range(nshards):
        in_shard = shard == p
        lm = in_shard & local_mask
        rm = in_shard & ~local_mask
        locals_.append((row[lm] - p * mp, col[lm] - p * mp, val[lm]))
        rr = row[rm] - p * mp
        gc = col[rm]
        if plan.halo_mode == "neighbor" and not plan.remote_empty:
            start, end = p * mp, (p + 1) * mp
            rc = np.where(gc < start, gc - (start - hw), hw + (gc - end))
        else:
            rc = gc
        remotes.append((rr, rc, val[rm]))
    return PartitionedCOO(locals_, remotes, mp, hw, plan.halo_mode, plan.shape,
                          remote_empty=plan.remote_empty)


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------


def build_dist_matrix(row, col, val, shape, mesh: Mesh, axis: AxisNames,
                      local_format: Format = Format.CSR,
                      remote_format: Format = Format.CSR,
                      mode: str = "uniform",
                      candidates: Sequence[Format] = (Format.COO, Format.CSR, Format.DIA, Format.ELL, Format.SELL),
                      tune: str = "calibrated",
                      halo_mode: str = "auto",
                      dtype=jnp.float32,
                      plan: Optional[DistPlan] = None,
                      check_plan: bool = True,
                      parts: Optional[Tuple[COO, COO]] = None,
                      split: Union[str, bool] = "auto",
                      plan_cache=None) -> DistSparseMatrix:
    """Build a distributed dynamic matrix (the paper's three versions).

    mode='uniform'      local/remote formats fixed (Morpheus & Ghost configs)
    mode='multiformat'  per-shard formats chosen by the auto-tuner, dispatched
                        via SwitchDynamicMatrix (paper's Multi-Format).

    The build is the plan/execute pipeline end-to-end: one host scan (or a
    caller-supplied :class:`DistPlan`, e.g. ``repro.core.hpcg.slab_plan``'s
    analytic one) plans the partition; one jitted ``partition_execute``
    scatters the triplets into stacked shard containers on device; one
    shared ``plan_switch_batch`` plan + one vmapped ``convert_execute_batch``
    per candidate format builds the variants; and in multiformat mode
    ``FormatPolicy.select_batch`` picks every shard's format from a single
    batched featurisation pass. No per-shard Python loops anywhere on the
    cached/ml/analytic paths.

    ``tune`` names the per-shard selection strategy: a
    ``repro.tuning.FormatPolicy`` mode ("ml" | "cached" | "analytic" |
    "profile"), a FormatPolicy instance, or the historical alias
    "calibrated" (= profile). At production shard counts use "cached": a
    warm cache selects every shard's format without a single profiling run.

    ``parts`` short-circuits the partition scatter with an already
    partitioned ``(local, remote)`` stacked-COO pair produced from the
    *same* plan (e.g. by ``hpcg.partition_problem``) — callers that need
    the stacked containers anyway (the MG hierarchy builder feeds them to
    the colored smoother) avoid running the device scatter twice.
    ``parts`` requires an explicit ``plan``.

    ``split`` controls the interior/boundary overlap decomposition of the
    local block: ``True`` forces it, ``False`` keeps the historical
    two-part matrix, ``"auto"`` (default) splits exactly when a halo
    exchange will actually be issued (``not remote_empty`` — a
    block-diagonal matrix has nothing to hide the collective behind).

    ``plan_cache`` (a ``repro.tuning.SelectionCache``) persists the fully
    enriched :class:`DistPlan` under a ``distplan:`` key derived from the
    live-pattern fingerprint, so a *restarted* process skips both the
    partition host scan and all per-candidate symbolic conversion
    planning: consulted only when ``plan`` is None, stored after every
    planning build. Hits/misses count as ``distplan.cache_hit`` /
    ``distplan.cache_miss``.
    """
    sizes = mesh.shape
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    nshards = int(np.prod([sizes[a] for a in names]))
    axis = names if len(names) > 1 else names[0]

    cache_key = None
    if plan is None and plan_cache is not None:
        sig = _pattern_sig(row, col, val)
        m, n = shape
        cache_key = f"distplan:{sig}|{m}x{n}|P{nshards}|{halo_mode}"
        rec = plan_cache.get_raw(cache_key)
        if rec is not None:
            try:
                plan = DistPlan.from_json(rec)
            except (KeyError, ValueError):
                plan = None  # unreadable/old record: fall through to planning
        _metrics.inc("distplan.cache_hit" if plan is not None
                     else "distplan.cache_miss")
        if plan is not None:
            _trace.event("plan.cache_hit", key=cache_key)

    if plan is None:
        plan = plan_partition(row, col, val, shape, nshards,
                              halo_mode=halo_mode)
    else:
        if plan.nshards != nshards or plan.shape != tuple(shape):
            raise ValueError(f"plan is for P={plan.nshards} shape={plan.shape}, "
                             f"build asked for P={nshards} shape={tuple(shape)}")
        if check_plan:
            # one vectorised host scan: a stale plan must fail loudly (or,
            # for the memoised format plans, fall back to re-planning)
            # rather than silently drop entries. check_plan=False skips it
            # for trusted analytic plans (e.g. hpcg.slab_plan) so the
            # triplets are touched only by the device scatter.
            _check_plan_fits(row, col, plan, val=val)
            if ((plan.local_plans is not None
                 or plan.interior_plans is not None)
                    and plan.pattern_sig != _pattern_sig(row, col, val)):
                # live pattern changed: the memoised format plans are void
                _metrics.inc("replan.pattern_sig")
                _trace.event("plan.replan", reason="pattern_sig")
                plan = dataclasses.replace(plan, candidates=None,
                                           local_plans=None,
                                           remote_plans=None,
                                           interior_plans=None,
                                           boundary_plans=None,
                                           pattern_sig=None)
    if split == "auto":
        split = not plan.remote_empty
    if split and plan.interior_cap is None:
        icap, bcap = _split_caps(row, col, val, plan.mp, plan.nshards)
        plan = dataclasses.replace(plan, interior_cap=icap, boundary_cap=bcap)
    if parts is not None:
        lcoos, rcoos = parts
        if (lcoos.shape != plan.local_shape
                or rcoos.shape != plan.remote_shape):
            raise ValueError(
                f"parts shapes {lcoos.shape}/{rcoos.shape} do not match the "
                f"plan's {plan.local_shape}/{plan.remote_shape}")
    else:
        # strip the format plans / fingerprint / split metadata for the
        # partition jit key: a plan enriched by plan_dist_formats or the
        # split-cap scan must hit the same partition_execute trace
        part_plan = dataclasses.replace(plan, candidates=None,
                                        local_plans=None, remote_plans=None,
                                        interior_plans=None,
                                        boundary_plans=None,
                                        interior_cap=None, boundary_cap=None,
                                        pattern_sig=None)
        with _trace.span("build.partition_execute", p=plan.nshards) as sp:
            lcoos, rcoos = partition_execute_jit(np.asarray(row),
                                                 np.asarray(col),
                                                 np.asarray(val),
                                                 plan=part_plan, dtype=dtype)
            sp.sync(lcoos.data, rcoos.data)

    bcoos = None
    if split:
        with _trace.span("build.split_execute", p=plan.nshards) as sp:
            lcoos, bcoos = split_local_execute_jit(
                lcoos, rcoos, mp=plan.mp, icap=plan.interior_cap,
                bcap=plan.boundary_cap)
            sp.sync(lcoos.data, bcoos.data)

    boundary = None
    if mode == "uniform":
        local = _row_shard(convert_execute_batch(
            lcoos, plan_switch_batch(lcoos, Format(local_format))))
        if bcoos is not None:
            boundary = _row_shard(convert_execute_batch(
                bcoos, plan_switch_batch(bcoos, Format(local_format))))
        remote = _row_shard(convert_execute_batch(
            rcoos, plan_switch_batch(rcoos, Format(remote_format))))
    elif mode == "multiformat":
        # per-shard selection, paper §V-E, via the unified FormatPolicy
        from repro.tuning.policy import FormatPolicy

        candidates = tuple(Format(c) for c in candidates)
        if isinstance(tune, FormatPolicy):
            policy = tune
            if not set(policy.candidates) <= set(candidates):
                raise ValueError(
                    f"tune policy candidates {[f.name for f in policy.candidates]} "
                    f"must be a subset of the build candidates "
                    f"{[f.name for f in candidates]}: every pick has "
                    f"to map onto a resident union variant")
        else:
            pmode = "profile" if tune == "calibrated" else tune
            policy = FormatPolicy(pmode, candidates=candidates,
                                  profile_iters=3)

        plan = plan_dist_formats(lcoos, rcoos, plan, candidates,
                                 boundary=bcoos)
        if plan.pattern_sig is None:
            # stamp the live pattern the memoised format plans are valid for
            plan = dataclasses.replace(
                plan, pattern_sig=_pattern_sig(row, col, val))
        # policy-candidate indices -> build-candidate (variant) indices
        remap = np.asarray([candidates.index(f) for f in policy.candidates],
                           np.int32)
        lplans = plan.interior_plans if split else plan.local_plans
        lids, rids = remap[policy.select_batch(lcoos)], remap[policy.select_batch(rcoos)]
        local = SwitchDynamicMatrix.build_batched(
            lcoos, candidates, plans=lplans, active_ids=lids)
        if bcoos is not None:
            bids = remap[policy.select_batch(bcoos)]
            boundary = SwitchDynamicMatrix.build_batched(
                bcoos, candidates, plans=plan.boundary_plans, active_ids=bids)
        remote = SwitchDynamicMatrix.build_batched(
            rcoos, candidates, plans=plan.remote_plans, active_ids=rids)
    else:
        raise ValueError(mode)

    if split:
        parts = {"interior": (local, lcoos), "boundary": (boundary, bcoos)}
    else:
        parts = {"local": (local, lcoos)}
    parts["remote"] = (remote, rcoos)
    counts = _count_parts(parts)
    A = DistSparseMatrix(local, remote, boundary=boundary, nshards=nshards,
                         mp=plan.mp, shape=shape, halo_mode=plan.halo_mode,
                         axis=axis, hw=plan.hw, remote_empty=plan.remote_empty)
    A = _shard_containers(A, mesh)
    # Build artifacts (not pytree state): pass back via build(plan=...) and a
    # rebuild performs zero symbolic pulls — partition caps, split caps and
    # per-format SwitchPlans are all memoised.
    A.plan = plan
    A.counts = counts
    if cache_key is not None and plan_cache is not None:
        if plan.pattern_sig is None:
            plan = dataclasses.replace(plan, pattern_sig=sig)
            A.plan = plan
        plan_cache.put_raw(cache_key, plan.to_json())
    return A


def _count_parts(parts: dict) -> dict:
    """``dist.stored.<part>``, the values a part's container stores over
    all shards, padding included, and ``dist.entries.<part>``, the live
    entries of its COO source: their gap is what the part's format pays in
    padding. ``parts`` maps each part's name to ``(container, coo)``. The
    live counts of all parts come in one planned pull; each count is also
    added to the always-on counter of its name."""
    live = _planned_pull(jnp.stack([jnp.count_nonzero(coo.data)
                                    for _, coo in parts.values()]))
    counts = {}
    for (name, (container, _)), n in zip(parts.items(), live):
        counts[f"dist.stored.{name}"] = sum(
            int(a.size) for a in jax.tree.leaves(container)
            if jnp.issubdtype(a.dtype, jnp.floating))
        counts[f"dist.entries.{name}"] = int(n)
    for name, n in counts.items():
        _metrics.inc(name, n)
    return counts


def _shard_containers(A: DistSparseMatrix, mesh: Mesh) -> DistSparseMatrix:
    """Place the parts' arrays on the mesh by :func:`_part_spec`."""
    axis = A.axis

    def put(t):
        # a planned *placement*, not a symbolic pull: resharding a committed
        # single-device array across the mesh may stage through host on CPU
        # backends, which must not trip a build-time transfer guard.
        with jax.transfer_guard("allow"):
            return jax.tree.map(
                lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
                t, _part_spec(t, axis))

    return A._replace_parts(put(A.local), put(A.remote),
                            boundary=put(A.boundary) if A.split else None)


def activate_dist(A: DistSparseMatrix, part: str, fmt_or_ids) -> DistSparseMatrix:
    """Runtime format switch of the local, boundary or remote part
    (paper activate())."""
    if part not in ("local", "boundary", "remote"):
        raise ValueError(f"part {part!r} not in ('local', 'boundary', "
                         f"'remote')")
    if part == "boundary" and not A.split:
        raise ValueError("matrix has no boundary part "
                         "(build_dist_matrix(split=True))")
    tgt = getattr(A, part)
    if isinstance(tgt, SwitchDynamicMatrix):
        if isinstance(fmt_or_ids, Format):
            idx = list(tgt.candidates).index(Format(fmt_or_ids))
            ids = jnp.full((A.nshards,), idx, jnp.int32)
        else:
            # scalar ids broadcast to the per-shard vector the stacked
            # union's shard axis expects
            ids = jnp.broadcast_to(jnp.asarray(fmt_or_ids, jnp.int32),
                                   (A.nshards,))
        new = tgt.activate_id(ids)
    else:
        raise TypeError("uniform-mode parts switch via build (conversion); "
                        "use mode='multiformat' for runtime switching")
    if part == "local":
        return A._replace_parts(new, A.remote)
    if part == "boundary":
        return A._replace_parts(A.local, A.remote, boundary=new)
    return A._replace_parts(A.local, new)


# ---------------------------------------------------------------------------
# Observability wrappers (spans on the host-side build pipeline)
# ---------------------------------------------------------------------------


def _traced_plan_partition(fn):
    @functools.wraps(fn)
    def wrapper(row, col, val, shape, nshards, **kwargs):
        if _trace.mode() == "off":
            return fn(row, col, val, shape, nshards, **kwargs)
        with _trace.span("plan.partition", p=int(nshards)) as sp:
            plan = fn(row, col, val, shape, nshards, **kwargs)
            sp.set(halo=plan.halo_mode, hw=plan.hw,
                   remote_empty=plan.remote_empty)
        return plan
    return wrapper


def _traced_build_dist(fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if _trace.mode() == "off":
            return fn(*args, **kwargs)
        with _trace.span("build.dist",
                         mode=kwargs.get("mode", "uniform")) as sp:
            A = fn(*args, **kwargs)
            sp.set(p=A.nshards, halo=A.halo_mode, hw=A.hw, **A.counts)
        return A
    return wrapper


# Rebind so internal callers (partition_coo, build_dist_matrix, the MG
# hierarchy builder) and importers all get the instrumented entry points.
plan_partition = _traced_plan_partition(plan_partition)
build_dist_matrix = _traced_build_dist(build_dist_matrix)
