"""Distributed MG-PCG: per-level slab partitions over the same mesh.

Every level of the geometric hierarchy is an independent HPCG stencil
system, so every level gets its own analytic
:class:`~repro.core.distributed.DistPlan` from ``hpcg.slab_plan`` (z-slab
partition, correct by construction -> ``check_plan=False``, triplets
touched once by the device scatter) and its own
:func:`~repro.core.distributed.build_dist_matrix` — including
``mode="multiformat"``, where the tuning policy picks each level's
per-shard local/remote formats exactly as for the top-level operator.

The smoother is the standard distributed adaptation of HPCG's SymGS:
halo values are exchanged once per sweep and *frozen* during it (hybrid
block-Jacobi across shards, colored symmetric Gauss-Seidel within each
shard's local block). Folding the frozen halo term into the right-hand
side (``b_eff = b - A_remote x_halo``) reduces the per-shard work to the
single-device colored sweep over the local block, in the same layout
(color-major where the shard slab's dims are all even, see
``repro.mg.smoothers``) — the same ``(NCOLORS, cap)`` stacked split,
built here with one vmapped device scatter over the shard axis. Grid
transfers are injection and z-slabs align across levels (fine z = 2 *
coarse z lands in the same shard), so restriction/prolongation are
shard-local gathers/scatters — no collective.

A V-cycle therefore issues collectives only where the operator itself
does: the per-sweep halo exchange and the residual's overlapped
``dist_spmv``. Each level's operator is built with the default
``split="auto"``, so the residual SpMV runs the interior/boundary overlap
schedule (interior compute while the halo collective is in flight); the
colored smoother keeps working off the *full* local stacked COO the
partition scatter already produced — the split never touches it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import ops as _ops
from repro.core.convert import _planned_pull, convert_execute_batch
from repro.core.distributed import (DistSparseMatrix, _exchange_halo,
                                    _part_spec, _unstack, build_dist_matrix,
                                    dist_spmv, leading_axis_spec)
from repro.core.dynamic import DEFAULT_CANDIDATES, SwitchDynamicMatrix
from repro.core.formats import COO, Format
from repro.core.hpcg import HPCGProblem, generate_problem, partition_problem
from repro.mg.cycle import MIN_COARSE_ROWS
from repro.obs import trace as _trace
from repro.mg.smoothers import (NCOLORS, _split_colors_device, color_grid,
                                color_major, color_major_index, color_ranks,
                                color_rows_padded, count_layout,
                                plan_color_block, symgs_sweeps,
                                to_color_major)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["blocks", "rows", "diag"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class DistColoredSystem:
    """Stacked per-shard color split of the local blocks.

    ``blocks[c]`` is a stacked ``(P, ...)`` container of shape
    ``(rmax, mp)``; ``diag`` is the stacked ``(P, mp)`` local diagonal.
    Every shard's slab has identical geometry, so the color structure is
    shared. Where the slab dims are all even (``smoothers.color_major``
    of the level's ``slab_dims``) the layout is color-major: the blocks'
    columns and ``diag`` are in color-major order, a DIA block is the 27
    diagonals of ``smoothers.color_block_offsets``, and ``rows`` is None.
    Otherwise the blocks keep natural columns and ``rows[c]``, replicated
    on the mesh, holds color ``c``'s local row ids for the sweep's gathers
    and scatter.
    """

    blocks: Tuple
    rows: Optional[Tuple[jax.Array, ...]]
    diag: jax.Array

    @property
    def formats(self):
        out = []
        for b in self.blocks:
            out.append([f.name for f in b.candidates]
                       if isinstance(b, SwitchDynamicMatrix)
                       else Format(b.format).name)
        return out


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["A", "colored", "f2c_local"],
                   meta_fields=["dims", "slab_dims"])
@dataclasses.dataclass(frozen=True)
class DistMGLevel:
    A: DistSparseMatrix
    colored: DistColoredSystem
    f2c_local: Optional[jax.Array]   # (mp_coarse,) replicated; None on coarsest
    dims: Tuple[int, int, int]
    slab_dims: Tuple[int, int, int]


# A pytree, so a solve takes the hierarchy as a jit argument: closed over,
# its arrays would be baked into the executable as constants (gigabytes
# at HPCG's 104^3 per chip).
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["levels"],
                   meta_fields=["mesh", "pre", "post", "coarse_sweeps",
                                "backend"])
@dataclasses.dataclass(frozen=True)
class DistMGHierarchy:
    levels: Tuple[DistMGLevel, ...]
    mesh: Mesh
    pre: int = 1
    post: int = 1
    coarse_sweeps: int = 4
    backend: str = "auto"

    @property
    def nlevels(self) -> int:
        return len(self.levels)

    def apply_M(self) -> Callable:
        return lambda r: v_cycle_dist(self, r)

    def formats(self):
        """Per-level distributed selection summary (A's per-shard active
        ids in multiformat mode + smoother block formats)."""
        out = []
        for i, lev in enumerate(self.levels):
            rec = {"level": i, "dims": lev.dims,
                   "colors": lev.colored.formats}
            parts = (("local", "boundary", "remote") if lev.A.split
                     else ("local", "remote"))
            for part in parts:
                t = getattr(lev.A, part)
                if isinstance(t, SwitchDynamicMatrix):
                    names = [f.name for f in t.candidates]
                    ids = np.asarray(t.active_id)
                    rec[part] = [names[j] for j in ids]
                else:
                    rec[part] = Format(t.format).name
            out.append(rec)
        return out

    def __repr__(self):
        dims = " > ".join("x".join(map(str, lev.dims)) for lev in self.levels)
        return (f"DistMGHierarchy({dims}; P={self.levels[0].A.nshards}, "
                f"pre={self.pre}, post={self.post})")


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------


def _shard_put(t, mesh: Mesh, axis):
    with jax.transfer_guard("allow"):
        return jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, leading_axis_spec(axis, a.ndim))), t)


def _replicate(t, mesh: Mesh):
    with jax.transfer_guard("allow"):
        return jax.device_put(t, NamedSharding(mesh, PartitionSpec()))


def _diag_batched(local: COO) -> jax.Array:
    """(P, mp) local-block diagonal in one vmapped device pass."""
    mp = local.shape[0]

    def one(row, col, data):
        on = row == col
        return jax.ops.segment_sum(jnp.where(on, data, 0), row,
                                   num_segments=mp)

    return jax.vmap(one)(local.row, local.col, local.data)


def _build_dist_colored(local: COO, slab_dims, mesh: Mesh, axis,
                        fmt: Optional[Format] = None,
                        policy=None,
                        candidates: Sequence[Format] = DEFAULT_CANDIDATES
                        ) -> DistColoredSystem:
    """Color-split every shard's local block in one vmapped device scatter.

    Slabs with every dim even take the color-major layout, where ``fmt``
    defaults to DIA; others the natural one, where it defaults to ELL.
    With a ``FormatPolicy``, each color's stacked shard batch goes through
    ``select_batch`` and becomes a stacked ``SwitchDynamicMatrix`` with
    per-shard active ids (the Multi-Format smoother); otherwise every
    block converts uniformly to ``fmt`` via the batched plan/execute.
    """
    mp = local.shape[0]
    cm = color_major(slab_dims)
    if fmt is None:
        fmt = Format.DIA if cm else Format.ELL
    colors = color_grid(*slab_dims)
    counts = np.bincount(colors, minlength=NCOLORS)
    rmax = max(1, int(counts.max()))
    colors_d = jnp.asarray(colors)
    rank_d = jnp.asarray(color_ranks(colors))
    colpos_d = jnp.asarray(color_major_index(slab_dims)) if cm else None

    # shared per-color capacity: one vmapped count + one planned pull
    def _counts(row, data):
        key = jnp.where(data != 0, colors_d[row], NCOLORS)
        return jnp.bincount(key, length=NCOLORS + 1)[:NCOLORS]

    cap = max(1, int(_planned_pull(jnp.max(jax.vmap(_counts)(
        local.row, local.data)))))

    split = jax.vmap(lambda r, c, v: _split_colors_device(
        r, c, v, colors_d, rank_d, cap, colpos_d))
    rr, cc, vv = split(local.row, local.col, local.data)  # (P, NCOLORS, cap)

    blocks = []
    for c in range(NCOLORS):
        Cc = COO(rr[:, c], cc[:, c], vv[:, c], (rmax, mp), cap)
        if policy is not None:
            ids = policy.select_batch(Cc)
            blk = SwitchDynamicMatrix.build_batched(
                Cc, candidates=tuple(policy.candidates), active_ids=ids)
        else:
            blk = convert_execute_batch(Cc, plan_color_block(
                Cc, Format(fmt), slab_dims, c, batch=True))
        blocks.append(_shard_put(blk, mesh, axis))
    diag = _diag_batched(local)
    if cm:
        diag = jax.vmap(lambda d: to_color_major(d, slab_dims))(diag)
        rows = None
    else:
        rows_np = color_rows_padded(colors, mp, rmax)
        rows = _replicate(tuple(rows_np[c] for c in range(NCOLORS)), mesh)
    return DistColoredSystem(tuple(blocks), rows,
                             _shard_put(diag, mesh, axis))


def build_dist_hierarchy(prob: HPCGProblem, mesh: Mesh, axis,
                         nlevels: Optional[int] = None,
                         mode: str = "uniform",
                         tune="cached",
                         local_format: Format = Format.DIA,
                         remote_format: Format = Format.COO,
                         candidates: Sequence[Format] = DEFAULT_CANDIDATES,
                         smoother_format: Optional[Format] = None,
                         smoother_policy=None,
                         pre: int = 1, post: int = 1, coarse_sweeps: int = 4,
                         backend: str = "auto",
                         dtype=jnp.float32) -> DistMGHierarchy:
    """Per-level slab-partitioned hierarchy on ``mesh``.

    Coarsening continues while the grid dims stay even, the coarse slab
    height divides the shard count (``(nz/2) % P == 0`` — each level's
    ``hpcg.slab_plan`` must exist) and the level keeps at least
    ``MIN_COARSE_ROWS`` rows. ``mode``/``tune``/``*_format`` flow into
    every level's ``build_dist_matrix``; ``smoother_policy`` upgrades the
    colored smoother blocks to per-(shard, color) Multi-Format selection.

    A level whose shard slab has every dim even smooths in the color-major
    layout (``repro.mg.smoothers``): each color block is then the 27
    diagonals of the stencil, and without a policy it is stored as DIA, a
    ``(27, mp/8)`` table per shard with no index arrays, which the sweep
    reads as static shifted slices of contiguous color-major vectors.
    A level with an odd slab dim (at HPCG's 104^3, the 13^3 coarsest)
    keeps the natural layout and its per-color gathers and scatter, with
    ELL blocks: at most 27 entries a row, one gather and a row sum. An
    explicit ``smoother_format`` applies to every level, in its layout.
    The counters ``mg.smoother.color_major`` and ``mg.smoother.gather``
    count the levels built each way.
    """
    sizes = mesh.shape
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    nshards = int(np.prod([sizes[a] for a in names]))

    dims = (prob.nx, prob.ny, prob.nz)
    if prob.nz % nshards:
        raise ValueError(f"nz={prob.nz} not divisible by P={nshards}")
    levels = []
    prob_l = prob
    while True:
        nx, ny, nz = dims
        last = ((nlevels is not None and len(levels) + 1 >= nlevels)
                or any(d % 2 for d in dims)
                or (nz // 2) % nshards
                or (nx * ny * nz) // 8 < MIN_COARSE_ROWS)
        # one device scatter per level: the stacked (local, remote) parts
        # feed both the matrix builder (parts=) and the colored smoother
        slab_dims = (nx, ny, nz // nshards)
        with _trace.span("build.mg_dist_level", level=len(levels),
                         dims="x".join(map(str, dims)), p=nshards,
                         layout=count_layout(slab_dims)):
            local, remote, plan = partition_problem(prob_l, nshards,
                                                    dtype=dtype)
            A = build_dist_matrix(prob_l.row, prob_l.col, prob_l.val,
                                  prob_l.shape, mesh, axis,
                                  local_format=local_format,
                                  remote_format=remote_format, mode=mode,
                                  tune=tune, candidates=candidates,
                                  plan=plan, check_plan=False, dtype=dtype,
                                  parts=(local, remote))
            colored = _build_dist_colored(local, slab_dims, mesh, axis,
                                          fmt=smoother_format,
                                          policy=smoother_policy,
                                          candidates=candidates)
        f2c_local = None
        if not last:
            # coarse slab -> fine slab injection map (shard-local: fine
            # z = 2 * coarse z stays inside the same z-slab)
            from repro.mg.coarsen import f2c_map, plan_coarsen

            cplan = plan_coarsen(nx, ny, nz // nshards)
            f2c_local = _replicate(np.asarray(f2c_map(cplan)), mesh)
        levels.append(DistMGLevel(A, colored, f2c_local, dims, slab_dims))
        if last:
            break
        dims = (nx // 2, ny // 2, nz // 2)
        prob_l = generate_problem(*dims)
    return DistMGHierarchy(tuple(levels), mesh, pre=pre, post=post,
                           coarse_sweeps=coarse_sweeps, backend=backend)


# ---------------------------------------------------------------------------
# The distributed V-cycle
# ---------------------------------------------------------------------------


def _dist_smooth(hier: DistMGHierarchy, lev: DistMGLevel, b, x,
                 sweeps: int, x_is_zero: bool):
    """``sweeps`` distributed SymGS sweeps: per sweep, one halo exchange
    (skipped when ``x`` is statically zero — the halo term vanishes) then
    the frozen-halo colored forward+backward sweep on the local block, in
    the level's layout (color-major where its slab dims are all even).
    The exchange and the frozen-halo term run in natural order in the
    ``dist.halo`` and ``dist.remote`` scopes, as in the distributed SpMV."""
    if sweeps <= 0:
        return x if x is not None else jnp.zeros_like(b)
    A, cs = lev.A, lev.colored
    axis = A.axis
    backend = hier.backend
    dims = lev.slab_dims if color_major(lev.slab_dims) else None

    def body(blocks_s, rows, diag_s, remote_s, b_blk, x_blk):
        blocks = [_unstack(blk) for blk in blocks_s]
        remote = _unstack(remote_s)

        def rhs(s, x_of):
            if A.remote_empty or (x_is_zero and s == 0):
                return b_blk
            halo = _exchange_halo(x_of(), A.hw, axis, A.nshards, A.halo_mode)
            with jax.named_scope("dist.remote"):
                y_remote = _ops.spmv(remote, halo, backend=backend)
            return b_blk - y_remote

        return symgs_sweeps(blocks, rows, diag_s[0], rhs, x_blk, sweeps,
                            dims, backend)

    if x is None:
        x = jnp.zeros_like(b)
    fn = jax.shard_map(
        body, mesh=hier.mesh,
        in_specs=(_part_spec(cs.blocks, axis), PartitionSpec(),
                  leading_axis_spec(axis, 2), _part_spec(A.remote, axis),
                  leading_axis_spec(axis, 1), leading_axis_spec(axis, 1)),
        out_specs=leading_axis_spec(axis, 1))
    return fn(cs.blocks, cs.rows, cs.diag, A.remote, b, x)


def _dist_restrict(hier: DistMGHierarchy, lev: DistMGLevel, r):
    axis = lev.A.axis
    fn = jax.shard_map(
        lambda rf, f2c: jnp.take(rf, f2c, mode="clip"),
        mesh=hier.mesh, in_specs=(leading_axis_spec(axis, 1), PartitionSpec()),
        out_specs=leading_axis_spec(axis, 1))
    return fn(r, lev.f2c_local)


def _dist_prolong(hier: DistMGHierarchy, lev: DistMGLevel, xc):
    axis = lev.A.axis
    mp = lev.A.mp

    fn = jax.shard_map(
        lambda xb, f2c: jnp.zeros((mp,), xb.dtype).at[f2c].set(xb),
        mesh=hier.mesh, in_specs=(leading_axis_spec(axis, 1), PartitionSpec()),
        out_specs=leading_axis_spec(axis, 1))
    return fn(xc, lev.f2c_local)


def v_cycle_dist(hier: DistMGHierarchy, r: jax.Array,
                 level: int = 0) -> jax.Array:
    """One distributed V-cycle from a zero guess (jit-able; collectives:
    halo exchanges in the smoother + the overlapped residual SpMV). Its
    steps carry the named scopes of :func:`repro.mg.cycle.v_cycle`."""
    lev = hier.levels[level]
    if level == hier.nlevels - 1:
        with jax.named_scope(f"mg.l{level}.smooth"):
            return _dist_smooth(hier, lev, r, None, hier.coarse_sweeps, True)
    with jax.named_scope(f"mg.l{level}.smooth"):
        x = _dist_smooth(hier, lev, r, None, hier.pre, True)
    with jax.named_scope(f"mg.l{level}.residual"):
        res = r - dist_spmv(lev.A, x, hier.mesh, backend=hier.backend)
    with jax.named_scope(f"mg.l{level}.restrict"):
        rc = _dist_restrict(hier, lev, res)
    xc = v_cycle_dist(hier, rc, level + 1)
    with jax.named_scope(f"mg.l{level}.prolong"):
        x = x + _dist_prolong(hier, lev, xc)
    with jax.named_scope(f"mg.l{level}.smooth"):
        return _dist_smooth(hier, lev, r, x, hier.post, False)
