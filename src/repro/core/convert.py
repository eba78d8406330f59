"""Format conversions (paper §III-B "Convert" copy concept).

Architecture: the classic sparse-library *symbolic/numeric* split, made
first-class as an explicit two-phase **plan/execute** API:

  * ``plan_switch`` (symbolic phase): analyse the sparsity *pattern* and
    produce a :class:`SwitchPlan` of static capacities / offset tables /
    block structure. The analysis runs on device (segment-sum / ``unique``
    / compare primitives); only the tiny plan artifacts — a handful of
    scalars, an offset list, a block map — cross to host, **once per
    plan**. The pre-plan pipeline shipped every index array to numpy on
    every ``DynamicMatrix.activate()``; that host round-trip was the
    dominant cost of a format switch.
  * ``convert_execute`` (numeric phase): a pure gather/scatter of values
    into the target layout. Fully jit-able with *zero* device->host
    transfers given a plan; plans are hashable and ride through
    ``jax.jit`` as static arguments, so a solver can re-switch formats
    inside a compiled step at memory-bandwidth cost.

As in the paper, COO acts as the proxy format: any -> COO -> any. Fast
paths exist where they fall out naturally (CSR<->COO order-preserving,
ELL->COO). The one-shot helpers (``coo_to_ell`` etc.) remain as thin
wrappers: hint missing -> plan on the fly; hint given -> validate +
execute.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import (BSR, COO, CSR, DIA, ELL, Dense, Format, HYB,
                                SELL, coo_from_arrays)
from repro.core.ops import csr_row_ids
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

# Sentinel pushed past every valid diagonal offset / block id during the
# device-side ``unique`` sweeps (offsets are < n <= int32 max; block grids
# are validated against int32 before use).
_SENTINEL = np.iinfo(np.int32).max

# Every device->host transfer the symbolic phase performs goes through
# ``_planned_pull`` below: the pull is executed under an explicit
# ``transfer_guard`` allowance (so builders can run with unplanned pulls
# *disallowed*) and counted (the ``planned_pulls`` metric), which is how
# tests assert that batched builds perform a constant number of host
# transfers independent of shard count.


def planned_pull_count() -> int:
    """Number of sanctioned symbolic-phase device->host pulls so far.

    Process-monotonic. For order-independent assertions use
    :func:`planned_pulls_scope` instead of before/after subtraction.
    """
    return int(_metrics.value("planned_pulls"))


class planned_pulls_scope:
    """``with planned_pulls_scope() as s: ...; s.count`` — the number of
    sanctioned pulls performed *inside* the scope, regardless of what ran
    before it in the process (the fix for order-dependent transfer-count
    assertions across a test suite). After exit, ``count`` freezes at the
    scope-closing value — pulls performed later never leak in."""

    _final: Optional[int] = None

    def __enter__(self):
        self._final = None
        self._scope = _metrics.scope()
        return self

    def __exit__(self, *exc):
        self._final = int(self._scope.delta("planned_pulls"))
        return False

    @property
    def count(self) -> int:
        if self._final is not None:
            return self._final
        return int(self._scope.delta("planned_pulls"))


def _planned_pull(x) -> np.ndarray:
    """Pull a small plan artifact (scalar / offset list) to host.

    This is the *only* sanctioned device->host transfer of the plan
    pipeline; it is exempted from any active ``transfer_guard`` and counted
    so callers can verify no O(shards) pulls sneak in.
    """
    _metrics.inc("planned_pulls")
    with jax.transfer_guard_device_to_host("allow"):
        return np.asarray(x)


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# any -> COO (device-friendly where the source layout permits)
# ---------------------------------------------------------------------------


def csr_to_coo(A: CSR) -> COO:
    """CSR -> COO. jit-able: recover row ids from the row-pointer array."""
    rows = csr_row_ids(A.indptr, A.capacity, A.shape[0])
    return COO(rows, A.indices, A.data, A.shape, A.nnz)


def ell_to_coo(A: ELL) -> COO:
    """ELL -> COO. jit-able flatten; padding entries stay (0-valued)."""
    m, k = A.data.shape
    rows = jnp.repeat(jnp.arange(m, dtype=jnp.int32), k)
    return COO(rows, jnp.clip(A.cols.reshape(-1), 0, A.shape[1] - 1),
               A.data.reshape(-1), A.shape, A.nnz)


def dia_to_coo(A: DIA) -> COO:
    """DIA -> COO. jit-able; out-of-matrix diagonal tails become padding."""
    m, n = A.shape
    nd = A.ndiag
    i = jnp.arange(m, dtype=jnp.int32)[None, :]  # (1, M)
    offs = A.offsets[:, None].astype(jnp.int32)  # (nd, 1)
    cols = i + offs
    valid = (cols >= 0) & (cols < n)
    rows = jnp.broadcast_to(i, (nd, m))
    data = jnp.where(valid, A.table, 0)
    rows = jnp.where(valid, rows, 0)
    cols = jnp.where(valid, cols, 0)
    return COO(rows.reshape(-1), cols.reshape(-1), data.reshape(-1), A.shape, A.nnz)


def bsr_to_coo(A: BSR) -> COO:
    """BSR -> COO. jit-able block expansion."""
    bs = A.block_size
    nblk = A.nblocks
    k = jnp.arange(nblk, dtype=jnp.int32)
    brow = jnp.searchsorted(A.indptr, k, side="right").astype(jnp.int32) - 1
    brow = jnp.clip(brow, 0, A.shape[0] // bs - 1)
    bi = jnp.arange(bs, dtype=jnp.int32)
    rows = (brow[:, None, None] * bs + bi[None, :, None])
    cols = (A.indices[:, None, None] * bs + bi[None, None, :])
    rows = jnp.broadcast_to(rows, (nblk, bs, bs)).reshape(-1)
    cols = jnp.broadcast_to(cols, (nblk, bs, bs)).reshape(-1)
    return COO(rows, cols, A.data.reshape(-1), A.shape, A.nnz)


def hyb_to_coo(A: HYB) -> COO:
    """HYB -> COO. jit-able: concatenate the parts' COO views."""
    e = ell_to_coo(A.ell)
    c = A.coo
    return COO(jnp.concatenate([e.row, c.row]), jnp.concatenate([e.col, c.col]),
               jnp.concatenate([e.data, c.data]), A.shape, A.nnz)


def sell_to_coo(A: SELL) -> COO:
    """SELL -> COO. jit-able: recover (slice, lane) from each flat position
    via searchsorted on the slice pointers, then the original row through
    the permutation. Padding/ghost entries stay inert (row 0, val 0)."""
    m, n = A.shape
    c = A.c
    cap = A.capacity
    p = jnp.arange(cap, dtype=jnp.int32)
    s = jnp.searchsorted(A.slice_ptrs, p, side="right").astype(jnp.int32) - 1
    s = jnp.clip(s, 0, A.nslices - 1)
    lane = (p - A.slice_ptrs[s]) % c
    rows = jnp.clip(A.perm[s * c + lane], 0, m - 1).astype(jnp.int32)
    live = A.data != 0
    rows = jnp.where(live, rows, 0)
    cols = jnp.where(live, jnp.clip(A.cols, 0, n - 1), 0).astype(jnp.int32)
    return COO(rows, cols, A.data, A.shape, A.nnz)


def dense_to_coo(A: Dense, capacity: Optional[int] = None) -> COO:
    """Dense -> COO. With ``capacity`` (from a plan) the extraction is
    jit-able and sync-free via ``jnp.nonzero(size=...)`` — capacity
    validation is the plan phase's job; excess nonzeros truncate. Without
    one, the nonzero count is pulled from device first (one scalar
    sync)."""
    cnt = jnp.count_nonzero(A.data)
    if capacity is None:
        capacity = max(1, int(cnt))
    cap = int(capacity)
    r, c = jnp.nonzero(A.data, size=cap, fill_value=0)
    mask = jnp.arange(cap) < jnp.minimum(cnt, cap)
    val = jnp.where(mask, A.data[r, c], 0)
    r = jnp.where(mask, r, 0).astype(jnp.int32)
    c = jnp.where(mask, c, 0).astype(jnp.int32)
    return COO(r, c, val, A.shape, cap)


def to_coo(A, capacity: Optional[int] = None) -> COO:
    if isinstance(A, COO):
        return A
    if isinstance(A, CSR):
        return csr_to_coo(A)
    if isinstance(A, ELL):
        return ell_to_coo(A)
    if isinstance(A, DIA):
        return dia_to_coo(A)
    if isinstance(A, BSR):
        return bsr_to_coo(A)
    if isinstance(A, HYB):
        return hyb_to_coo(A)
    if isinstance(A, SELL):
        return sell_to_coo(A)
    if isinstance(A, Dense):
        return dense_to_coo(A, capacity)
    raise TypeError(f"not a sparse container: {type(A)}")


# ---------------------------------------------------------------------------
# The symbolic phase: SwitchPlan / plan_switch
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SwitchPlan:
    """Output of the symbolic phase of a format switch.

    Everything in here is *static* python data (ints and tuples), which
    makes a plan hashable — pass it through ``jax.jit`` as a static
    argument and the numeric phase compiles once per (shapes, plan) and
    never touches host again. Plans are produced by :func:`plan_switch`
    (or by the tuning policy via ``FormatPolicy.plan_for``) and consumed
    by :func:`convert_execute`.
    """

    target: Format
    ell_k: Optional[int] = None                       # ELL width / HYB split
    dia_offsets: Optional[Tuple[int, ...]] = None     # occupied diagonals
    block_size: Optional[int] = None                  # BSR block edge
    bsr_indptr: Optional[Tuple[int, ...]] = None      # BSR block-row ptrs
    bsr_indices: Optional[Tuple[int, ...]] = None     # BSR block columns
    hyb_coo_capacity: Optional[int] = None            # HYB overflow slots
    capacity: Optional[int] = None                    # Dense->COO extraction
    sell_c: Optional[int] = None                      # SELL slice height C
    sell_sigma: Optional[int] = None                  # SELL sort window
    sell_slice_ptrs: Optional[Tuple[int, ...]] = None  # SELL flat slice caps
    sell_perm: Optional[Tuple[int, ...]] = None       # SELL row permutation
    # (sell_perm is None for batch plans: each part derives its own sigma-
    # sort permutation on device in the numeric phase; the slice caps are
    # the elementwise max over parts and stay shared/static.)

    def __post_init__(self):
        object.__setattr__(self, "target", Format(self.target))

    def to_json(self) -> dict:
        """JSON-ready dict (Format by name, tuples as lists) — the on-disk
        half of persistent plan caching (``distplan:`` namespace)."""
        out = {"target": Format(self.target).name}
        for f in dataclasses.fields(self):
            if f.name == "target":
                continue
            v = getattr(self, f.name)
            if v is not None:
                out[f.name] = list(v) if isinstance(v, tuple) else v
        return out

    @classmethod
    def from_json(cls, doc: dict) -> "SwitchPlan":
        kw = {"target": Format[doc["target"]]}
        for f in dataclasses.fields(cls):
            if f.name == "target" or f.name not in doc:
                continue
            v = doc[f.name]
            kw[f.name] = tuple(v) if isinstance(v, list) else v
        return cls(**kw)


def _live_row_counts(C: COO, live) -> jax.Array:
    """Per-row count of live (non-zero) entries, on device."""
    return jax.ops.segment_sum(live.astype(jnp.int32), C.row,
                               num_segments=C.shape[0])


def _unique_small(values, sentinel=_SENTINEL) -> np.ndarray:
    """Device ``unique`` then pull only the compacted result to host.

    The transfer is O(#unique) — an offset list or a block map — not
    O(nnz) like the pre-plan host symbolic phase.
    """
    u = _planned_pull(jnp.unique(values))
    return u[u != sentinel]


def _dia_offsets(row, col, live, m: int, n: int) -> np.ndarray:
    """Ascending distinct live diagonals ``col - row`` (any leading batch
    axes), pulled to host.

    Diagonals of an ``(m, n)`` matrix lie in ``[1 - m, n - 1]``, so one
    presence scatter over that range finds them: O(nnz) device work and an
    ``(m + n - 1)``-long mask pulled, where a ``unique`` would sort every
    entry and pull an nnz-long mask.
    """
    span = m + n - 1
    d = jnp.where(live, col.astype(jnp.int32) - row.astype(jnp.int32)
                  + (m - 1), span)
    seen = jnp.zeros((span,), bool).at[d.ravel()].set(True, mode="drop")
    return np.flatnonzero(_planned_pull(seen)) - (m - 1)


def _sell_geometry(c: Optional[int], sigma: Optional[int], m: int):
    """Normalize (C, sigma) hints: C defaults to 32 lanes, sigma to 8*C
    (and is never smaller than C — a sub-slice sort window is meaningless)."""
    C = 32 if c is None else max(1, int(c))
    s = 8 * C if sigma is None else int(sigma)
    s = max(C, s)
    nslices = max(1, -(-m // C))
    return C, s, nslices


def _sell_perm(counts, sigma: int, m: int) -> jax.Array:
    """sigma-window sort permutation, on device: rows ordered by window,
    then by descending live-entry count (stable — ties keep matrix order).
    ``perm[p]`` is the original row stored at sorted position ``p``."""
    wid = jnp.arange(m, dtype=jnp.int32) // sigma
    return jnp.lexsort((-counts.astype(jnp.int32), wid)).astype(jnp.int32)


def _sell_widths(counts, perm, c: int, nslices: int) -> jax.Array:
    """Per-slice max live-row-count after the sigma-sort, on device."""
    mp = nslices * c
    m = counts.shape[0]
    sc = jnp.zeros((mp,), jnp.int32).at[:m].set(counts[perm].astype(jnp.int32))
    sids = jnp.arange(mp, dtype=jnp.int32) // c
    return jax.ops.segment_max(sc, sids, num_segments=nslices)


def _sell_ptrs(widths_np: np.ndarray, c: int) -> Tuple[int, ...]:
    """Static flat slice pointers from pulled per-slice widths. An all-empty
    matrix keeps one padding plane so the flat arrays are never zero-size."""
    widths_np = np.asarray(widths_np, np.int64).copy()
    if widths_np.sum() == 0:
        widths_np[0] = 1
    ptrs = np.concatenate([np.zeros(1, np.int64), np.cumsum(widths_np * c)])
    return tuple(int(x) for x in ptrs)


def plan_switch(A, fmt: Format, *, k: Optional[int] = None,
                offsets: Optional[Sequence[int]] = None,
                block_size: int = 128,
                capacity: Optional[int] = None,
                c: Optional[int] = None,
                sigma: Optional[int] = None,
                check: bool = True) -> SwitchPlan:
    """Symbolic phase: compute the :class:`SwitchPlan` for ``A`` -> ``fmt``.

    Pattern analysis (row counts, occupied diagonals, block structure)
    runs on device; only the plan artifacts are pulled to host. Explicit
    hints (``k=``, ``offsets=``, ``block_size=``) short-circuit the
    analysis — that is how the tuning policy or a distributed builder
    supplies a plan computed elsewhere.
    """
    fmt = Format(fmt)
    if isinstance(A, Dense):
        need = max(1, int(_planned_pull(jnp.count_nonzero(A.data))))
        if capacity is None:
            capacity = need
        elif int(capacity) < need:
            raise ValueError(f"capacity {capacity} < {need} nonzeros")
    if capacity is not None:
        capacity = int(capacity)

    if fmt in (Format.COO, Format.CSR, Format.DENSE):
        return SwitchPlan(fmt, capacity=capacity)

    C = to_coo(A, capacity=capacity)
    m, n = C.shape
    live = C.data != 0

    if fmt == Format.ELL:
        if k is None:
            k = max(1, int(_planned_pull(jnp.max(_live_row_counts(C, live)))))
        elif check and not _is_tracer(C.data):
            counts = _live_row_counts(C, live)
            probe = _planned_pull(jnp.stack([jnp.max(counts),
                                             jnp.argmax(counts).astype(jnp.int32)]))
            kmax, bad_row = int(probe[0]), int(probe[1])
            if kmax > int(k):
                raise ValueError(
                    f"coo_to_ell: k={int(k)} but row {bad_row} holds {kmax} "
                    f"live entries; the overflow would be silently dropped. "
                    f"Pass k>={kmax}, or use Format.HYB which spills "
                    f"overflow into its COO part.")
        return SwitchPlan(fmt, ell_k=int(k), capacity=capacity)

    if fmt == Format.SELL:
        C_, sig, nslices = _sell_geometry(c, sigma, m)
        counts = _live_row_counts(C, live)
        perm = _sell_perm(counts, sig, m)
        widths = _sell_widths(counts, perm, C_, nslices)
        # one planned pull for the whole geometry: widths then permutation
        probe = _planned_pull(jnp.concatenate([widths, perm]))
        ptrs = _sell_ptrs(probe[:nslices], C_)
        return SwitchPlan(fmt, sell_c=C_, sell_sigma=sig,
                          sell_slice_ptrs=ptrs,
                          sell_perm=tuple(int(x) for x in probe[nslices:]),
                          capacity=capacity)

    if fmt == Format.DIA:
        if offsets is None:
            offs = _dia_offsets(C.row, C.col, live, m, n)
            offsets = offs if offs.size else np.array([0])
        # the numeric phase routes entries with searchsorted, which needs
        # ascending *unique* offsets: a duplicated offset would leave its
        # second slot permanently unreachable, and the historical distributed
        # builder's duplicate-offset padding could alias a live diagonal —
        # dedupe here so every plan is canonical.
        offsets = tuple(int(o) for o in np.unique(np.asarray(offsets).ravel()))
        return SwitchPlan(fmt, dia_offsets=offsets, capacity=capacity)

    if fmt == Format.BSR:
        bs = int(block_size)
        if m % bs or n % bs:
            raise ValueError(f"shape {C.shape} not a multiple of block size {bs}")
        nbr, nbc = m // bs, n // bs
        if nbr * nbc >= np.iinfo(np.int32).max:
            raise ValueError("block grid too large for int32 block ids")
        gid = jnp.where(live, (C.row // bs) * nbc + (C.col // bs), _SENTINEL)
        blk = _unique_small(gid).astype(np.int64)
        if blk.size == 0:
            blk = np.zeros(1, np.int64)  # single inert zero block at (0, 0)
        pbr, pbc = blk // nbc, blk % nbc
        indptr = np.zeros(nbr + 1, np.int64)
        np.add.at(indptr, pbr + 1, 1)
        indptr = np.cumsum(indptr)
        return SwitchPlan(fmt, block_size=bs,
                          bsr_indptr=tuple(int(i) for i in indptr),
                          bsr_indices=tuple(int(c) for c in pbc),
                          capacity=capacity)

    if fmt == Format.HYB:
        counts = _live_row_counts(C, live)
        if k is None:
            k = _median_positive(counts, m)
        k = max(1, int(k))
        coo_cap = max(1, int(jnp.sum(jnp.maximum(counts - k, 0))))
        return SwitchPlan(fmt, ell_k=k, hyb_coo_capacity=coo_cap,
                          capacity=capacity)

    raise ValueError(f"unknown format {fmt}")


def _median_positive(counts, m: int) -> int:
    """Median of the positive row counts, computed on device (one scalar
    sync). Mirrors the historical ``np.median(counts[counts > 0])``."""
    npos = int(_planned_pull(jnp.sum(counts > 0)))
    if npos == 0:
        return 1
    s = jnp.sort(counts)
    nz = m - npos
    lo = min(nz + (npos - 1) // 2, m - 1)
    hi = min(nz + npos // 2, m - 1)
    return max(1, int(_planned_pull(s[lo] + s[hi])) // 2)


# ---------------------------------------------------------------------------
# Batched symbolic/numeric phases (stacked shard containers)
# ---------------------------------------------------------------------------


def _batch_row_counts(C: COO) -> jax.Array:
    """(P, M) live-entry row counts of a stacked COO batch, one device pass."""
    m = C.shape[0]

    def one(row, data):
        return jax.ops.segment_sum((data != 0).astype(jnp.int32), row,
                                   num_segments=m)

    return jax.vmap(one)(C.row, C.data)


def plan_switch_batch(A: COO, fmt: Format, *, k: Optional[int] = None,
                      offsets: Optional[Sequence[int]] = None,
                      block_size: int = 128,
                      capacity: Optional[int] = None,
                      c: Optional[int] = None,
                      sigma: Optional[int] = None,
                      check: bool = True) -> SwitchPlan:
    """Shared symbolic phase over a *stacked* batch of same-shape COO parts.

    ``A`` is a COO container whose arrays carry a leading batch (shard)
    axis: ``row/col/data`` of shape ``(P, capacity)`` with ``shape`` the
    per-part matrix shape — exactly what the distributed partitioner emits.
    One device pass analyses every part at once and produces a single
    :class:`SwitchPlan` valid for the whole batch (shared ELL width = max
    over parts, DIA offsets = deduped union over parts, shared HYB split,
    union BSR block map), so the numeric phase can ``vmap`` under one
    static plan — see :func:`convert_execute_batch`. Host traffic is a
    handful of :func:`_planned_pull` artifacts, independent of P.
    """
    fmt = Format(fmt)
    if not isinstance(A, COO) or getattr(A.data, "ndim", 1) != 2:
        raise TypeError("plan_switch_batch expects a stacked COO container "
                        "with (P, capacity) arrays")
    m, n = A.shape

    if fmt in (Format.COO, Format.CSR, Format.DENSE):
        return SwitchPlan(fmt, capacity=capacity)

    live = A.data != 0

    if fmt == Format.ELL:
        if k is None:
            k = max(1, int(_planned_pull(jnp.max(_batch_row_counts(A)))))
        elif check and not _is_tracer(A.data):
            counts = _batch_row_counts(A)
            probe = _planned_pull(jnp.stack([jnp.max(counts),
                                             jnp.argmax(counts).astype(jnp.int32)]))
            kmax, flat = int(probe[0]), int(probe[1])
            part, bad_row = divmod(flat, m)
            if kmax > int(k):
                raise ValueError(
                    f"plan_switch_batch: k={int(k)} but row {bad_row} of "
                    f"part {part} holds {kmax} live entries; the overflow "
                    f"would be silently dropped. Pass k>={kmax}, or use "
                    f"Format.HYB which spills overflow into its COO part.")
        return SwitchPlan(fmt, ell_k=int(k), capacity=capacity)

    if fmt == Format.SELL:
        C_, sig, nslices = _sell_geometry(c, sigma, m)
        counts = _batch_row_counts(A)  # (P, M)

        def one(cnt):
            return _sell_widths(cnt, _sell_perm(cnt, sig, m), C_, nslices)

        # shared static slice caps = elementwise max over parts: a part's
        # i-th-largest count inside any sigma window is <= the max over
        # parts, so every part's own sigma-sort fits under the shared caps.
        widths = jnp.max(jax.vmap(one)(counts), axis=0)
        ptrs = _sell_ptrs(_planned_pull(widths), C_)
        return SwitchPlan(fmt, sell_c=C_, sell_sigma=sig,
                          sell_slice_ptrs=ptrs, sell_perm=None,
                          capacity=capacity)

    if fmt == Format.DIA:
        if offsets is None:
            offs = _dia_offsets(A.row, A.col, live, m, n)  # union over parts
            offsets = offs if offs.size else np.array([0])
        offsets = tuple(int(o) for o in np.unique(np.asarray(offsets).ravel()))
        return SwitchPlan(fmt, dia_offsets=offsets, capacity=capacity)

    if fmt == Format.HYB:
        counts = _batch_row_counts(A)
        if k is None:
            k = _median_positive(counts.ravel(), int(counts.size))
        k = max(1, int(k))
        overflow = jnp.sum(jnp.maximum(counts - k, 0), axis=1)  # per part
        coo_cap = max(1, int(_planned_pull(jnp.max(overflow))))
        return SwitchPlan(fmt, ell_k=k, hyb_coo_capacity=coo_cap,
                          capacity=capacity)

    if fmt == Format.BSR:
        bs = int(block_size)
        if m % bs or n % bs:
            raise ValueError(f"shape {A.shape} not a multiple of block size {bs}")
        nbr, nbc = m // bs, n // bs
        if nbr * nbc >= np.iinfo(np.int32).max:
            raise ValueError("block grid too large for int32 block ids")
        gid = jnp.where(live, (A.row // bs) * nbc + (A.col // bs), _SENTINEL)
        blk = _unique_small(gid.ravel()).astype(np.int64)  # union over parts
        if blk.size == 0:
            blk = np.zeros(1, np.int64)
        pbr, pbc = blk // nbc, blk % nbc
        indptr = np.zeros(nbr + 1, np.int64)
        np.add.at(indptr, pbr + 1, 1)
        indptr = np.cumsum(indptr)
        return SwitchPlan(fmt, block_size=bs,
                          bsr_indptr=tuple(int(i) for i in indptr),
                          bsr_indices=tuple(int(c) for c in pbc),
                          capacity=capacity)

    raise ValueError(f"unknown format {fmt}")


@functools.partial(jax.jit, static_argnums=1)
def convert_execute_batch(A, plan: SwitchPlan):
    """Batched numeric phase: ``vmap`` of :func:`convert_execute` over the
    leading (shard) axis under one shared static plan. Jit-compiled once
    per (shapes, plan), zero device->host transfers — the distributed
    builder's conversion is one call of this per candidate format, never a
    per-shard Python loop.
    """
    return jax.vmap(lambda part: convert_execute(part, plan))(A)


# ---------------------------------------------------------------------------
# The numeric phase: convert_execute (fully jit-able given a plan)
# ---------------------------------------------------------------------------


def _row_slots(C: COO):
    """Stable row sort + within-row slot of every *live* entry (device).

    Slots rank live (non-zero) entries only: dead entries — capacity
    padding, or explicit zeros interleaved with data as ``dia_to_coo``
    emits for partially-filled diagonals — must not inflate the rank of
    the live entries behind them, or ELL widths and HYB split capacities
    (both derived from live counts) silently drop data. Dead entries get
    a meaningless (possibly colliding) slot; callers mask them out.
    """
    m = C.shape[0]
    order = jnp.argsort(C.row, stable=True)
    rows, cols, data = C.row[order], C.col[order], C.data[order]
    live = data != 0
    live_counts = jax.ops.segment_sum(live.astype(jnp.int32), rows,
                                      num_segments=m)
    live_starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(live_counts).astype(jnp.int32)])[:-1]
    slot = jnp.cumsum(live.astype(jnp.int32)) - 1 - live_starts[rows]
    return rows, cols, data, slot, live


def coo_to_csr(A: COO) -> CSR:
    """COO -> CSR. jit-able: stable sort by row, bincount row pointers.

    Padding entries (row 0, val 0) sort to the front of row 0 — harmless.
    """
    m = A.shape[0]
    order = jnp.argsort(A.row, stable=True)
    rows = A.row[order]
    counts = jnp.bincount(rows, length=m)
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    return CSR(indptr, A.col[order], A.data[order], A.shape, A.nnz)


def _coo_to_ell_exec(A: COO, k: int) -> ELL:
    """ELL numeric phase: jit-able scatter into the (M, K) planes."""
    m = A.shape[0]
    k = int(k)
    rows, cols, data, slot, live = _row_slots(A)
    # zero-valued (dead) entries carry meaningless slots; park them in the
    # guard column dropped below. ELL padding sentinel is col=-1 (gathers
    # clip to 0, data=0 keeps it inert; -1 can never collide with a real
    # diagonal position).
    dead = ~live
    slot = jnp.where(dead, k, slot)
    cols_plane = jnp.full((m, k + 1), -1, jnp.int32).at[rows, jnp.clip(slot, 0, k)].set(jnp.where(dead, -1, cols))
    data_plane = jnp.zeros((m, k + 1), A.dtype).at[rows, jnp.clip(slot, 0, k)].add(jnp.where(dead, 0, data))
    return ELL(cols_plane[:, :k], data_plane[:, :k], A.shape, A.nnz)


def coo_to_ell(A: COO, k: Optional[int] = None, *, check: bool = True) -> ELL:
    """COO -> ELL. ``k`` missing -> planned on the fly; ``k`` given ->
    validated (live entries beyond slot ``k`` would otherwise be silently
    dropped) unless ``check=False`` or the data is a tracer (a jitted
    caller must pass a validated plan/width)."""
    plan = plan_switch(A, Format.ELL, k=k, check=check)
    return _coo_to_ell_exec(A, plan.ell_k)


def _coo_to_dia_exec(A: COO, offsets: Sequence[int]) -> DIA:
    """DIA numeric phase: jit-able scatter into the (ndiag, M) table."""
    m, n = A.shape
    offsets_arr = jnp.asarray(np.asarray(offsets, np.int32))
    nd = int(offsets_arr.shape[0])
    k = (A.col - A.row).astype(jnp.int32)
    slot = jnp.searchsorted(offsets_arr, k).astype(jnp.int32)
    slot = jnp.clip(slot, 0, nd - 1)
    hit = offsets_arr[slot] == k  # entries on non-listed diagonals are dropped
    data = jnp.zeros((nd, m), A.dtype).at[slot, A.row].add(jnp.where(hit, A.data, 0))
    return DIA(offsets_arr, data, A.shape, A.nnz)


def coo_to_dia(A: COO, offsets: Optional[Sequence[int]] = None) -> DIA:
    """COO -> DIA. Symbolic: the set of occupied diagonals (planned unless
    given, sorted ascending); numeric: jit-able scatter."""
    plan = plan_switch(A, Format.DIA, offsets=offsets)
    return _coo_to_dia_exec(A, plan.dia_offsets)


def _coo_to_bsr_exec(A: COO, plan: SwitchPlan) -> BSR:
    """BSR numeric phase: jit scatter of entries into their blocks. The
    block map rides in the plan and lowers to on-device constants."""
    m, n = A.shape
    bs = plan.block_size
    nbc = n // bs
    bcol_np = np.asarray(plan.bsr_indices, np.int32)
    indptr_np = np.asarray(plan.bsr_indptr, np.int32)
    brow_np = np.repeat(np.arange(len(indptr_np) - 1, dtype=np.int64),
                        np.diff(indptr_np))
    blk_sorted = brow_np * nbc + bcol_np.astype(np.int64)
    nblk = max(1, len(bcol_np))
    blk_lut = jnp.asarray(blk_sorted.astype(np.int32))
    gid = (A.row // bs) * nbc + A.col // bs
    slot = jnp.searchsorted(blk_lut, gid).astype(jnp.int32)
    slot = jnp.clip(slot, 0, nblk - 1)
    hit = blk_lut[slot] == gid
    bi = (A.row % bs).astype(jnp.int32)
    bj = (A.col % bs).astype(jnp.int32)
    data = jnp.zeros((nblk, bs, bs), A.dtype).at[slot, bi, bj].add(jnp.where(hit, A.data, 0))
    return BSR(jnp.asarray(indptr_np), jnp.asarray(bcol_np), data, A.shape,
               A.nnz, bs)


def coo_to_bsr(A: COO, block_size: int = 128, plan=None) -> BSR:
    """COO -> BSR. ``plan`` may be a :class:`SwitchPlan` or the legacy
    ``(indptr, bcol, blk)`` numpy triple."""
    if plan is None:
        plan = plan_switch(A, Format.BSR, block_size=block_size)
    elif not isinstance(plan, SwitchPlan):
        indptr_np, bcol_np, _blk = plan
        plan = SwitchPlan(Format.BSR, block_size=int(block_size),
                          bsr_indptr=tuple(int(i) for i in np.asarray(indptr_np)),
                          bsr_indices=tuple(int(c) for c in np.asarray(bcol_np)))
    return _coo_to_bsr_exec(A, plan)


def _coo_to_hyb_exec(A: COO, k: int, coo_cap: int) -> HYB:
    """HYB numeric phase: one stable row sort, then jit-able scatters into
    the ELL planes (within-row rank < k) and the COO overflow arrays.

    The overflow capacity is static (from the plan); overflow entries are
    compacted with a cumsum and any excess past ``coo_cap`` lands in a
    dropped guard slot.
    """
    m, n = A.shape
    k, coo_cap = int(k), int(coo_cap)
    rows, cols, data, slot, live = _row_slots(A)
    in_ell = (slot < k) & live
    in_coo = (~in_ell) & live
    ell_slot = jnp.where(in_ell, slot, k)
    cols_plane = jnp.full((m, k + 1), -1, jnp.int32).at[rows, jnp.clip(ell_slot, 0, k)].set(jnp.where(in_ell, cols, -1))
    data_plane = jnp.zeros((m, k + 1), A.dtype).at[rows, jnp.clip(ell_slot, 0, k)].add(jnp.where(in_ell, data, 0))
    ell = ELL(cols_plane[:, :k], data_plane[:, :k], A.shape, A.nnz)
    pos = jnp.cumsum(in_coo.astype(jnp.int32)) - 1
    pos = jnp.clip(jnp.where(in_coo, pos, coo_cap), 0, coo_cap)
    crow = jnp.zeros((coo_cap + 1,), jnp.int32).at[pos].set(jnp.where(in_coo, rows, 0))[:coo_cap]
    ccol = jnp.zeros((coo_cap + 1,), jnp.int32).at[pos].set(jnp.where(in_coo, cols, 0))[:coo_cap]
    cdat = jnp.zeros((coo_cap + 1,), A.dtype).at[pos].set(jnp.where(in_coo, data, 0))[:coo_cap]
    coo = COO(crow, ccol, cdat, A.shape, coo_cap)
    return HYB(ell, coo, A.shape, A.nnz)


def coo_to_hyb(A: COO, k: Optional[int] = None) -> HYB:
    """COO -> HYB. Symbolic: split each row at k entries (planned; default
    k = median positive row length); numeric: jit-able scatters."""
    plan = plan_switch(A, Format.HYB, k=k)
    return _coo_to_hyb_exec(A, plan.ell_k, plan.hyb_coo_capacity)


def _coo_to_sell_exec(A: COO, plan: SwitchPlan) -> SELL:
    """SELL numeric phase: jit-able scatter into the flat column-major
    slice storage. When the plan carries ``sell_perm`` (single-matrix
    plans) the permutation lowers to an on-device constant; batch plans
    ship ``sell_perm=None`` and each part re-derives its own sigma-sort on
    device — sort/segment/scatter all ``vmap`` cleanly and the shared
    static slice caps are guaranteed to fit every part.
    """
    m, n = A.shape
    cs = int(plan.sell_c)
    ptrs_np = np.asarray(plan.sell_slice_ptrs, np.int32)
    nslices = len(ptrs_np) - 1
    cap = int(ptrs_np[-1])
    mp = nslices * cs
    rows, cols, data, slot, live = _row_slots(A)
    if plan.sell_perm is not None:
        perm = jnp.asarray(np.asarray(plan.sell_perm, np.int32))
    else:
        counts = jax.ops.segment_sum((A.data != 0).astype(jnp.int32), A.row,
                                     num_segments=m)
        perm = _sell_perm(counts, int(plan.sell_sigma), m)
    # sorted position of each original row; ghost lanes past M map to row M
    inv = jnp.zeros((m,), jnp.int32).at[perm].set(
        jnp.arange(m, dtype=jnp.int32))
    perm_p = jnp.concatenate(
        [perm, jnp.full((mp - m,), m, jnp.int32)]) if mp > m else perm
    ptrs = jnp.asarray(ptrs_np)
    p = inv[rows]
    sl = p // cs
    lane = p % cs
    width = (ptrs[sl + 1] - ptrs[sl]) // cs
    # a live entry whose within-row rank exceeds its slice cap can only
    # mean a stale plan; park it in the dropped guard slot at ``cap``.
    ok = live & (slot < width)
    pos = jnp.where(ok, ptrs[sl] + slot * cs + lane, cap)
    # padding sentinel col=-1 (as in ELL): gathers clip to 0 with data=0
    # inert, and -1 never collides with a real diagonal position.
    cols_flat = jnp.full((cap + 1,), -1, jnp.int32).at[pos].set(
        jnp.where(ok, cols, -1))[:cap]
    data_flat = jnp.zeros((cap + 1,), A.dtype).at[pos].add(
        jnp.where(ok, data, 0))[:cap]
    return SELL(cols_flat, data_flat, perm_p, ptrs, A.shape, A.nnz,
                cs, int(plan.sell_sigma))


def coo_to_sell(A: COO, c: Optional[int] = None,
                sigma: Optional[int] = None) -> SELL:
    """COO -> SELL-C-sigma. Symbolic: sigma-window sort permutation and
    per-slice caps (planned); numeric: jit-able flat scatter."""
    plan = plan_switch(A, Format.SELL, c=c, sigma=sigma)
    return _coo_to_sell_exec(A, plan)


def coo_to_dense(A: COO) -> Dense:
    """COO -> Dense. jit-able scatter-add."""
    m, n = A.shape
    out = jnp.zeros((m, n), A.dtype).at[A.row, A.col].add(A.data)
    return Dense(out, A.shape, A.nnz)


def convert_execute(A, plan: SwitchPlan):
    """Numeric phase of the paper's convert(): any -> ``plan.target`` via
    the COO proxy, with every shape-determining quantity taken from the
    plan. jit-able with ``plan`` as a static argument; performs zero
    device->host transfers.
    """
    fmt = Format(plan.target)
    C = to_coo(A, capacity=plan.capacity)
    if fmt == Format.COO:
        return C
    if fmt == Format.CSR:
        return coo_to_csr(C)
    if fmt == Format.ELL:
        return _coo_to_ell_exec(C, plan.ell_k)
    if fmt == Format.DIA:
        return _coo_to_dia_exec(C, plan.dia_offsets)
    if fmt == Format.BSR:
        return _coo_to_bsr_exec(C, plan)
    if fmt == Format.HYB:
        return _coo_to_hyb_exec(C, plan.ell_k, plan.hyb_coo_capacity)
    if fmt == Format.SELL:
        return _coo_to_sell_exec(C, plan)
    if fmt == Format.DENSE:
        return coo_to_dense(C)
    raise ValueError(f"unknown format {fmt}")


# ---------------------------------------------------------------------------
# The paper's convert(): any -> any via the COO proxy
# ---------------------------------------------------------------------------


def convert(A, fmt: Format, plan: Optional[SwitchPlan] = None, **kwargs):
    """Element-wise conversion between any two formats via the COO proxy.

    With ``plan`` (a precomputed :class:`SwitchPlan`) the call is the pure
    numeric phase — jit-able, zero host syncs. Without one, the symbolic
    hints in ``kwargs`` (``k=`` for ELL/HYB, ``offsets=`` for DIA,
    ``block_size=`` for BSR, ``capacity=`` for Dense sources) seed
    :func:`plan_switch` and the plan is computed on the fly.
    """
    fmt = Format(fmt)
    if plan is not None:
        if not isinstance(plan, SwitchPlan):
            if fmt == Format.BSR:  # legacy (indptr, bcol, blk) triple
                return coo_to_bsr(to_coo(A), kwargs.get("block_size", 128),
                                  plan=plan)
            raise TypeError(f"plan must be a SwitchPlan, got {type(plan)}")
        if Format(plan.target) != fmt:
            raise ValueError(f"plan targets {Format(plan.target).name}, not {fmt.name}")
        return convert_execute(A, plan)
    if getattr(A, "format", None) == fmt and not kwargs:
        return A
    with _trace.span("convert.any", target=fmt.name):
        return convert_execute(A, plan_switch(A, fmt, **kwargs))


# ---------------------------------------------------------------------------
# Observability: plan/execute spans + padding-waste histograms
# ---------------------------------------------------------------------------
# Spans here wrap *host-side* symbolic work (plan_switch) or the dispatch
# of the numeric phase; when a wrapped function is itself being traced by
# jax (tracer inputs), the span measures trace/compile time, which the
# attribution report counts once per compilation rather than per call.
# Padding-waste histograms cost two static-int divisions — every input
# to them (shape, nnz, plan fields) is host metadata, never device data.


def _observe_plan_waste(A, plan: SwitchPlan) -> None:
    try:
        m = int(A.shape[0])
        nnz = int(A.nnz)
    except (TypeError, AttributeError):  # duck-typed inputs without nnz
        return
    if m <= 0:
        return
    if Format(plan.target) == Format.SELL and plan.sell_slice_ptrs:
        slots = int(plan.sell_slice_ptrs[-1])
        if slots > 0:
            _metrics.observe("sell.padding_waste",
                             min(1.0, max(0.0, 1.0 - nnz / slots)))
        return
    if plan.ell_k is None:
        return
    slots = m * int(plan.ell_k)
    if slots <= 0:
        return
    if Format(plan.target) == Format.ELL:
        _metrics.observe("ell.padding_waste",
                         min(1.0, max(0.0, 1.0 - nnz / slots)))
    elif Format(plan.target) == Format.HYB:
        # ELL-part occupancy estimate: nnz minus (at most) the planned COO
        # overflow capacity lands in the k-wide slots.
        ell_nnz = max(0, nnz - int(plan.hyb_coo_capacity or 0))
        _metrics.observe("hyb.padding_waste",
                         min(1.0, max(0.0, 1.0 - ell_nnz / slots)))


def _traced_plan(fn, name: str):
    @functools.wraps(fn)
    def wrapper(A, fmt, **kwargs):
        fmt = Format(fmt)
        if _trace.mode() == "off":
            plan = fn(A, fmt, **kwargs)
        else:
            with _trace.span(name, fmt=fmt.name) as sp:
                plan = fn(A, fmt, **kwargs)
                if plan.ell_k is not None:
                    sp.set(ell_k=plan.ell_k)
                if plan.dia_offsets is not None:
                    sp.set(n_offsets=len(plan.dia_offsets))
        _observe_plan_waste(A, plan)
        return plan
    return wrapper


# Rebind so internal callers (convert, coo_to_*, the tuning policy, the
# distributed builders) all go through the instrumented entry points.
plan_switch = _traced_plan(plan_switch, "plan.switch")
plan_switch_batch = _traced_plan(plan_switch_batch, "plan.switch_batch")


def _traced_execute(fn):
    @functools.wraps(fn)
    def wrapper(A, plan: SwitchPlan):
        if _trace.mode() == "off":
            return fn(A, plan)
        with _trace.span("convert.execute", target=Format(plan.target).name):
            return fn(A, plan)
    return wrapper


convert_execute = _traced_execute(convert_execute)
