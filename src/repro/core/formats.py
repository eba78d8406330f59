"""Sparse-matrix storage-format containers (the paper's "concrete formats").

Each container is a registered JAX pytree dataclass, uniformly parameterized
by value/index dtype and carrying *static* shape/capacity metadata so that
format switches are jit-stable (the TPU analogue of the paper's
"containers resolved at compile time").

Padding convention: containers are capacity-padded; padding entries are
(row=0, col=0, val=0) which contribute nothing under SpMV accumulate
semantics. `nnz` (the *logical* number of stored entries) is static metadata.

Formats:
  COO    - coordinate list; the conversion proxy format (paper §III-B).
  CSR    - compressed sparse row; the paper's reference format.
  DIA    - diagonal; the paper's winner for stencil matrices; ideal on TPU
           (contiguous shifted vector ops, zero gathers).
  ELL    - ELLPACK padded rows; TPU-friendly gather + dense reduce.
  BSR    - block CSR with MXU-aligned blocks (beyond-paper, TPU-native).
  Dense  - dense fallback for the near-dense small-problem regime.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Format(enum.IntEnum):
    """Enum of supported storage formats (paper's `formats_e`)."""

    COO = 0
    CSR = 1
    DIA = 2
    ELL = 3
    BSR = 4
    DENSE = 5
    HYB = 6
    SELL = 7


def _register(cls):
    """Register a dataclass container as a pytree (data vs. meta fields)."""
    data_fields = [f.name for f in dataclasses.fields(cls) if f.metadata.get("pytree_node", True)]
    meta_fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("pytree_node", True)]
    return jax.tree_util.register_dataclass(cls, data_fields, meta_fields)


def static_field():
    return dataclasses.field(metadata={"pytree_node": False})


@_register
@dataclasses.dataclass(frozen=True)
class COO:
    """Coordinate format: explicit (row, col, val) triplets, no ordering."""

    row: jax.Array  # (capacity,) int32
    col: jax.Array  # (capacity,) int32
    data: jax.Array  # (capacity,) values
    shape: Tuple[int, int] = static_field()
    nnz: int = static_field()  # logical nnz (<= capacity)

    format = Format.COO

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def dtype(self):
        return self.data.dtype

    def tree_flatten(self):  # pragma: no cover - convenience
        return (self.row, self.col, self.data), (self.shape, self.nnz)


@_register
@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse row: row-pointer array + (col, val) pairs.

    Entries are sorted by row (CSR's intrinsic ordering); padding lives past
    ``indptr[-1]`` with val=0/col=0 and is dropped by segment-sum.
    """

    indptr: jax.Array  # (M+1,) int32
    indices: jax.Array  # (capacity,) int32 column indices
    data: jax.Array  # (capacity,) values
    shape: Tuple[int, int] = static_field()
    nnz: int = static_field()

    format = Format.CSR

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def dtype(self):
        return self.data.dtype


@_register
@dataclasses.dataclass(frozen=True)
class DIA:
    """Diagonal format.

    ``data[d, i]`` holds A[i, i + offsets[d]] (cusp convention, padded with
    zeros where the diagonal leaves the matrix). Rectangular matrices are
    supported: offsets range over [-(M-1), N-1].

    ``data`` is the ``(ndiag, M)`` table, or the same table lane-tiled,
    ``(ndiag, ceil(M / 128), 128)`` with A[i, i + offsets[d]] at
    ``data[d, i // 128, i % 128]`` and zeros in the rows past M: the
    layout the Pallas DIA kernel reads, which a distributed part stores
    (``repro.core.distributed``). :attr:`table` is the 2-D view of either.
    """

    offsets: jax.Array  # (ndiag,) int32 diagonal offsets (k = col - row)
    data: jax.Array  # (ndiag, M) or lane-tiled (ndiag, ceil(M/128), 128)
    shape: Tuple[int, int] = static_field()
    nnz: int = static_field()

    format = Format.DIA

    @property
    def ndiag(self) -> int:
        return int(self.data.shape[0])

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def tiled(self) -> bool:
        """True when ``data`` is lane-tiled, ``(ndiag, ceil(M/128), 128)``."""
        return self.data.ndim == 3

    @property
    def table(self) -> jax.Array:
        """``data`` as the ``(ndiag, M)`` table, whatever its layout."""
        if not self.tiled:
            return self.data
        return self.data.reshape(self.ndiag, -1)[:, :self.shape[0]]


def tile_dia_table(data: jax.Array) -> jax.Array:
    """An ``(..., M)`` DIA table lane-tiled: ``(..., ceil(M/128), 128)``,
    the rows past M zero."""
    m = data.shape[-1]
    pad = -m % 128
    if pad:
        data = jnp.pad(data, [(0, 0)] * (data.ndim - 1) + [(0, pad)])
    return data.reshape(*data.shape[:-1], (m + pad) // 128, 128)


@_register
@dataclasses.dataclass(frozen=True)
class ELL:
    """ELLPACK: every row padded to K entries; column-index + value planes."""

    cols: jax.Array  # (M, K) int32
    data: jax.Array  # (M, K) values
    shape: Tuple[int, int] = static_field()
    nnz: int = static_field()

    format = Format.ELL

    @property
    def k(self) -> int:
        return int(self.data.shape[1])

    @property
    def dtype(self):
        return self.data.dtype


@_register
@dataclasses.dataclass(frozen=True)
class BSR:
    """Block CSR: (bs x bs) dense blocks addressed CSR-style by block row.

    TPU-native: each stored block feeds the MXU directly. Capacity-padded
    with zero blocks pointing at block-column 0.
    """

    indptr: jax.Array  # (Mb+1,) int32 block-row pointers
    indices: jax.Array  # (blk_capacity,) int32 block-column indices
    data: jax.Array  # (blk_capacity, bs, bs) values
    shape: Tuple[int, int] = static_field()  # element shape (multiple of bs)
    nnz: int = static_field()  # logical element nnz
    block_size: int = static_field()

    format = Format.BSR

    @property
    def nblocks(self) -> int:
        return int(self.data.shape[0])

    @property
    def dtype(self):
        return self.data.dtype


@_register
@dataclasses.dataclass(frozen=True)
class Dense:
    """Dense matrix container (paper's DenseMatrix)."""

    data: jax.Array  # (M, N)
    shape: Tuple[int, int] = static_field()
    nnz: int = static_field()

    format = Format.DENSE

    @property
    def dtype(self):
        return self.data.dtype


@_register
@dataclasses.dataclass(frozen=True)
class HYB:
    """Hybrid ELL + COO (Bell & Garland; cited by the paper as HYB [15]).

    The regular part of each row (up to k entries) lives in the ELL planes;
    the irregular overflow lives in COO — the classic fix for ELL's
    worst-case padding on power-law row lengths. Demonstrates the paper's
    extensibility claim: added without touching DynamicMatrix/algorithms.
    """

    ell: "ELL"
    coo: "COO"
    shape: Tuple[int, int] = static_field()
    nnz: int = static_field()

    format = Format.HYB

    @property
    def dtype(self):
        return self.ell.data.dtype

    @property
    def k(self) -> int:
        return self.ell.k


@_register
@dataclasses.dataclass(frozen=True)
class SELL:
    """SELL-C-sigma: sliced ELLPACK with sigma-window row sorting
    (Kreutzer et al., arXiv:1307.6209).

    Rows are sorted by descending length within sigma-row windows, then
    grouped into slices of C consecutive sorted rows; each slice is padded
    only to its *own* max width — the fix for ELL's global-kmax padding
    blowup on irregular (e.g. power-law) row lengths.

    Storage is flat and column-major within a slice: the entry at lane
    ``r`` (0 <= r < C) and plane ``j`` of slice ``s`` lives at
    ``slice_ptrs[s] + j*C + r``, so every plane is C contiguous lanes —
    SpMV is a dense gather+FMA over contiguous vectors per plane, with one
    output element per lane and no segmented reduction. ``perm[p]`` is the
    original row index stored at sorted position ``p``; ghost lanes past M
    map to row index M (dropped by the out-of-bounds scatter), and padding
    entries carry col=0/val=0 (inert under accumulate).
    """

    cols: jax.Array  # (capacity,) int32, column-major within each slice
    data: jax.Array  # (capacity,) values
    perm: jax.Array  # (nslices*C,) int32 original row at sorted position
    slice_ptrs: jax.Array  # (nslices+1,) int32 flat offset of each slice
    shape: Tuple[int, int] = static_field()
    nnz: int = static_field()
    c: int = static_field()  # slice height C
    sigma: int = static_field()  # sort-window height (multiple of C)

    format = Format.SELL

    @property
    def nslices(self) -> int:
        return int(self.slice_ptrs.shape[-1]) - 1

    @property
    def capacity(self) -> int:
        return int(self.data.shape[-1])

    @property
    def dtype(self):
        return self.data.dtype


SparseMatrix = (COO, CSR, DIA, ELL, BSR, Dense, HYB, SELL)

FORMAT_TO_CLS = {
    Format.COO: COO,
    Format.CSR: CSR,
    Format.DIA: DIA,
    Format.ELL: ELL,
    Format.BSR: BSR,
    Format.DENSE: Dense,
    Format.HYB: HYB,
    Format.SELL: SELL,
}


# ---------------------------------------------------------------------------
# Host-side builders (setup phase; numeric data may later be updated on device)
# ---------------------------------------------------------------------------

def coo_from_arrays(row, col, val, shape, capacity=None, dtype=jnp.float32) -> COO:
    """Build a COO container from host triplets, padding to ``capacity``."""
    row = np.asarray(row, dtype=np.int32)
    col = np.asarray(col, dtype=np.int32)
    val = np.asarray(val)
    nnz = int(row.shape[0])
    cap = int(capacity) if capacity is not None else nnz
    if cap < nnz:
        raise ValueError(f"capacity {cap} < nnz {nnz}")
    r = np.zeros((cap,), np.int32)
    c = np.zeros((cap,), np.int32)
    v = np.zeros((cap,), np.dtype(dtype))
    r[:nnz], c[:nnz], v[:nnz] = row, col, val
    return COO(jnp.asarray(r), jnp.asarray(c), jnp.asarray(v), tuple(shape), nnz)


def dense_from_array(a, dtype=None) -> Dense:
    a = jnp.asarray(a, dtype=dtype)
    return Dense(a, tuple(a.shape), int(a.shape[0] * a.shape[1]))


def coo_from_dense_np(a: np.ndarray, capacity=None, dtype=None) -> COO:
    """Host helper: extract non-zeros of a dense numpy matrix into COO."""
    a = np.asarray(a)
    row, col = np.nonzero(a)
    order = np.lexsort((col, row))
    row, col = row[order], col[order]
    val = a[row, col]
    return coo_from_arrays(row, col, val, a.shape, capacity, dtype or a.dtype)


def random_coo(key, shape, density=0.05, capacity=None, dtype=jnp.float32) -> COO:
    """Random sparse matrix for tests/benchmarks (host-side)."""
    m, n = shape
    rng = np.random.default_rng(int(key) if not hasattr(key, "shape") else int(jax.random.randint(key, (), 0, 2**31 - 1)))
    nnz = max(1, int(density * m * n))
    lin = rng.choice(m * n, size=nnz, replace=False)
    lin.sort()
    row, col = lin // n, lin % n
    val = rng.standard_normal(nnz).astype(np.dtype(dtype))
    # Avoid exact zeros so nnz is meaningful.
    val = np.where(np.abs(val) < 1e-3, 1e-3, val)
    return coo_from_arrays(row, col, val, shape, capacity, dtype)


def banded_coo(shape, offsets, fill=None, dtype=jnp.float32, capacity=None) -> COO:
    """Banded (multi-diagonal) matrix — the stencil-like regular pattern."""
    m, n = shape
    rows, cols, vals = [], [], []
    for d_i, off in enumerate(offsets):
        r = np.arange(max(0, -off), min(m, n - off), dtype=np.int64)
        c = r + off
        rows.append(r)
        cols.append(c)
        if fill is None:
            vals.append(np.full(r.shape, float(len(offsets) - d_i), np.dtype(dtype)))
        else:
            vals.append(np.full(r.shape, fill[d_i], np.dtype(dtype)))
    row = np.concatenate(rows)
    col = np.concatenate(cols)
    val = np.concatenate(vals)
    order = np.lexsort((col, row))
    return coo_from_arrays(row[order], col[order], val[order], shape, capacity, dtype)


def to_dense_np(A) -> np.ndarray:
    """Host-side densification (oracle for tests)."""
    if isinstance(A, HYB):
        return to_dense_np(A.ell) + to_dense_np(A.coo)
    m, n = A.shape
    out = np.zeros((m, n), dtype=np.asarray(A.data).dtype)
    if isinstance(A, COO):
        r, c, v = np.asarray(A.row), np.asarray(A.col), np.asarray(A.data)
        np.add.at(out, (r, c), v)
    elif isinstance(A, CSR):
        indptr = np.asarray(A.indptr)
        idx, v = np.asarray(A.indices), np.asarray(A.data)
        for i in range(m):
            sl = slice(indptr[i], indptr[i + 1])
            np.add.at(out, (np.full(indptr[i + 1] - indptr[i], i), idx[sl]), v[sl])
    elif isinstance(A, DIA):
        offs, d = np.asarray(A.offsets), np.asarray(A.table)
        for k in range(d.shape[0]):
            off = int(offs[k])
            i = np.arange(max(0, -off), min(m, n - off))
            out[i, i + off] += d[k, i]
    elif isinstance(A, ELL):
        cols, v = np.asarray(A.cols), np.asarray(A.data)
        for i in range(m):
            np.add.at(out[i], cols[i], v[i])
    elif isinstance(A, BSR):
        bs = A.block_size
        indptr = np.asarray(A.indptr)
        idx, v = np.asarray(A.indices), np.asarray(A.data)
        for bi in range(len(indptr) - 1):
            for p in range(indptr[bi], indptr[bi + 1]):
                bj = idx[p]
                out[bi * bs:(bi + 1) * bs, bj * bs:(bj + 1) * bs] += v[p]
    elif isinstance(A, SELL):
        cols, v = np.asarray(A.cols), np.asarray(A.data)
        perm, ptrs = np.asarray(A.perm), np.asarray(A.slice_ptrs)
        C = A.c
        for s in range(ptrs.shape[0] - 1):
            w = (int(ptrs[s + 1]) - int(ptrs[s])) // C
            for r in range(C):
                i = int(perm[s * C + r])
                if i >= m:
                    continue  # ghost lane past the last row
                sl = int(ptrs[s]) + r + C * np.arange(w)
                np.add.at(out[i], np.clip(cols[sl], 0, n - 1), v[sl])
    elif isinstance(A, Dense):
        out = np.asarray(A.data).copy()
    else:
        raise TypeError(type(A))
    return out


# ---------------------------------------------------------------------------
# Copy semantics (paper §III-B)
# ---------------------------------------------------------------------------

def shallow_copy(A):
    """Shallow copy: JAX arrays are immutable — aliasing is free and safe.

    Mirrors the paper's same-type requirement: the result *is* the same
    container type with the same buffers.
    """
    return A


def deep_copy(A, sharding=None):
    """Deep (bitwise) copy; with ``sharding`` this is the mirroring interface
    (HostMirror/device transfer analogue): a cross-memory-space memcpy."""
    if sharding is None:
        return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), A)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), A)


def bytes_of(A) -> int:
    """Total payload bytes of a container (for the analytic autotuner)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(A))
