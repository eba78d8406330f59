"""Kernel-config autotuning: pick (tile config) per (format, shape, device).

The format-selection subsystem answers "which *format*?"; this module
answers the next question down the stack: "with which *kernel
configuration*?" — tile sizes, layouts, and ultimately whether the Pallas
kernel beats the pure-jnp reference path at all. AlphaSparse
(arXiv:2212.10432) shows the winning kernel is a property of the matrix,
and Morpheus-unleashed (arXiv:2304.09511) that it is a property of the
device; both are runtime facts, so we measure them once and cache them.

Design:

* Winners are keyed by ``kernel:`` namespace entries in the *same*
  :class:`~repro.tuning.cache.SelectionCache` JSON store (same flush
  path, same merge-on-flush concurrency story) — one cache file holds
  both format selections and kernel configs.
* The key is (op, format, **shape bucket**, jax backend + interpret
  mode, device kind). The bucket quantizes (m, n, avg row nnz) to
  powers of two: matrices in the same bucket share a winner, so tuning
  one HPCG slab covers every same-sized shard.
* :func:`tune_kernel` times a small tile grid (``default_grid``) with
  the existing ``repro.tuning.engines.time_fn`` harness against the
  reference SpMV, and persists the winner *with both timings* — the
  record keeps ``ref_us`` so routing can refuse a kernel that lost.
* :func:`best_config` is the pure lookup used on the hot path
  (``repro.core.ops.resolve_backend("auto")``): no measuring, host
  dict access only. ``"auto"`` routes to Pallas **iff** a cached record
  exists for the bucket and its measured time beats the reference —
  never merely because the kernel compiles.

Interpret mode (the CPU backend) is part of the backend tag: configs
tuned against interpreted kernels are keyed ``cpu-interp`` and never
replayed against natively-compiled kernels, and vice versa.

CLI::

    python -m repro.tuning.kernel_tune           # warm the default cache
    python -m repro.tuning.kernel_tune --smoke   # tiny-grid CI self-check
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BSR, CSR, DIA, ELL, HYB, SELL, Format
from repro.tuning.cache import SelectionCache, default_cache_path
from repro.tuning.engines import time_fn

KERNEL_NS = "kernel"
KERNEL_SCHEMA = 1


# ---------------------------------------------------------------------------
# Records & keys
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelRecord:
    """A measured kernel-config winner for one (op, format, bucket, device).

    ``speedup`` is kernel-vs-reference: routing treats ``>= 1.0`` as "the
    Pallas path earned the hot path" and anything less as a measured veto.
    """

    fmt: str            # Format name
    op: str             # "spmv" | "spmm" | "spmm_t"
    cfg: dict           # kernel kwargs (tm/tk/layout/tn/...)
    kernel_us: float    # best measured time of cfg, microseconds
    ref_us: float       # reference-path time on the same matrix

    @property
    def speedup(self) -> float:
        return self.ref_us / max(self.kernel_us, 1e-9)

    def to_json(self) -> str:
        return json.dumps({"v": KERNEL_SCHEMA, "fmt": self.fmt, "op": self.op,
                           "cfg": self.cfg, "kernel_us": self.kernel_us,
                           "ref_us": self.ref_us}, sort_keys=True)

    @classmethod
    def from_json(cls, raw: str) -> Optional["KernelRecord"]:
        try:
            d = json.loads(raw)
            if d.get("v") != KERNEL_SCHEMA:
                return None
            return cls(str(d["fmt"]), str(d["op"]), dict(d["cfg"]),
                       float(d["kernel_us"]), float(d["ref_us"]))
        except (ValueError, KeyError, TypeError):
            return None


def _lg(v: float) -> int:
    return int(round(np.log2(max(1.0, float(v)))))


def shape_bucket(m: int, n: int, nnz: int) -> str:
    """Power-of-two bucket of (rows, cols, avg row nnz): the granularity at
    which a tuned config is reused."""
    return f"m{_lg(m)}n{_lg(n)}r{_lg(max(1, nnz) / max(1, m))}"


def backend_tag() -> str:
    """``"<jax backend>-interp"`` or ``"-native"``: a config measured
    against interpreted kernel bodies must never route compiled ones."""
    from repro.kernels import ops as kops
    mode = "interp" if kops.interpret_mode() else "native"
    return f"{jax.default_backend()}-{mode}"


def _device_kind() -> str:
    try:
        return jax.devices()[0].device_kind.replace("|", "/")
    except (IndexError, RuntimeError):
        return "unknown"


def rhs_bucket(ncols: Optional[int]) -> str:
    """Pow2 bucket of the rhs batch width — part of the spmm/spmm_t key.
    ``None`` means "width not stated" and lands in the b=1 bucket, so a
    forgetful caller reads and writes the narrow-decode record
    consistently rather than aliasing every width onto one entry."""
    return f"b{_lg(ncols or 1)}"


def kernel_key(fmt: Format, m: int, n: int, nnz: int, op: str = "spmv",
               backend: Optional[str] = None,
               ncols: Optional[int] = None) -> str:
    """The spmm ops carry the rhs-width bucket in the key (a winner tuned
    at b=1 is never replayed at b=256); spmv keys are unchanged, so
    records tuned before the width axis existed stay valid."""
    width = f"|{rhs_bucket(ncols)}" if op in ("spmm", "spmm_t") else ""
    return (f"{KERNEL_NS}:v{KERNEL_SCHEMA}|{op}|{Format(fmt).name}|"
            f"{shape_bucket(m, n, nnz)}{width}|{backend or backend_tag()}|"
            f"{_device_kind()}")


# Process-wide default cache handle. Re-created when $REPRO_TUNING_CACHE
# repoints the default path (tests / multi-config jobs stay isolated) OR
# when the file changed on disk since it was loaded — SelectionCache reads
# the file once, so without the stamp a tune flushed through a different
# handle (or another process) would be invisible to auto routing for the
# rest of this process's life.
_DEFAULT_CACHE: Optional[SelectionCache] = None
_DEFAULT_STAMP = None


def _cache_stamp(path: str):
    import os
    try:
        st = os.stat(path)
        return (st.st_mtime_ns, st.st_size)
    except OSError:
        return None


def default_kernel_cache() -> SelectionCache:
    global _DEFAULT_CACHE, _DEFAULT_STAMP
    path = default_cache_path()
    stamp = _cache_stamp(path)
    if (_DEFAULT_CACHE is None or _DEFAULT_CACHE.path != path
            or stamp != _DEFAULT_STAMP):
        _DEFAULT_CACHE = SelectionCache(path)
        _DEFAULT_STAMP = stamp
    return _DEFAULT_CACHE


def best_config(A, backend: Optional[str] = None, *, op: str = "spmv",
                ncols: Optional[int] = None,
                cache: Optional[SelectionCache] = None) -> Optional[KernelRecord]:
    """Cached winner for ``A``'s (format, shape bucket[, rhs-width bucket])
    on ``backend`` (default: the running process's tag). Pure lookup —
    never measures."""
    fmt = getattr(A, "format", None)
    if fmt is None:
        return None
    nnz = max(1, int(getattr(A, "nnz", 1)))
    return best_config_for(Format(fmt), A.shape[0], A.shape[1], nnz,
                           backend=backend, op=op, ncols=ncols, cache=cache)


def best_config_for(fmt: Format, m: int, n: int, nnz: int,
                    backend: Optional[str] = None, *, op: str = "spmv",
                    ncols: Optional[int] = None,
                    cache: Optional[SelectionCache] = None
                    ) -> Optional[KernelRecord]:
    # NB: "cache or ..." would misfire — an *empty* SelectionCache is falsy
    cache = cache if cache is not None else default_kernel_cache()
    raw = cache.get_raw(kernel_key(fmt, m, n, nnz, op=op, backend=backend,
                                   ncols=ncols))
    if raw is None:
        return None
    rec = KernelRecord.from_json(raw)
    if rec is None or rec.fmt != Format(fmt).name:
        return None
    return rec


# ---------------------------------------------------------------------------
# Tile grids
# ---------------------------------------------------------------------------


def default_grid(A, smoke: bool = False, op: str = "spmv",
                 ncols: Optional[int] = None) -> List[dict]:
    """The small per-format tile grid :func:`tune_kernel` searches.

    ``smoke=True`` shrinks it to 2-3 configs for CI self-checks. Grids
    always include the density-heuristic default so the tuner can only
    improve on the untuned path. The spmm ops add the ``tn`` rhs-tile
    axis: candidates bracket the (pow2) batch width, so a b=256 sweep
    tries both one wide slab and split rhs tiles.
    """
    from repro.kernels import ops as kops

    # one quantizer for grid generation and the defaults it must include
    _pow2ceil = kops._pow2_clamp
    m = A.shape[0]
    spmm = op in ("spmm", "spmm_t")
    base = kops.default_config(A, op=op, ncols=ncols)
    if isinstance(A, CSR):
        if spmm:
            tn0 = kops._rhs_tile(ncols)
            tns = sorted({tn0, max(1, tn0 // 8)})
            if smoke:
                grid = [base] + [{"tm": 128, "tk": 256, "tn": tn}
                                 for tn in tns]
            else:
                tms = sorted({128, 256, _pow2ceil(min(m, 1024), 128, 1024)})
                grid = [base] + [{"tm": tm, "tk": tk, "tn": tn}
                                 for tm in tms for tk in (512, 2048)
                                 for tn in tns]
        elif smoke:
            grid = [base, {"tm": 128, "tk": 256}]
        else:
            tms = sorted({128, 256, _pow2ceil(min(m, 1024), 128, 1024)})
            tks = (512, 2048, 4096)
            grid = [base] + [{"tm": tm, "tk": tk} for tm in tms for tk in tks]
    elif isinstance(A, ELL):
        if spmm:
            tn0 = kops._rhs_tile(ncols)
            lays = ("row", "col")
            if smoke:
                grid = [base] + [{"tm": 128, "layout": lay, "tn": tn0}
                                 for lay in lays]
            else:
                tms = sorted({256, _pow2ceil(min(m, 1024), 128, 8192)})
                grid = [base] + [{"tm": tm, "layout": lay, "tn": tn}
                                 for tm in tms for lay in lays
                                 for tn in sorted({tn0, max(1, tn0 // 8)})]
        elif smoke:
            grid = [base, {"tm": 128, "layout": "row"},
                    {"tm": 128, "layout": "col"}]
        else:
            tms = sorted({256, 1024, _pow2ceil(m, 128, 8192)})
            grid = [base] + [{"tm": tm, "layout": lay}
                             for tm in tms for lay in ("row", "col")]
    elif isinstance(A, SELL):
        # (c, sigma) reshape the *container* (slice height / sort window) —
        # tune_kernel rebuilds the matrix per cfg; ts is launch geometry.
        # Every cfg carries explicit (c, sigma) so the persisted record
        # names the container geometry its timing was measured on.
        own = {"c": A.c, "sigma": A.sigma}
        base = dict(own, **base)
        if smoke:
            alt_c = 64 if A.c != 64 else 32
            grid = [base, {"c": alt_c, "sigma": 8 * alt_c, "ts": 2}]
        else:
            grid = [base] + [{"c": c, "sigma": 8 * c, "ts": ts}
                             for c in (32, 64, 256) for ts in (1, 2, 8)]
        if spmm:
            tn0 = kops._rhs_tile(ncols)
            grid = [dict(g, tn=g.get("tn", tn0)) for g in grid]
    elif isinstance(A, DIA):
        from repro.kernels.dia_spmv import row_unit
        unit = row_unit(A.dtype)  # tm granule: whole (sublanes, 128) tiles
        grid = [base] + ([{"tm": 2 * unit}] if smoke else
                         [{"tm": k * unit} for k in (1, 4, 16)])
    elif isinstance(A, BSR):
        grid = [base] + ([] if smoke else [{"tn": 256}])
    elif isinstance(A, HYB):
        sub = default_grid(A.ell, smoke=smoke, op=op, ncols=ncols)
        if spmm:
            csr_sub = base.get("csr", {})
            grid = [{"ell": g, "csr": csr_sub} for g in sub]
        else:
            grid = [{"ell": g} for g in sub]
    else:
        grid = [base]
    # dedup while keeping order (the heuristic default may recur in the grid)
    seen, out = set(), []
    for g in grid:
        key = json.dumps(g, sort_keys=True)
        if key not in seen:
            seen.add(key)
            out.append(g)
    return out


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


def _cfg_operand(A, cfg: dict):
    """The container a cfg must be timed on. For SELL, ``c``/``sigma`` are
    container-geometry knobs, not kernel kwargs: a cfg that changes them is
    timed on a rebuilt matrix (same pattern, different slicing)."""
    if isinstance(A, SELL) and cfg:
        c = int(cfg.get("c", A.c))
        sigma = int(cfg.get("sigma", A.sigma))
        if (c, sigma) != (A.c, A.sigma):
            from repro.core.convert import coo_to_sell, sell_to_coo
            return coo_to_sell(sell_to_coo(A), c=c, sigma=sigma)
    return A


def tune_kernel(A, x=None, *, op: str = "spmv",
                cache: Optional[SelectionCache] = None,
                grid: Optional[Sequence[dict]] = None,
                iters: int = 5, inner: int = 4,
                B_cols: int = 8) -> KernelRecord:
    """Search the tile grid for ``A``, persist and return the winner.

    Times every config of ``grid`` (default: :func:`default_grid`) plus
    the reference path with the shared :func:`~repro.tuning.engines.time_fn`
    harness; the stored :class:`KernelRecord` carries both timings so the
    ``"auto"`` route can *refuse* a kernel that measured slower than ref.
    Setup-phase work — never call this inside a jitted step.
    """
    from repro.core import ops as _ops

    cache = cache if cache is not None else default_kernel_cache()
    # A is closed over (not a jit argument): wrappers with host-side
    # preconditions (BSR's indptr scan) need the concrete arrays, and the
    # operand-only signature matches how a solver-jitted SpMV sees them.
    ncols = None
    if op == "spmv":
        if x is None:
            x = jnp.ones((A.shape[1],), A.dtype)
        ref_fn = jax.jit(lambda v: _ops.spmv(A, v, backend="ref"))
        run = lambda cfg: jax.jit(
            lambda v, a=_cfg_operand(A, cfg): _ops.spmv(
                a, v, backend="pallas", cfg=cfg))
    elif op == "spmm":
        if x is None:
            x = jnp.ones((A.shape[1], B_cols), A.dtype)
        ncols = x.shape[1]
        ref_fn = jax.jit(lambda b: _ops.spmm(A, b, backend="ref"))
        run = lambda cfg: jax.jit(
            lambda b, a=_cfg_operand(A, cfg): _ops.spmm(
                a, b, backend="pallas", cfg=cfg))
    elif op == "spmm_t":
        if x is None:
            x = jnp.ones((B_cols, A.shape[1]), A.dtype)
        ncols = x.shape[0]
        ref_fn = jax.jit(lambda b: _ops.spmm_t(A, b, backend="ref"))
        run = lambda cfg: jax.jit(
            lambda b, a=_cfg_operand(A, cfg): _ops.spmm_t(
                a, b, backend="pallas", cfg=cfg))
    else:
        raise ValueError(f"op {op!r} not in ('spmv', 'spmm', 'spmm_t')")

    ref_t = time_fn(ref_fn, x, iters=iters, inner=inner)
    times: Dict[str, float] = {}
    cfgs: Dict[str, dict] = {}
    search = grid if grid is not None else default_grid(A, op=op, ncols=ncols)
    for cfg in search:
        key = json.dumps(cfg, sort_keys=True)
        times[key] = time_fn(run(cfg), x, iters=iters, inner=inner)
        cfgs[key] = cfg
    best_key = min(times, key=times.get)
    rec = KernelRecord(fmt=Format(A.format).name, op=op, cfg=cfgs[best_key],
                       kernel_us=times[best_key] * 1e6, ref_us=ref_t * 1e6)
    nnz = max(1, int(getattr(A, "nnz", 1)))
    cache.put_raw(kernel_key(Format(A.format), A.shape[0], A.shape[1], nnz,
                             op=op, ncols=ncols), rec.to_json())
    return rec


# ---------------------------------------------------------------------------
# CLI: cache warm-up + CI smoke self-check
# ---------------------------------------------------------------------------


def _suite(smoke: bool):
    """Representative matrices to warm the cache with (HPCG stencil +
    irregular random, CSR/ELL/DIA, plus a power-law-rows SELL target)."""
    from repro.core import convert, hpcg, random_coo
    from repro.tuning.corpus import make_matrix

    sizes = ((8, 8, 8),) if smoke else ((8, 8, 8), (16, 16, 16))
    mats = []
    for s in sizes:
        prob = hpcg.generate_problem(*s)
        coo = hpcg.to_coo(prob)
        for fmt in (Format.CSR, Format.ELL, Format.DIA):
            mats.append(convert(coo, fmt))
    n = 512 if smoke else 2048
    rnd = random_coo(0, (n, n), density=0.02)
    for fmt in (Format.CSR, Format.ELL):
        mats.append(convert(rnd, fmt))
    # irregular power-law rows — the workload SELL-C-sigma exists for
    pow_coo = make_matrix("powerlaw", np.random.default_rng(7))
    mats.append(convert(pow_coo, Format.SELL))
    if not smoke:
        mats.append(convert(pow_coo, Format.CSR))
        mats.append(convert(pow_coo, Format.ELL))
    return mats


def run_smoke(cache_path: str, iters: int = 3, inner: int = 2) -> List[KernelRecord]:
    """Tiny-grid tune + the three CI invariants:

    1. the ``kernel:`` records round-trip through a *fresh* cache handle;
    2. ``resolve_backend("auto")`` never routes a config measured slower
       than the reference path;
    3. the auto route agrees numerically with the reference SpMV.
    """
    import os

    from repro.core import ops as _ops
    from repro.tuning.cache import CACHE_PATH_ENV

    # Point the process-default cache at the smoke path so the real
    # spmv("auto") route (not a test-only seam) is what gets exercised.
    prev = os.environ.get(CACHE_PATH_ENV)
    os.environ[CACHE_PATH_ENV] = cache_path
    try:
        cache = SelectionCache(cache_path)
        recs = []
        for A in _suite(smoke=True):
            rec = tune_kernel(A, cache=cache, grid=default_grid(A, smoke=True),
                              iters=iters, inner=inner)
            recs.append(rec)
            fresh = best_config(A, cache=SelectionCache(cache_path))
            assert fresh is not None and fresh.cfg == rec.cfg, \
                f"kernel cache round-trip failed for {rec}"
            backend, cfg = _ops.kernel_route(A, cache=SelectionCache(cache_path))
            if rec.speedup < 1.0:
                assert backend == "ref", \
                    f"auto routed a losing config: {rec} -> {backend}"
            else:
                assert backend == "pallas" and cfg == rec.cfg, (rec, backend, cfg)
            x = jnp.ones((A.shape[1],), A.dtype)
            y_auto = _ops.spmv(A, x, backend="auto")
            y_ref = _ops.spmv(A, x, backend="ref")
            np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_ref),
                                       rtol=1e-4, atol=1e-4)
        # SELL: the persisted record must name the container geometry its
        # timing was measured on — CI asserts a tuned (C, sigma) pair
        # landed in the cache artifact.
        sell_recs = [r for r in recs if r.fmt == "SELL"]
        assert sell_recs, "smoke suite lost its SELL matrix"
        assert all({"c", "sigma", "ts"} <= set(r.cfg) for r in sell_recs), \
            f"SELL record missing container geometry: {sell_recs}"
        # rhs-width isolation: an spmm record tuned at b=1 must be found
        # in the b=1 bucket and invisible to a b=256 lookup.
        A = _suite(smoke=True)[0]
        b1 = jnp.ones((A.shape[1], 1), A.dtype)
        rec = tune_kernel(A, b1, op="spmm", cache=cache,
                          grid=default_grid(A, smoke=True, op="spmm", ncols=1),
                          iters=iters, inner=inner)
        recs.append(rec)
        fresh = SelectionCache(cache_path)
        assert best_config(A, op="spmm", ncols=1, cache=fresh) is not None
        assert best_config(A, op="spmm", ncols=256, cache=fresh) is None, \
            "a b=1 spmm record leaked into the b=256 bucket"
        B = jnp.arange(A.shape[1] * 8, dtype=A.dtype).reshape(A.shape[1], 8)
        np.testing.assert_allclose(
            np.asarray(_ops.spmm(A, B, backend="auto")),
            np.asarray(_ops.spmm(A, B, backend="ref")), rtol=1e-4, atol=1e-4)
        return recs
    finally:
        if prev is None:
            os.environ.pop(CACHE_PATH_ENV, None)
        else:
            os.environ[CACHE_PATH_ENV] = prev


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="tiny grid + cache/routing self-checks (CI)")
    p.add_argument("--cache", default=None,
                   help="cache path (default: the process default)")
    args = p.parse_args(argv)

    if args.smoke:
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            path = args.cache or f"{td}/kernels.json"
            recs = run_smoke(path)
        for r in recs:
            print(f"smoke {r.fmt:4s} cfg={r.cfg} "
                  f"{r.kernel_us:9.1f}us vs ref {r.ref_us:9.1f}us "
                  f"(x{r.speedup:.2f})")
        print(f"kernel_tune smoke OK: {len(recs)} records, "
              f"cache round-trip + auto-routing verified")
        return

    cache = SelectionCache(args.cache) if args.cache else default_kernel_cache()
    for A in _suite(smoke=False):
        rec = tune_kernel(A, cache=cache)
        print(f"tuned {rec.fmt:4s} {A.shape}: cfg={rec.cfg} "
              f"{rec.kernel_us:9.1f}us vs ref {rec.ref_us:9.1f}us "
              f"(x{rec.speedup:.2f})")
    print(f"kernel cache: {cache.path}")


if __name__ == "__main__":
    main()
