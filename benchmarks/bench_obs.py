"""Observability deliverable: localize the distributed overlap loss.

``results/generated_tables.md`` showed ghost-mode distributed SpMV
regressing at P=8 (``scaling_spmv_ghost_p8`` ~0.78x vs reference) after
scaling fine at P=2/4 — the halo exchange stopped overlapping with local
compute somewhere between 4 and 8 shards. This bench answers *where*
using :func:`repro.core.distributed.dist_spmv_phase`: per shard count it
times the production SpMV (``full``) against its phases run alone —

  * ``local``     local SpMV only (interior + boundary), no collective;
  * ``exchange``  halo exchange + remote SpMV only, no local SpMV;
  * ``interior``/``boundary``  the split halves of the local block (the
    interior term is the dependency-free window the scheduler can hide
    the collective in) —

and reports ``hidden_us = local + exchange - full``: the wall time XLA's
latency-hiding scheduler actually overlapped. ``hidden_frac`` normalizes
the *positive* part by ``min(local, exchange)`` (the most overlap that
phase pair could ever hide): ~1.0 means the exchange is fully hidden
behind local compute, 0 means nothing was hidden. A *negative*
``hidden_us`` means composing the phases costs more than running them
separately — that overhead is reported explicitly as ``overhead_frac``
(``max(0, -hidden) / min(local, exchange)``) instead of being silently
floored into the 0.000 that used to hide the p8 regression.

Runs in subprocesses on the CPU backend (``JAX_PLATFORMS=cpu``: one
forced host-device view per shard count, set up by ``repro.env``; on a
chip host the parent holds the chip, and every row says ``platform=cpu``),
same harness shape as ``bench_scaling``, and warms the
kernel-config cache on shard 0's containers first so the phases measure
the same ``backend="auto"`` schedule the scaling bench's ghost runs. Rows land in
``BENCH_obs.json`` via ``python -m benchmarks.run --only obs`` and render
with ``python -m repro.obs.report --bench BENCH_obs.json``.
"""
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = """
import os, sys, tempfile
sys.path.insert(0, %(src)r)
from repro import env
env.apply(host_devices=%(ndev)d)
os.environ.setdefault("REPRO_TUNING_CACHE",
                      os.path.join(tempfile.mkdtemp(), "selections.json"))
import time, json
import jax, jax.numpy as jnp, numpy as np
from repro.core import Format, convert, hpcg
from repro.core.distributed import (build_dist_matrix, dist_spmv,
                                    dist_spmv_phase, distribute_vector)
from repro.obs import metrics
from repro.tuning import kernel_tune
from repro.tuning.cache import SelectionCache
from repro.launch.mesh import make_mesh

mesh = make_mesh((%(ndev)d,), ("rows",))
prob = hpcg.generate_problem(*%(grid)r)
x = distribute_vector(np.ones(prob.shape[0], np.float32), mesh, "rows")
A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                      "rows", local_format=Format.CSR,
                      remote_format=Format.COO)  # the ghost config

# production routing: tune the kernel decision on shard 0's containers so
# backend="auto" measures the same schedule bench_scaling's ghost runs
cache = SelectionCache()
xb = jnp.ones((A.plan.mp,), jnp.float32)
for part in ((A.local, A.boundary) if A.split else (A.local,)):
    s0 = jax.tree_util.tree_map(lambda l: l[0], part)
    kernel_tune.tune_kernel(s0 if Format(s0.format) == Format.CSR
                            else convert(s0, Format.CSR), xb, cache=cache,
                            iters=3, inner=2)

fns = {
    "full": jax.jit(lambda a, v: dist_spmv(a, v, mesh)),
    "local": jax.jit(lambda a, v: dist_spmv_phase(a, v, mesh, phase="local")),
    "exchange": jax.jit(
        lambda a, v: dist_spmv_phase(a, v, mesh, phase="exchange")),
}
if A.split:
    fns["interior"] = jax.jit(
        lambda a, v: dist_spmv_phase(a, v, mesh, phase="interior"))
    fns["boundary"] = jax.jit(
        lambda a, v: dist_spmv_phase(a, v, mesh, phase="boundary"))
out = {"phases": {}, "halo_mode": A.halo_mode, "hw": int(A.hw),
       "remote_empty": bool(A.remote_empty), "split": bool(A.split)}
for name, f in fns.items():
    jax.block_until_ready(f(A, x))  # compile
# round-robin repeats: timing each phase's repeats back-to-back lets
# slow allocator/cache drift within the process masquerade as a phase
# difference — interleaving exposes every phase to the same drift, and
# min-per-phase then shields against scheduler noise
for _ in range(5):
    for name, f in fns.items():
        t0 = time.perf_counter()
        for _ in range(%(iters)d):
            jax.block_until_ready(f(A, x))
        dt = (time.perf_counter() - t0) / %(iters)d
        out["phases"][name] = min(out["phases"].get(name, dt), dt)
out["halo_bytes"] = metrics.value("halo.bytes")
print("RESULT " + json.dumps(out))
"""


def run(shards=(1, 2, 4, 8, 16, 32), grid=(16, 16, 32), iters=20,
        attempts=3):
    rows = []
    for ndev in shards:
        script = SCRIPT % {"ndev": ndev, "src": os.path.abspath(SRC),
                           "grid": tuple(grid), "iters": iters}
        # process-level min: allocator layout and host load perturb a whole
        # process by more than the phase deltas being measured, so the
        # subprocess runs `attempts` times and the run with the fastest
        # production SpMV is kept — the same noise-shielding as the
        # min-over-repeats inside the process, one level up. All phases
        # come from that single process, so the decomposition stays
        # internally consistent (never a mix of best-ofs across runs).
        out, last_err = None, ""
        for _ in range(max(1, attempts)):
            res = subprocess.run([sys.executable, "-c", script],
                                 capture_output=True, text=True, timeout=1800,
                                 env=dict(os.environ, JAX_PLATFORMS="cpu"))
            line = [l for l in res.stdout.splitlines()
                    if l.startswith("RESULT ")]
            if not line:
                last_err = res.stderr[-200:]
                continue
            cand = json.loads(line[0][len("RESULT "):])
            if out is None or cand["phases"]["full"] < out["phases"]["full"]:
                out = cand
        if out is None:
            rows.append((f"obs_overlap_p{ndev}_FAILED", 0.0, last_err))
            continue
        ph = out["phases"]
        full, loc, exc = ph["full"], ph["local"], ph["exchange"]
        derived = (f"local_us={loc * 1e6:.0f};exch_us={exc * 1e6:.0f};"
                   f"halo_mode={out['halo_mode']};hw={out['hw']};"
                   f"halo_bytes={out['halo_bytes']:.0f};platform=cpu")
        if out.get("split") and "interior" in ph:
            derived += (f";interior_us={ph['interior'] * 1e6:.0f};"
                        f"boundary_us={ph['boundary'] * 1e6:.0f}")
        if not out["remote_empty"]:
            # overlap stats only when there is an exchange to hide (at P=1
            # the remote part is statically empty — full == local). The
            # signed hidden_us is reported as-is; its negative part is the
            # phase-composition overhead, called out as overhead_frac.
            hidden = loc + exc - full
            denom = min(loc, exc) or 1.0
            derived += (f";hidden_us={hidden * 1e6:.0f};"
                        f"hidden_frac={max(0.0, hidden) / denom:.3f};"
                        f"overhead_frac={max(0.0, -hidden) / denom:.3f}")
        rows.append((f"obs_overlap_ghost_p{ndev}", full * 1e6, derived))
    if rows and all(name.endswith("_FAILED") for name, _, _ in rows):
        raise RuntimeError(f"bench_obs: all shard counts failed; "
                           f"last: {rows[-1]}")
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(c) for c in r))
