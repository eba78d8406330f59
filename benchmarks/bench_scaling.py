"""Paper Fig. 5: multi-shard scaling of the distributed build and SpMV.

Strong scaling (fixed global problem) over 1..32 simulated shards, for the
paper's versions: reference (CSR/CSR), Morpheus (DIA local / CSR remote),
Ghost (CSR local / COO remote) and Multi-Format (per-shard selection via
the cached policy — the production restart path). Three axes per shard
count:

  * ``scaling_build_*``   wall time of ``build_dist_matrix`` in multiformat
    mode — cold (first build: partition plan + switch plans + jit traces)
    and warm (rebuild with the DistPlan's memoised format plans and a hot
    jit cache: the device work only), plus ``ktune``: the once-per-problem
    kernel-config tuning pass on shard 0's containers (records are
    shape-bucketed, so one tune covers every shard). The batched
    partition/convert/select pipeline makes the warm rebuild ~flat in P,
    where the pre-plan host loop grew linearly.
  * ``scaling_spmv_*``    per-call distributed SpMV time for each version;
    the derived column reports the speedup over the uniform-CSR reference.
    The reference is built ``split=False`` and pinned ``backend="ref"`` —
    the paper's baseline issues the exchange against the whole local block
    with nothing reordered and reference kernels only — while the
    optimized versions run the interior/boundary split schedule with
    ``backend="auto"`` routing from the tuned records.
  * ``scaling_restart_first_spmv_*``  restart-to-first-SpMV: a *fresh*
    process whose ``build_dist_matrix(plan_cache=...)`` finds the
    persisted DistPlan (partition caps, split caps, per-candidate
    SwitchPlans) on disk and skips planning entirely, against an
    identical fresh process that re-plans from the triplets.

Subprocess environments are set up by ``repro.env.apply`` (backend-gated
XLA flags, forced host device count) so each shard count gets its own
device view. The children run on the CPU backend (``JAX_PLATFORMS=cpu``):
the suite rehearses a mesh on forced host devices, and on a chip host the
parent process already holds the chip. Every row says ``platform=cpu``.
"""
import json
import os
import subprocess
import sys
import tempfile

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
from repro.core import Format, hpcg
from repro.core.distributed import build_dist_matrix, dist_spmv, distribute_vector
from repro.tuning.cache import SelectionCache
from repro.launch.mesh import make_mesh

mesh = make_mesh((%(ndev)d,), ("rows",))
prob = hpcg.generate_problem(*%(grid)r)
x = distribute_vector(np.ones(prob.shape[0], np.float32), mesh, "rows")
out = {"spmv": {}, "build": {}}
cache = SelectionCache()

build = lambda **kw: build_dist_matrix(prob.row, prob.col, prob.val,
                                       prob.shape, mesh, "rows", **kw)
t0 = time.perf_counter()
A = build(mode="multiformat", tune="cached", plan_cache=cache)
out["build"]["cold"] = time.perf_counter() - t0
t0 = time.perf_counter()
A = build(mode="multiformat", tune="cached", plan=A.plan)
out["build"]["warm"] = time.perf_counter() - t0

# Problem optimization, kernel layer (PR 4): measure the Pallas-vs-ref
# decision once per (format, shape bucket) on shard 0's containers —
# records are bucketed, so one tune covers every same-sized shard, and
# dist_spmv's backend="auto" then routes from measurement instead of
# defaulting to ref. The split interior/boundary containers sit in their
# own (smaller-cap) buckets, which is why the slices are tuned directly
# rather than a synthetic whole-slab block. The reference version never
# reads these records: it is pinned backend="ref" below, the paper's
# untouched baseline.
from repro.core import convert
from repro.tuning import kernel_tune
ghost0 = build(local_format=Format.CSR, remote_format=Format.COO)
xb = jnp.ones((ghost0.plan.mp,), jnp.float32)
t0 = time.perf_counter()
parts = (ghost0.local, ghost0.boundary) if ghost0.split else (ghost0.local,)
for part in parts:
    s0 = jax.tree_util.tree_map(lambda l: l[0], part)
    for fmt in (Format.CSR, Format.DIA, Format.ELL):
        blk = convert(s0, fmt) if Format(s0.format) != fmt else s0
        kernel_tune.tune_kernel(blk, xb, cache=cache, iters=3, inner=2)
out["build"]["ktune"] = time.perf_counter() - t0

for name, backend, kw in [
    # reference = the paper's non-overlapped baseline: whole local block,
    # no interior/boundary reordering, reference kernels only
    ("reference", "ref", dict(local_format=Format.CSR,
                              remote_format=Format.CSR, split=False)),
    ("morpheus", "auto", dict(local_format=Format.DIA,
                              remote_format=Format.CSR)),
    ("ghost", "auto", dict(local_format=Format.CSR,
                           remote_format=Format.COO)),
    ("multiformat", "auto", dict(mode="multiformat", tune="cached")),
]:
    A = build(**kw)
    f = jax.jit(lambda a, v, b=backend: dist_spmv(a, v, mesh, backend=b))
    jax.block_until_ready(f(A, x))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(%(iters)d):
            jax.block_until_ready(f(A, x))
        best = min(best, (time.perf_counter() - t0) / %(iters)d)
    out["spmv"][name] = best
print("RESULT " + json.dumps(out))
"""

# Restart-to-first-SpMV: a fresh process, optionally finding the DistPlan
# persisted by a previous run in the shared SelectionCache store.
RESTART_SCRIPT = """
import os, sys
sys.path.insert(0, %(src)r)
from repro import env
env.apply(host_devices=%(ndev)d)
import time, json
import jax, numpy as np
from repro.core import hpcg
from repro.core.distributed import build_dist_matrix, dist_spmv, distribute_vector
from repro.obs import metrics
from repro.tuning.cache import SelectionCache
from repro.launch.mesh import make_mesh

mesh = make_mesh((%(ndev)d,), ("rows",))
prob = hpcg.generate_problem(*%(grid)r)
x = distribute_vector(np.ones(prob.shape[0], np.float32), mesh, "rows")
kw = dict(mode="multiformat", tune="cached")
if %(use_cache)d:
    kw["plan_cache"] = SelectionCache()
with metrics.scope() as s:
    t0 = time.perf_counter()
    A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", **kw)
    t1 = time.perf_counter()
    jax.block_until_ready(dist_spmv(A, x, mesh))
    t2 = time.perf_counter()
    hit = s.delta("distplan.cache_hit")
print("RESULT " + json.dumps({"build": t1 - t0, "spmv": t2 - t1,
                              "total": t2 - t0, "plan_cache_hit": int(hit)}))
"""


def _run(script: str, timeout: int = 1800, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if env_extra:
        env.update(env_extra)
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")]
    if not line:
        return None, res.stderr[-200:]
    return json.loads(line[0][len("RESULT "):]), None


def _restart_rows(ndev, grid, src):
    """Two fresh processes sharing one on-disk cache: the first warms it,
    the timed pair then measures restart with vs. without the persisted
    plan (both pay identical jit-compile costs — only planning differs)."""
    rows = []
    with tempfile.TemporaryDirectory() as td:
        env_extra = {"REPRO_TUNING_CACHE": os.path.join(td, "selections.json")}
        warm = SCRIPT % {"ndev": ndev, "src": src, "grid": tuple(grid),
                         "iters": 1}
        out, err = _run(warm, env_extra=env_extra)
        if out is None:
            return [(f"scaling_restart_p{ndev}_FAILED", 0.0, err)]
        cached, err = _run(RESTART_SCRIPT % {
            "ndev": ndev, "src": src, "grid": tuple(grid), "use_cache": 1},
            env_extra=env_extra)
        replan, err2 = _run(RESTART_SCRIPT % {
            "ndev": ndev, "src": src, "grid": tuple(grid), "use_cache": 0},
            env_extra=env_extra)
        if cached is None or replan is None:
            return [(f"scaling_restart_p{ndev}_FAILED", 0.0,
                     (err or err2 or "")[-200:])]
        rows.append((
            f"scaling_restart_first_spmv_p{ndev}", cached["total"] * 1e6,
            f"build_us={cached['build'] * 1e6:.0f};"
            f"spmv_us={cached['spmv'] * 1e6:.0f};"
            f"plan_cache_hit={cached['plan_cache_hit']};"
            f"replan_total_us={replan['total'] * 1e6:.0f};"
            f"replan_build_us={replan['build'] * 1e6:.0f};"
            f"speedup_vs_replan={replan['total'] / max(cached['total'], 1e-9):.2f};"
            "platform=cpu"))
    return rows


def run(shards=(1, 2, 4, 8, 16, 32), grid=(16, 16, 32), iters=20,
        restart_shards=(8,)):
    src = os.path.abspath(SRC)
    rows = []
    for ndev in shards:
        script = SCRIPT % {"ndev": ndev, "src": src,
                           "grid": tuple(grid), "iters": iters}
        out, err = _run(script)
        if out is None:
            rows.append((f"scaling_p{ndev}_FAILED", 0.0, err))
            continue
        for phase, t in out["build"].items():
            rows.append((f"scaling_build_{phase}_p{ndev}", t * 1e6,
                         f"per_shard_us={t * 1e6 / ndev:.0f};platform=cpu"))
        ref = out["spmv"]["reference"]
        for name, t in out["spmv"].items():
            rows.append((f"scaling_spmv_{name}_p{ndev}", t * 1e6,
                         f"speedup_vs_ref={ref / t:.2f};platform=cpu"))
    for ndev in restart_shards:
        if ndev in shards:
            rows.extend(_restart_rows(ndev, grid, src))
    if rows and all(name.endswith("_FAILED") for name, _, _ in rows):
        # every shard count crashed: a *_FAILED-only artifact must not keep
        # CI green — surface the last stderr snippet instead
        raise RuntimeError(f"bench_scaling: all shard counts failed; "
                           f"last: {rows[-1]}")
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(c) for c in r))
