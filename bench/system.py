"""The system under test: HPCG through the library's public API.

The same calls ``examples/hpcg_solve.py`` makes: ``hpcg.generate_problem``,
then ``build_dist_matrix`` over ``hpcg.slab_plan`` (CG) or
``mg.build_dist_hierarchy`` (MG-PCG) on the host's CPU device, then one
jitted ``cg``/``pcg`` over ``operator(A, mesh, backend)``, compiled once.
Imported only after the environment is prepared (``harness.prepare``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax

from repro.core import Format, hpcg
from repro.core.distributed import build_dist_matrix, distribute_vector
from repro.core.solvers import cg, operator, pcg
from repro.launch.mesh import make_mesh

AXIS = "rows"


@dataclasses.dataclass
class System:
    mesh: Any
    A: Any
    hier: Optional[Any]
    build_s: float
    optimize_s: float


def build(config: dict, devices) -> System:
    """Generate the problem and optimize it (partition, select, convert)
    on the host's CPU device; the containers land on ``devices``."""
    mesh = make_mesh((len(devices),), (AXIS,), devices=devices)
    t0 = time.perf_counter()
    prob = hpcg.generate_problem(*config["grid"])
    t1 = time.perf_counter()
    host = jax.local_devices(backend="cpu")[0]
    fmt = dict(local_format=Format[config["local_format"]],
               remote_format=Format[config["remote_format"]],
               mode=config["mode"], tune=config["tune"])
    with jax.default_device(host):
        if config["solver"] == "mg-pcg":
            from repro.mg import build_dist_hierarchy

            mg = config["mg"]
            hier = build_dist_hierarchy(
                prob, mesh, AXIS, nlevels=mg["levels"], pre=mg["pre"],
                post=mg["post"], coarse_sweeps=mg["coarse_sweeps"],
                backend=config["backend"], **fmt)
            if hier.nlevels != mg["levels"]:
                raise ValueError(f"hierarchy has {hier.nlevels} levels, "
                                 f"the configuration states {mg['levels']}")
            A = hier.levels[0].A
        else:
            hier = None
            A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape,
                                  mesh, AXIS,
                                  plan=hpcg.slab_plan(prob, len(devices)),
                                  check_plan=False, **fmt)
    jax.block_until_ready(jax.tree.leaves(A))
    return System(mesh, A, hier, t1 - t0, time.perf_counter() - t1)


def place(system: System, b_host):
    """The right-hand side, row-sharded on the system's mesh."""
    return distribute_vector(b_host, system.mesh, AXIS)


def compile_solve(config: dict, system: System, b, tol: float,
                  maxiter: int) -> Callable:
    """``b -> CGResult``: the jitted solve, compiled for ``b``'s shape."""
    mesh, backend = system.mesh, config["backend"]
    if system.hier is not None:
        fn = jax.jit(lambda a, bb, h: pcg(
            operator(a, mesh, backend=backend), bb, tol=tol, maxiter=maxiter,
            apply_M=h.apply_M()))
        operands = (system.A, system.hier)
    else:
        fn = jax.jit(lambda a, bb: cg(
            operator(a, mesh, backend=backend), bb, tol=tol, maxiter=maxiter))
        operands = (system.A,)
    compiled = fn.lower(operands[0], b, *operands[1:]).compile()
    return lambda bb: compiled(operands[0], bb, *operands[1:])
