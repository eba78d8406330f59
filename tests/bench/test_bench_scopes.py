"""A device op's layer is read from the scopes the program puts in its
``op_name``, a profile's op is placed by its instruction name, and every
op the compiled solves repeat carries a scope."""
import re

import numpy as np
import pytest

import jax

import small_cells  # noqa: F401  (puts the checkout on sys.path)
from bench import scopes


def _op(name, op_name=None, opcode="fusion"):
    meta = (f', metadata={{op_name="{op_name}" source_file="s.py" '
            f'source_line=7}}' if op_name is not None else "")
    return f"%{name} = f32[1124864]{{0}} {opcode}(%p.1), kind=kLoop{meta}"


@pytest.mark.parametrize("text,scope", [
    (_op("fusion.1", "jit(solve)/while/body/solver.vector/mul"),
     "solver.vector"),
    (_op("copy.1"), None),                                   # no metadata
    (_op("fusion.2", "jit(solve)/while/body/dot_general"), None),
    (_op("dia_spmv.9", "jit(<lambda>)/while/body/solver.spmv/jit(dia_spmv)/"
         "dia_spmv/pallas_call", "custom-call"), "solver.spmv"),  # nested jit
    (_op("gather.3", "jit(f)/while/body/solver.precond/mg.l0.smooth/"
         "shard_map/gather"), "mg.l0.smooth"),               # innermost wins
    (_op("cp.4", "jit(f)/while/body/solver.precond/mg.l1.residual/"
         "dist.halo/ppermute", "collective-permute"), "dist.halo"),
    (_op("fusion.5", "jit(f)/while/body/solver.precond/mg.l12.smooth/add"),
     "mg.l12.smooth"),
    (_op("fusion.6", "jit(f)/my_solver.spmvx/xmg.l0.smooth/add"), None),
])
def test_scope_of_reads_the_innermost_program_scope(text, scope):
    assert scopes.scope_of(text) == scope


MODULE = """HloModule jit_solve, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.2 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(solve)/while/body/solver.vector/mul"}
}

%body.3 (state: f32[8]) -> f32[8] {
  %state = f32[8]{0} parameter(0)
  %dia_spmv.9 = f32[8]{0} custom-call(%state), custom_call_target="tpu_custom_call", metadata={op_name="jit(solve)/while/body/solver.spmv/jit(dia_spmv)/dia_spmv/pallas_call" source_file="k.py" source_line=3}
  ROOT %fusion.4 = f32[8]{0} fusion(%dia_spmv.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(solve)/while/body/solver.vector/mul"}
}

%cond.5 (state.1: f32[8]) -> pred[] {
  %state.1 = f32[8]{0} parameter(0)
  ROOT %constant.6 = pred[] constant(false)
}

ENTRY %main.7 (b: f32[8]) -> f32[8] {
  %b = f32[8]{0} parameter(0)
  %copy.8 = f32[8]{0} copy(%b)
  ROOT %while.10 = f32[8]{0} while(%copy.8), condition=%cond.5, body=%body.3, metadata={op_name="jit(solve)/while"}
}
"""


def test_instruction_scopes_place_a_profiles_ops_by_name():
    table = scopes.instruction_scopes(MODULE)
    assert table["dia_spmv.9"] == "solver.spmv"
    assert table["fusion.4"] == table["multiply.2"] == "solver.vector"
    assert table["copy.8"] is None and table["while.10"] is None
    # a TPU profile's XLA Ops event: the HLO text, no metadata
    event = ("%dia_spmv.9 = f32[8788,128]{1,0:T(8,128)S(1)} custom-call("
             "s32[27]{0:T(128)S(1)} %p), custom_call_target=\"tpu_custom_call\"")
    assert table[scopes.instruction(event)] == "solver.spmv"
    assert scopes.instruction("ROOT %fusion.4 = f32[8]{0} fusion()") == (
        "fusion.4")


def test_loop_ops_are_the_fusions_calls_and_dots_of_while_bodies():
    assert [scopes.instruction(line) for line in scopes.loop_ops(MODULE)] == [
        "dia_spmv.9", "fusion.4"]


# -- every op the compiled solves repeat carries a scope -------------------

GRID = 16


def _cg_dia():
    from repro.core import Format, convert, hpcg
    from repro.core.solvers import cg, operator

    prob = hpcg.generate_problem(GRID, GRID, GRID)
    A = convert(hpcg.to_coo(prob), Format.DIA)
    b = jax.numpy.asarray(hpcg.rhs_for_ones(prob))
    fn = jax.jit(lambda a, bb: cg(operator(a, backend="pallas"), bb,
                                  tol=1e-7, maxiter=50))
    return fn.lower(A, b).compile().as_text()


def _mgpcg_dist():
    """The MG-PCG cell's own path (bench.system), 4 levels."""
    from bench import system
    from repro.core.solvers import operator, pcg

    cell = small_cells.small_cell("hpcg104-mgpcg", GRID, 4,
                                  backend="auto")
    sysm = system.build(cell.config, jax.devices()[:1])
    b = system.place(sysm, np.ones(GRID ** 3, np.float32))
    fn = jax.jit(lambda a, bb, h: pcg(operator(a, sysm.mesh), bb, tol=0.0,
                                      maxiter=5, apply_M=h.apply_M()))
    return fn.lower(sysm.A, b, sysm.hier).compile().as_text()


def _mgpcg_local():
    from repro.core import Format, convert, hpcg
    from repro.core.solvers import operator, pcg
    from repro.mg import build_hierarchy

    prob = hpcg.generate_problem(GRID, GRID, GRID)
    hier = build_hierarchy(prob, nlevels=4, fmt=Format.ELL)
    A = convert(hpcg.to_coo(prob), Format.DIA)
    b = jax.numpy.asarray(hpcg.rhs_for_ones(prob))
    fn = jax.jit(lambda a, bb: pcg(operator(a), bb, tol=0.0, maxiter=5,
                                   apply_M=hier.apply_M()))
    return fn.lower(A, b).compile().as_text()


# The CPU backend wraps a lone broadcast of a constant in a fusion of its
# own, made after the program's ops and given no metadata; on a TPU the
# broadcast fuses into its user.
_CPU_CONSTANT_BROADCAST = re.compile(
    r"^%wrapped_broadcast[.\d]* = \S+ fusion\(%constant[\w.]*\), "
    r"kind=kLoop, calls=%wrapped_broadcast_computation[.\d]*$")


@pytest.mark.parametrize("build", [_cg_dia, _mgpcg_dist, _mgpcg_local],
                         ids=["cg-dia", "mgpcg-dist", "mgpcg-local"])
def test_every_op_the_solve_repeats_carries_a_scope(tmp_path, monkeypatch,
                                                    build):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "sel.json"))
    text = build()
    ops = scopes.loop_ops(text)
    assert ops
    table = scopes.instruction_scopes(text)
    assert all(table[scopes.instruction(line)] == scopes.scope_of(line)
               for line in ops)
    unscoped = [line for line in ops if scopes.scope_of(line) is None
                and not _CPU_CONSTANT_BROADCAST.match(line)]
    assert unscoped == []
    found = {scopes.scope_of(line) for line in ops} - {None}
    assert {"solver.spmv", "solver.vector"} <= found
    if build is _mgpcg_dist:
        assert {"mg.l0.smooth", "mg.l0.residual", "mg.l0.restrict",
                "mg.l0.prolong", "mg.l3.smooth"} <= found
    if build is _mgpcg_local:
        # XLA may fuse a level's residual into its restriction: the fusion
        # then carries the scope of its root, the restriction
        assert {"mg.l0.smooth", "mg.l0.restrict", "mg.l0.prolong",
                "mg.l3.smooth"} <= found
