import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
# 512 placeholder host devices back both production meshes (256-chip pod and
# 2x256 multi-pod). Never set this globally — tests/benches must see 1 device.

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For every (architecture x input-shape x mesh) cell:
  1. build the step fn (train_step / prefill / serve_step per shape kind),
  2. ``jax.jit(...).lower(**input_specs).compile()`` on the production mesh,
  3. print ``compiled.memory_analysis()``  (proves the cell fits HBM),
     print ``compiled.cost_analysis()``    (FLOPs / bytes for §Roofline),
  4. parse the compiled HLO for collective operand bytes,
  5. [--cost] compile depth-0 and depth-1(unrolled) variants: XLA counts a
     lax.scan body ONCE regardless of trip count (verified empirically), so
     the corrected cost is  c0 + L*(c1 - c0)  with no scans left inside c1.

Results land in results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm_1_6b \
      --shape train_4k --mesh single --cost
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.configs.shapes import SHAPES, skip_reason, token_input_specs
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models import sharding_ctx
from repro.optim.adamw import AdamW, AdamWState

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

COLLECTIVE_RE = re.compile(
    r"(\w+\[[^\]]*\](?:, \w+\[[^\]]*\])*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|c64)\[([\d,]*)\]")
DTYPE_BYTES = {"f64": 8, "s64": 8, "c64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand bytes of every collective op (per-device shapes)."""
    out = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?\S+ = (.*)", line)
        if not m:
            continue
        rhs = m.group(1)
        cm = re.match(
            r"(.*?)\s(all-gather|all-reduce|reduce-scatter|"
            r"all-to-all|collective-permute)(-start|-done)?\(", rhs)
        if not cm or cm.group(3) == "-done":
            continue
        op = cm.group(2)
        shapes = SHAPE_RE.findall(cm.group(1))
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES.get(dt, 4)
        out[op] = out.get(op, 0) + nbytes
        out["total"] = out.get("total", 0) + nbytes
    return out



def accum_steps(cfg, cell) -> int:
    """Gradient-accumulation factor (hillclimbed, EXPERIMENTS.md §Perf).

    More microbatches shrink per-microbatch activations (incl. flash
    custom_vjp residuals) enough that sequence parallelism — and its
    per-layer all-gathers, the dominant collective term — can be dropped
    for every arch < 60B. Capped so each microbatch still fills the
    data-parallel axis (batch/dp >= 1: no redundant compute)."""
    n = cfg.n_params()
    want = 16 if n >= 2.5e10 else (8 if n >= 1.2e10 else 4)
    cap = max(1, cell.global_batch // 16)  # dp axis = 16
    a = min(want, cap)
    while a > 1 and cell.global_batch % a:
        a //= 2
    return max(1, a)


def sp_axis(cfg) -> str:
    """Sequence-parallel axis for train cells: only the >=60B models still
    need SP for memory after microbatching; everywhere else SP's per-layer
    gathers dominated the collective roofline term (qwen: 7.7 TB/dev -> 
    ~30 GB/dev corrected when dropped; §Perf)."""
    return "model" if cfg.n_params() >= 6e10 else None


def grad_accum(model, params, batch, accum: int, unroll: bool,
               grad_pspecs=None):
    """Mean loss + grads over ``accum`` microbatches (lax.scan or unrolled).

    The scan keeps all per-microbatch activations (incl. custom_vjp flash
    residuals, which remat cannot discard) scoped to one microbatch.
    ``grad_pspecs`` pins per-microbatch grads to the param layout before
    accumulation (stops GSPMD materialising full unsharded dW tiles).
    """
    loss_fn = lambda p, b: model.loss(p, b, unroll=unroll)

    def constrain_g(g):
        if grad_pspecs is None:
            return g
        return jax.tree.map(jax.lax.with_sharding_constraint, g, grad_pspecs)

    if accum <= 1:
        l, g = jax.value_and_grad(loss_fn)(params, batch)
        return l, constrain_g(g)
    micro = jax.tree.map(
        lambda a: a.reshape((accum, a.shape[0] // accum) + a.shape[1:]), batch)

    def body(acc, mb):
        l, g = jax.value_and_grad(loss_fn)(params, mb)
        g = constrain_g(g)
        return (acc[0] + l / accum,
                jax.tree.map(lambda x, y: x + y / accum, acc[1], g)), None

    zeros = (jnp.zeros((), jnp.float32),
             jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
    if unroll:
        acc = zeros
        for i in range(accum):
            acc, _ = body(acc, jax.tree.map(lambda a: a[i], micro))
    else:
        acc, _ = jax.lax.scan(body, zeros, micro)
    return acc


def build_step(model, cfg, shape_name, mesh):
    """Returns (jitted_fn, kwargs_of_abstract_args)."""
    cell = SHAPES[shape_name]
    if cell.kind == "train":
        opt = AdamW()
        # mixed precision: bf16 params + f32 Adam moments for the largest
        # models (halves param args + weight-sized backward transients)
        pdtype = jnp.bfloat16 if cfg.n_params() >= 6e10 else jnp.float32
        specs = model.specs(pdtype)
        p_sh = shd.param_shardings(specs, mesh, shd.TRAIN_RULES)
        opt_sh = AdamWState(NamedSharding(mesh, P()), p_sh, p_sh)
        batch_specs = token_input_specs(cfg, cell, with_labels=True)
        b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            shd.batch_pspec(mesh, batch_specs),
                            is_leaf=lambda x: isinstance(x, P))

        accum = accum_steps(cfg, cell)
        g_ps = shd.param_pspecs(specs, mesh, shd.TRAIN_RULES)

        def train_step(params, opt_state, batch):
            loss, grads = grad_accum(model, params, batch, accum, unroll=False,
                                     grad_pspecs=g_ps)
            new_p, new_o = opt.update(grads, opt_state, params)
            return new_p, new_o, loss

        fn = jax.jit(train_step,
                     in_shardings=(p_sh, opt_sh, b_sh),
                     out_shardings=(p_sh, opt_sh, NamedSharding(mesh, P())),
                     donate_argnums=(0, 1))
        args = (model.abstract_params(pdtype),
                AdamWState(jax.ShapeDtypeStruct((), jnp.int32),
                           model.abstract_params(), model.abstract_params()),
                batch_specs)
        return fn, args

    if cell.kind == "prefill":
        specs = model.specs(jnp.bfloat16)
        rules = dict(shd.DECODE_RULES,
                     embed="data" if cfg.n_params() > 1e10 else None)
        p_sh = shd.param_shardings(specs, mesh, rules)
        batch_specs = token_input_specs(cfg, cell, with_labels=False)
        b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            shd.batch_pspec(mesh, batch_specs),
                            is_leaf=lambda x: isinstance(x, P))
        fn = jax.jit(lambda params, batch: model.prefill(params, batch),
                     in_shardings=(p_sh, b_sh))
        return fn, (model.abstract_params(jnp.bfloat16), batch_specs)

    # decode. Models >10B cannot replicate bf16 params over 'data'
    # (command-r: 13 GiB/dev) -> weight-gathered decode (2-D sharded params,
    # per-layer all-gather amortised over the 128-sequence batch).
    specs = model.specs(jnp.bfloat16)
    rules = dict(shd.DECODE_RULES,
                 embed="data" if cfg.n_params() > 1e10 else None)
    p_sh = shd.param_shardings(specs, mesh, rules)
    chips = int(np.prod(list(mesh.shape.values())))
    bf16_cache = (2 * 2 * cfg.n_layers * cell.global_batch * cell.seq_len
                  * cfg.n_kv * cfg.hd) if cfg.n_kv else 0
    kv_quant = cfg.family in ("dense", "moe", "vlm") and \
        bf16_cache / chips > 8 * 2 ** 30  # int8 cache when bf16 won't fit
    cache_specs = model.cache_specs(cell.global_batch, cell.seq_len,
                                    kv_quant=kv_quant)
    c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        shd.cache_pspec(mesh, cache_specs, cfg),
                        is_leaf=lambda x: isinstance(x, P))
    tok = jax.ShapeDtypeStruct((cell.global_batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((cell.global_batch,), jnp.int32)
    rep = NamedSharding(mesh, P())
    fn = jax.jit(lambda params, cache, tokens, pos:
                 model.decode_step(params, cache, tokens, pos),
                 in_shardings=(p_sh, c_sh, rep, rep),
                 out_shardings=(rep, c_sh),
                 donate_argnums=(1,))
    return fn, (model.abstract_params(jnp.bfloat16), cache_specs, tok, pos)


def _reduced_cfg(cfg, n_layers):
    """Depth-reduced config for the c0/c1 cost compiles."""
    return dataclasses.replace(cfg, n_layers=n_layers)


def compile_cell(arch, shape_name, multi_pod, *, with_cost=False,
                 unroll_for_cost=True, save=True, verbose=True,
                 cfg_override=None, tag=""):
    cfg = cfg_override or get_config(arch)
    reason = skip_reason(cfg, shape_name)
    mesh_name = "multipod" if multi_pod else "pod"
    cellname = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
           "n_params": None, "skip": reason}
    if reason:
        if verbose:
            print(f"[{cellname}] SKIP: {reason}")
        if save:
            _save(cellname, rec)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    rec["n_params"] = cfg.n_params()
    rec["n_active_params"] = cfg.n_active_params()
    cell = SHAPES[shape_name]
    rec["tokens"] = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    rec["chips"] = int(np.prod(list(mesh.shape.values())))

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    cellk = SHAPES[shape_name].kind
    sharding_ctx.set_policy(dp=dp if len(dp) > 1 else dp[0], tp="model",
                            sp=sp_axis(cfg) if cellk == "train" else None)
    t0 = time.perf_counter()
    with mesh:
        fn, args = build_step(model, cfg, shape_name, mesh)
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["memory"] = {
        k: int(getattr(mem, k))
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)}
    per_dev = (rec["memory"].get("temp_size_in_bytes", 0)
               + rec["memory"].get("argument_size_in_bytes", 0))
    rec["bytes_per_device"] = per_dev
    rec["cost_reported"] = {k: float(cost.get(k, 0.0))
                            for k in ("flops", "bytes accessed")}
    hlo = compiled.as_text()
    rec["collectives_reported"] = parse_collective_bytes(hlo)

    if verbose:
        print(f"[{cellname}] compiled in {t_compile:.0f}s | "
              f"per-device {per_dev / 2**30:.2f} GiB | "
              f"reported GFLOPs {rec['cost_reported']['flops'] / 1e9:.1f} | "
              f"collective MB {rec['collectives_reported'].get('total', 0) / 2**20:.1f}")
        print(f"  memory_analysis: {rec['memory']}")

    if with_cost:
        rec["cost_corrected"] = corrected_costs(
            arch, shape_name, cfg, mesh, unroll=unroll_for_cost,
            verbose=verbose)

    if save:
        _save(cellname, rec)
    return rec


def corrected_costs(arch, shape_name, cfg, mesh, *, unroll=True, verbose=True):
    """c0 + L*(c1 - c0): exact scan-trip-count-corrected cost terms."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    cellk = SHAPES[shape_name].kind
    sharding_ctx.set_policy(dp=dp if len(dp) > 1 else dp[0], tp="model",
                            sp=sp_axis(cfg) if cellk == "train" else None)
    unit = cfg.attn_every if cfg.family == "hybrid" else 1
    n_units = cfg.n_layers // unit
    out = {}
    costs = {}
    for depth_units, key in ((0, "c0"), (1, "c1")):
        dcfg = _reduced_cfg(cfg, depth_units * unit)
        dmodel = build_model(dcfg)
        cell = SHAPES[shape_name]
        with mesh:
            if cell.kind == "train":
                fn, args = _train_step_unrolled(dmodel, dcfg, cell, mesh, unroll)
            else:
                fn, args = build_step(dmodel, dcfg, shape_name, mesh)
            comp = fn.lower(*args).compile()
        cost = comp.cost_analysis()
        coll = parse_collective_bytes(comp.as_text())
        costs[key] = {"flops": float(cost.get("flops", 0.0)),
                      "bytes": float(cost.get("bytes accessed", 0.0)),
                      "coll": float(coll.get("total", 0.0))}
    for term, key in (("flops", "flops"), ("bytes", "bytes"), ("coll", "coll")):
        c0, c1 = costs["c0"][key], costs["c1"][key]
        out[term] = c0 + n_units * max(0.0, c1 - c0)
    out["c0"] = costs["c0"]
    out["c1"] = costs["c1"]
    out["n_units"] = n_units
    if verbose:
        print(f"  corrected: GFLOPs {out['flops'] / 1e9:.1f} | "
              f"GiB accessed {out['bytes'] / 2**30:.1f} | "
              f"collective GiB {out['coll'] / 2**30:.2f} (x{n_units} units)")
    return out


def _train_step_unrolled(model, cfg, cell, mesh, unroll):
    """Train step with python-loop layers + unrolled attention (cost-exact)."""
    opt = AdamW()
    pdtype0 = jnp.bfloat16 if cfg.n_params() >= 6e10 else jnp.float32
    specs = model.specs(pdtype0)
    p_sh = shd.param_shardings(specs, mesh, shd.TRAIN_RULES)
    opt_sh = AdamWState(NamedSharding(mesh, P()), p_sh, p_sh)
    batch_specs = token_input_specs(cfg, cell, with_labels=True)
    b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        shd.batch_pspec(mesh, batch_specs),
                        is_leaf=lambda x: isinstance(x, P))

    accum = accum_steps(cfg, cell)
    g_ps = shd.param_pspecs(specs, mesh, shd.TRAIN_RULES)
    pdtype = jnp.bfloat16 if cfg.n_params() >= 6e10 else jnp.float32

    def train_step(params, opt_state, batch):
        loss, grads = grad_accum(model, params, batch, accum, unroll=unroll,
                                 grad_pspecs=g_ps)
        new_p, new_o = opt.update(grads, opt_state, params)
        return new_p, new_o, loss

    fn = jax.jit(train_step, in_shardings=(p_sh, opt_sh, b_sh),
                 out_shardings=(p_sh, opt_sh, NamedSharding(mesh, P())))
    args = (model.abstract_params(pdtype0),
            AdamWState(jax.ShapeDtypeStruct((), jnp.int32),
                       model.abstract_params(), model.abstract_params()),
            batch_specs)
    return fn, args


def _save(cellname, rec):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, cellname + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="")
    p.add_argument("--shape", default="", choices=[""] + list(SHAPES))
    p.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--cost", action="store_true",
                   help="also run the c0/c1 corrected-cost compiles")
    p.add_argument("--continue-on-error", action="store_true")
    args = p.parse_args(argv)

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    compile_cell(arch, shape, mp, with_cost=args.cost and not mp)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"[{arch}__{shape}__{'multipod' if mp else 'pod'}] "
                          f"FAILED: {e}")
                    traceback.print_exc()
                    if not args.continue_on_error:
                        raise
    print(f"\ndone. {len(failures)} failures.")
    for f in failures:
        print("  FAIL:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
