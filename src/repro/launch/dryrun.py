import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * 16x16 single-pod mesh (256 chips)  — roofline source
  * 2x16x16 multi-pod mesh (512 chips) — proves the 'pod' axis shards
For each cell we lower the right step (train_step / prefill / decode),
compile, and record memory_analysis, cost_analysis and the collective
schedule into a JSON artifact consumed by benchmarks/roofline.py and
EXPERIMENTS.md.

NOTE: the XLA_FLAGS line above MUST run before any jax import — jax locks
the device count at first init.  Do not set it globally.
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs import REGISTRY
from ..configs.base import ModelConfig, ShapeCell, cells_for
from ..dist.hlo_analysis import (collective_stats, dominant_term,
                                 roofline_terms)
from ..dist.sharding import (batch_pspecs, cache_pspecs, padded_shape,
                             param_pspecs, unpad_leaf, use_mesh)
from ..models import moe as moe_mod
from ..models.api import build
from ..optim.optimizers import adamw
from ..train.state import TrainState
from ..train.step import (freeze_mask, microbatched_value_and_grad,
                          quant_reg_loss)
from .cache import enable_compile_cache
from .mesh import make_production_mesh


def _shardings(mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda ps: jax.sharding.NamedSharding(mesh, ps), pspec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def _pad_abstract(tree, mesh):
    """Padded-sharding boundary for abstract lowering: fit each leaf's
    (padded-mode) spec, grow the ShapeDtypeStruct to the padded shape so
    ``in_shardings`` stay divisible, and remember the true shapes for the
    in-graph unpad.  Returns (padded_tree, spec_tree, true_shapes)."""
    from jax.sharding import PartitionSpec as P
    with use_mesh(mesh):
        specs = param_pspecs(tree)
    flat, treedef = jax.tree_util.tree_flatten(tree)
    sflat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    padded = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(padded_shape(s, x.shape, mesh), x.dtype)
        for x, s in zip(flat, sflat)])
    return padded, specs, [tuple(x.shape) for x in flat]


def _unpadding(fn, true_shapes):
    """Wrap a step fn so its first arg (params) is sliced back to the true
    shapes before the model sees it — the consumer mask of padded
    placement, identical to ``ServeEngine._unpad_params``."""
    def wrapped(params, *args, **kwargs):
        flat, treedef = jax.tree_util.tree_flatten(params)
        params = jax.tree_util.tree_unflatten(
            treedef, [unpad_leaf(x, s)
                      for x, s in zip(flat, true_shapes)])
        return fn(params, *args, **kwargs)
    return wrapped


def _cost_dict(compiled) -> Dict[str, float]:
    """compiled.cost_analysis() returns a dict on new jax, [dict] on old."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def _model_flops_estimate(cfg: ModelConfig, cell: ShapeCell) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*D (fwd-only)."""
    d, L = cfg.d_model, cfg.n_layers
    dh = cfg.head_dim
    attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads + cfg.n_heads) * dh
    if cfg.n_experts:
        ff_active = 3 * d * cfg.d_ff * (cfg.top_k + cfg.n_shared_experts)
    elif cfg.family == "ssm":
        ff_active = 5 * d * d + 2 * d * cfg.d_ff     # time mix + channel mix
        attn = 0
    elif cfg.family == "hybrid":
        di = cfg.ssm_expand * d
        ff_active = 2 * d * di + 2 * d * cfg.ssm_state + d * di
        attn = 0
    else:
        mult = 3 if cfg.mlp_kind == "swiglu" else 2
        ff_active = mult * d * cfg.d_ff
    n_active = L * (attn + ff_active)
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        blocks = cfg.n_layers // cfg.hybrid_attn_every
        n_active += blocks * (
            2 * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * dh   # 2d-in qkv
            + cfg.n_heads * dh * d                            # wo
            + 3 * d * cfg.d_ff)                               # shared mlp
    if cfg.is_encdec and cell.kind != "decode":
        n_active *= 2            # encoder stack of similar size
    n_active += 2 * cfg.vocab * d    # embed + lm head
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    factor = 6.0 if cell.kind == "train" else 2.0
    return factor * n_active * tokens


def _lower_once(cfg: ModelConfig, cell: ShapeCell, mesh, microbatches: int,
                deploy_bits: int = 0):
    """Build + lower + compile one step; returns (compiled, timings).

    ``deploy_bits`` > 0 lowers decode/prefill against packed integer
    serving weights (EXPERIMENTS.md §Perf beyond-paper path)."""
    moe_mod.GROUPED_IMPL["impl"] = "capacity"   # at-scale MoE path
    api = build(cfg)
    t0 = time.time()
    with use_mesh(mesh):
        aparams = api.abstract_params()
        if deploy_bits and cell.kind != "train":
            from ..serve.deploy import to_serving_params
            aparams = jax.eval_shape(
                lambda p: to_serving_params(p, deploy_bits), aparams)
        aparams_p, p_specs, p_shapes = _pad_abstract(aparams, mesh)
        p_sh = _shardings(mesh, p_specs)
        if cell.kind == "train":
            opt = adamw()
            astate = jax.eval_shape(
                lambda p: TrainState.create(p, opt), aparams)
            # the train state is donated and round-trips through the jit:
            # it cannot carry placement padding, so fit with the drop rule
            s_sh = _shardings(mesh, param_pspecs(astate, pad=False))
            batch = api.train_batch_spec(cell)
            b_sh = _shardings(mesh, batch_pspecs(batch))

            def train_step(state, b):
                def total(params, bb):
                    loss, metrics = api.loss(params, bb)
                    return loss + quant_reg_loss(params, state.alpha), metrics
                vg = microbatched_value_and_grad(total, microbatches)
                (loss, _), grads = vg(state.params, b)
                grads = freeze_mask(grads)
                new_p, new_o = opt.update(grads, state.opt_state,
                                          state.params, 1e-3)
                return TrainState(step=state.step + 1, params=new_p,
                                  opt_state=new_o, alpha=state.alpha), loss

            jitted = jax.jit(train_step, in_shardings=(s_sh, b_sh),
                             out_shardings=(s_sh, None), donate_argnums=(0,))
            lowered = jitted.lower(astate, batch)
        elif cell.kind == "prefill":
            batch = api.train_batch_spec(cell)
            batch.pop("labels", None)
            b_sh = _shardings(mesh, batch_pspecs(batch))
            jitted = jax.jit(_unpadding(api.prefill, p_shapes),
                             in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(aparams_p, batch)
        else:  # decode
            state_spec = api.decode_state_spec(cell)
            # donated decode state round-trips: fit with the drop rule
            c_sh = _shardings(mesh, cache_pspecs(state_spec,
                                                 cell.global_batch,
                                                 pad=False))
            tok = api.decode_token_spec(cell)
            t_sh = _shardings(mesh, batch_pspecs({"t": tok}))["t"]
            idx = jax.ShapeDtypeStruct((), jnp.int32)
            i_sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            jitted = jax.jit(_unpadding(api.decode_step, p_shapes),
                             in_shardings=(p_sh, t_sh, c_sh, i_sh),
                             out_shardings=(None, c_sh), donate_argnums=(2,))
            lowered = jitted.lower(aparams_p, tok, state_spec, idx)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile


def _calibrated_costs(cfg: ModelConfig, cell: ShapeCell, mesh,
                      microbatches: int,
                      deploy_bits: int = 0) -> Dict[str, float]:
    """Exact per-device FLOP/byte/collective totals via unrolled smalls.

    XLA cost_analysis counts each scan body ONCE, so the scanned lowering
    undercounts by the trip counts.  We lower tiny UNROLLED configs
    (scan_layers=False, single microbatch, un-chunked SSM, dense attention)
    at 1 / 2 layers (hybrids: one attn period + one extra), solve the
    linear model cost(L) = base + L * per_layer, and scale to the full
    depth and microbatch count.  This matches what the scanned program
    executes because every scan body is shape-identical across trips.
    """
    from ..models import attention as attn_mod

    n_mb = microbatches if cell.kind == "train" else 1
    small_cell = dataclasses.replace(
        cell, global_batch=max(1, cell.global_batch // max(n_mb, 1)))

    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        p = cfg.hybrid_attn_every
        points = [p, p + 1, 2 * p]
    else:
        points = [1, 2]

    results = []
    old_opts = dict(attn_mod.ATTN_OPTS)
    attn_mod.ATTN_OPTS["min_elems"] = 1 << 62     # force dense (no scan)
    try:
        for L in points:
            over = dict(n_layers=L, scan_layers=False,
                        ssm_chunk=1 << 30, rwkv_chunk=1 << 30)
            if cfg.is_encdec:
                over["enc_layers"] = L
            ccfg = dataclasses.replace(cfg, **over)
            compiled, _, _ = _lower_once(ccfg, small_cell, mesh,
                                         microbatches=1,
                                         deploy_bits=deploy_bits)
            ca = _cost_dict(compiled)
            colls = collective_stats(compiled.as_text())
            results.append(dict(flops=float(ca.get("flops", 0.0)),
                                bytes=float(ca.get("bytes accessed", 0.0)),
                                coll=colls.total_bytes))
    finally:
        attn_mod.ATTN_OPTS.update(old_opts)

    def solve(key):
        if len(points) == 2:
            per_layer = results[1][key] - results[0][key]
            base = results[0][key] - points[0] * per_layer
            total = base + cfg.n_layers * per_layer
        else:                      # hybrid: f(p), f(p+1), f(2p)
            f_p, f_p1, f_2p = (r[key] for r in results)
            mamba = f_p1 - f_p
            period = f_2p - f_p            # p mamba + 1 shared block
            base = f_p - period
            n_super = cfg.n_layers // cfg.hybrid_attn_every
            tail = cfg.n_layers - n_super * cfg.hybrid_attn_every
            total = base + n_super * period + tail * mamba
        return max(total, 0.0) * n_mb

    return dict(flops=solve("flops"), bytes=solve("bytes"),
                coll=solve("coll"))


def lower_cell(cfg: ModelConfig, cell: ShapeCell, mesh,
               include_text: bool = False, microbatches: int = 0,
               calibrate: bool = True,
               deploy_bits: int = 0) -> Dict[str, Any]:
    if microbatches == 0 and cell.kind == "train":
        # default: ~2 sequences per device per microbatch
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dp *= mesh.shape[a]
        per_dev = max(1, cell.global_batch // dp)
        microbatches = max(1, per_dev)       # ~1 sequence/device/microbatch
        while cell.global_batch % microbatches:
            microbatches -= 1

    compiled, t_lower, t_compile = _lower_once(cfg, cell, mesh, microbatches,
                                               deploy_bits=deploy_bits)
    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled)
    txt = compiled.as_text()
    colls = collective_stats(txt)
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))

    if calibrate:
        cal = _calibrated_costs(cfg, cell, mesh, microbatches, deploy_bits)
        flops, bytes_acc, coll_bytes = cal["flops"], cal["bytes"], cal["coll"]
    else:
        flops, bytes_acc, coll_bytes = raw_flops, raw_bytes, colls.total_bytes

    terms = roofline_terms(flops, bytes_acc, coll_bytes)
    model_flops = _model_flops_estimate(cfg, cell)
    chips = mesh.devices.size
    rec = dict(
        arch=cfg.name, cell=cell.name, kind=cell.kind,
        mesh=list(mesh.shape.values()), chips=chips,
        seq_len=cell.seq_len, global_batch=cell.global_batch,
        microbatches=microbatches,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        per_device=dict(
            flops=flops, bytes_accessed=bytes_acc,
            collective_bytes=coll_bytes,
            raw_scan_flops=raw_flops, raw_scan_bytes=raw_bytes,
            raw_scan_collective_bytes=colls.total_bytes,
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            peak_hbm_gib=round((mem.argument_size_in_bytes
                                + mem.temp_size_in_bytes
                                + mem.output_size_in_bytes) / 2**30, 3),
        ),
        collectives=dict(counts=colls.counts, bytes=colls.bytes_by_op),
        roofline=terms,
        dominant=dominant_term(terms),
        model_flops_global=model_flops,
        hlo_flops_global=flops * chips,
        useful_flops_frac=(model_flops / (flops * chips)
                           if flops else 0.0),
    )
    if include_text:
        rec["hlo_text"] = txt
    return rec


def run_cells(arch_names, cell_names, multi_pod: bool, out_dir: str,
              skip_existing: bool = True) -> None:
    mesh = make_production_mesh(multi_pod=multi_pod)
    tag = "multipod" if multi_pod else "singlepod"
    os.makedirs(out_dir, exist_ok=True)
    for name in arch_names:
        cfg = REGISTRY[name]
        for cell in cells_for(cfg):
            if cell_names and cell.name not in cell_names:
                continue
            out = os.path.join(out_dir, f"{tag}__{name}__{cell.name}.json")
            if skip_existing and os.path.exists(out):
                print(f"[skip] {out}")
                continue
            print(f"[dryrun] {tag} {name} {cell.name} ...", flush=True)
            try:
                # roofline calibration is single-pod only (assignment);
                # the multi-pod pass proves the 'pod' axis shards.
                rec = lower_cell(cfg, cell, mesh, calibrate=not multi_pod)
                with open(out, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"  ok: dominant={rec['dominant']} "
                      f"hbm/dev={rec['per_device']['peak_hbm_gib']}GiB "
                      f"compile={rec['compile_s']}s", flush=True)
            except Exception as e:
                err = os.path.join(out_dir,
                                   f"{tag}__{name}__{cell.name}.ERROR")
                with open(err, "w") as f:
                    f.write(traceback.format_exc())
                print(f"  FAIL: {type(e).__name__}: {e}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--cell", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    archs = sorted(REGISTRY) if args.arch == "all" else args.arch.split(",")
    cells = None if args.cell == "all" else args.cell.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for mp in meshes:
        run_cells(archs, cells, mp, args.out, skip_existing=not args.force)


if __name__ == "__main__":
    main()
