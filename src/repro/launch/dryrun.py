import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the very first lines, before any other import: jax locks the
#   device count on first init. Only the dry-run sees 512 placeholder
#   devices; smoke tests and benches see 1 (no global XLA_FLAGS).

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against ShapeDtypeStruct stand-ins (zero allocation), prove the
sharding config is coherent, and extract the roofline inputs:

  * memory_analysis()      — per-device bytes (proves it fits)
  * cost_analysis()        — per-device FLOPs / bytes accessed
  * compiled.as_text()     — post-SPMD collective schedule (parsed)

Costs of scanned layer stacks are recovered with two-point unrolled fits
(see repro.roofline). Results cache incrementally as JSON under
results/dryrun/ so the 40-cell x 2-mesh matrix is resumable.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k --multi-pod
"""
import argparse
import dataclasses
import json
import math
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim.adamw import AdamW
from repro.parallel.sharding import (batch_partition_specs, dp_axes,
                                     param_partition_specs)
from repro.roofline.analysis import (HW_V5E, collective_bytes_from_hlo,
                                     model_flops, roofline_terms, two_point_fit)

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "results", "dryrun")


# ---------------------------------------------------------------------------
# Step builders (train / prefill / decode) parameterized by arch + options.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DryrunOptions:
    remat: str = "full"
    shard_acts: bool = True
    include_optimizer: bool = True
    unroll_layers: int = 0       # >0: python-unrolled groups (cost fits)
    microbatches: int = 1        # grad-accumulation splits (memory knob;
    #                              ONE deferred gradient reduction per step)
    cost_fit: bool = True        # run the two-point cost lowers (roofline
    #                              terms are single-pod only per the brief;
    #                              multi-pod cells skip them)


# per-(arch, shape) microbatch defaults: the large models need gradient
# accumulation to fit 16 GB/chip at global batch 256 x 4k (the production
# config a real run would use; cost lowers always use 1 — total FLOPs are
# invariant to the split).
MICROBATCH_DEFAULTS = {
    ("mixtral-8x7b", "train_4k"): 8,
    ("llama3-8b", "train_4k"): 4,
    ("stablelm-12b", "train_4k"): 4,
    ("pixtral-12b", "train_4k"): 4,
    ("qwen1.5-4b", "train_4k"): 4,
    ("whisper-large-v3", "train_4k"): 8,
    ("granite-moe-1b-a400m", "train_4k"): 4,
    ("hymba-1.5b", "train_4k"): 8,
    ("tinyllama-1.1b", "train_4k"): 2,
    ("xlstm-350m", "train_4k"): 2,
}


def build_step(arch: ArchConfig, shape: ShapeConfig, mesh,
               opts: DryrunOptions):
    """Returns (fn, example_args_specs, in_shardings)."""
    specs = input_specs(arch, shape)
    pspecs_tree = lm.param_specs(arch)
    ppart = param_partition_specs(pspecs_tree, mesh)
    bpart = batch_partition_specs(specs, mesh, kind=shape.kind)
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "train":
        opt = AdamW(learning_rate=1e-4)
        ostate_tree = jax.eval_shape(opt.init, pspecs_tree)
        opart = opt.state_specs(ppart)

        def train_step(params, opt_state, batch):
            def loss_fn(p, b):
                return lm.train_loss(
                    p, arch, b, remat=opts.remat,
                    shard_acts=opts.shard_acts,
                    unroll_layers=opts.unroll_layers)
            mb = opts.microbatches
            if mb == 1:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            else:
                micro = jax.tree.map(
                    lambda x: x.reshape(
                        (mb, x.shape[0] // mb) + x.shape[1:]), batch)

                def acc(carry, b):
                    tl, tg = carry
                    l, g = jax.value_and_grad(loss_fn)(params, b)
                    return (tl + l, jax.tree.map(jnp.add, tg, g)), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (loss, grads), _ = jax.lax.scan(
                    acc, (jnp.float32(0), zeros), micro)
                loss = loss / mb
                grads = jax.tree.map(lambda g: g / mb, grads)
            if opts.include_optimizer:
                params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        args = (pspecs_tree, ostate_tree, specs)
        in_sh = (ns(ppart), ns(opart), ns(bpart))
        out_sh = (ns(ppart), ns(opart), None)
        # donate params+opt: in-place update, halves resident state.
        return train_step, args, in_sh, out_sh, (0, 1)

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            extras = {k: v for k, v in batch.items() if k != "tokens"}
            logits, _, _ = lm.forward(
                params, arch, batch["tokens"], extras,
                shard_acts=opts.shard_acts,
                unroll_layers=opts.unroll_layers)
            return logits[:, -1:]

        args = (pspecs_tree, specs)
        return prefill_step, args, (ns(ppart), ns(bpart)), None, ()

    def serve_step(params, batch):
        return lm.decode_step(params, arch, batch,
                              unroll_layers=opts.unroll_layers)

    args = (pspecs_tree, specs)
    # donate the batch (the KV cache updates in place).
    return serve_step, args, (ns(ppart), ns(bpart)), None, (1,)


def _attention_flops(arch: ArchConfig, shape: ShapeConfig) -> float:
    """Analytic attention FLOPs per step: 4*B*Hq*Dh*sum_attended per layer
    forward (QK^T + PV), x3 for train (bwd). Causal full attention sums
    ~S^2/2 pairs; sliding window ~S*window."""
    B, S = shape.global_batch, shape.seq_len
    Hq, Dh = arch.n_heads, arch.head_dim_
    n_attn_layers = sum(
        1 for i in range(arch.n_layers)
        if arch.block_at(i) in ("attn_mlp", "swa_mlp", "moe", "hybrid"))
    if shape.kind == "decode":
        attended = min(S, arch.window) if arch.window else S
        per_layer = 4.0 * B * Hq * Dh * attended
        return per_layer * n_attn_layers
    if arch.window:
        pairs = S * min(arch.window, S)
    else:
        pairs = S * S / 2.0
    per_layer = 4.0 * B * Hq * Dh * pairs
    mult = 3.0 if shape.kind == "train" else 1.0
    total = per_layer * n_attn_layers * mult
    if arch.is_encdec:
        enc_pairs = arch.encoder_seq ** 2
        total += 4.0 * B * Hq * Dh * enc_pairs * arch.encoder_layers * mult
        total += 4.0 * B * Hq * Dh * S * arch.encoder_seq \
            * arch.n_layers * mult        # cross-attention
    return total


def _reduced(arch: ArchConfig, groups: int) -> ArchConfig:
    period = len(arch.block_pattern)
    kw = {"n_layers": period * groups}
    if arch.encoder_layers:
        kw["encoder_layers"] = max(1, groups)
    return dataclasses.replace(arch, **kw)


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             opts: Optional[DryrunOptions] = None,
             mesh=None, verbose: bool = True) -> Dict:
    opts = opts or DryrunOptions()
    if opts.microbatches == 1:
        mb = MICROBATCH_DEFAULTS.get((arch_name, shape_name), 1)
        if mb != 1:
            opts = dataclasses.replace(opts, microbatches=mb)
    arch = get_config(arch_name)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result: Dict = {"arch": arch_name, "shape": shape_name,
                    "mesh": mesh_name, "status": "ok",
                    "opts": dataclasses.asdict(opts)}
    if shape_name in arch.skip_shapes:
        result["status"] = "skip"
        result["reason"] = ("pure full-attention arch: long_500k needs "
                            "sub-quadratic attention (DESIGN.md)")
        return result

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.devices.shape)

    t0 = time.time()
    try:
        # ---- full-depth compile: proves sharding + memory fit ----------
        # jax.set_mesh context: the in-model with_sharding_constraint hints
        # (SP activations, EP buffers, split-KV) need a mesh during trace.
        fn, args, in_sh, out_sh, donate = build_step(arch, shape, mesh,
                                                     opts)
        jit_kw = {"in_shardings": in_sh, "donate_argnums": donate}
        if out_sh is not None:
            jit_kw["out_shardings"] = out_sh
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, **jit_kw).lower(*args)
            compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t0, 1)
        ma = compiled.memory_analysis()
        result["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "total_bytes": int(ma.argument_size_in_bytes
                               + ma.temp_size_in_bytes),
            "fits_hbm": bool(ma.argument_size_in_bytes
                             + ma.temp_size_in_bytes < HW_V5E.hbm_bytes),
        }
        hlo_full = compiled.as_text()
        coll_full = collective_bytes_from_hlo(hlo_full)
        result["collectives_static"] = {
            k: v for k, v in coll_full.items() if k != "counts"}
        result["collective_counts"] = coll_full["counts"]

        # ---- two-point unrolled fits for scan-aware costs ----------------
        if not opts.cost_fit:
            result["wall_s"] = round(time.time() - t0, 1)
            if verbose:
                _print_cell(result)
            return result
        period = len(arch.block_pattern)
        n_groups = arch.n_layers // period
        fit = {}
        for key in ("flops", "bytes", "coll"):
            fit[key] = {}
        pts = {}
        for g in (1, 2):
            red = _reduced(arch, g)
            opts_g = dataclasses.replace(opts, unroll_layers=g,
                                         microbatches=1)
            fng, argsg, in_shg, out_shg, dong = build_step(red, shape,
                                                           mesh, opts_g)
            jkw = {"in_shardings": in_shg, "donate_argnums": dong}
            if out_shg is not None:
                jkw["out_shardings"] = out_shg
            from repro.kernels.flash_attention.ops import cost_exact_mode
            with jax.set_mesh(mesh), cost_exact_mode():
                cg = jax.jit(fng, **jkw).lower(*argsg).compile()
            ca = cg.cost_analysis()
            coll = collective_bytes_from_hlo(cg.as_text())
            pts[g] = {"flops": float(ca.get("flops", 0.0)),
                      "bytes": float(ca.get("bytes accessed", 0.0)),
                      "coll": float(coll["total"])}
        flops_dev = two_point_fit(pts[1]["flops"], pts[2]["flops"], 1, 2,
                                  n_groups)
        bytes_dev = two_point_fit(pts[1]["bytes"], pts[2]["bytes"], 1, 2,
                                  n_groups)
        coll_dev = two_point_fit(pts[1]["coll"], pts[2]["coll"], 1, 2,
                                 n_groups)
        result["cost_fit_points"] = pts
        result["per_device"] = {"flops_macs": flops_dev,
                                "hbm_bytes": bytes_dev,
                                "collective_bytes": coll_dev}

        # ---- roofline terms ---------------------------------------------
        terms = roofline_terms(flops_dev, bytes_dev, coll_dev, HW_V5E)
        result["roofline"] = terms
        n_active = arch.active_param_count() if arch.n_experts \
            else lm.param_count(arch)
        # use spec-derived count for non-MoE; MoE active from analytic.
        if arch.n_experts:
            total = lm.param_count(arch)
            analytic_total = arch.param_count()
            # rescale analytic active count by the spec/analytic ratio.
            n_active = int(arch.active_param_count()
                           * total / max(analytic_total, 1))
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(n_active, shape.kind, tokens, shape.global_batch)
        # cost_analysis reports per-device FLOPs in the 2*M*N*K convention
        # (verified in tests/test_roofline.py) -> global = x n_chips.
        hlo_flops_global = flops_dev * n_chips
        result["model_flops"] = mf
        result["useful_ratio"] = mf / hlo_flops_global \
            if hlo_flops_global else 0.0
        # 6*N*D excludes attention score/value matmuls; add the analytic
        # attention term so quadratic-attention cells are judged fairly.
        af = _attention_flops(arch, shape)
        result["attention_flops"] = af
        result["useful_ratio_attn"] = (mf + af) / hlo_flops_global \
            if hlo_flops_global else 0.0
        result["n_chips"] = n_chips
        result["wall_s"] = round(time.time() - t0, 1)
    except Exception as e:
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-3000:]
    if verbose:
        _print_cell(result)
    return result


def _print_cell(r: Dict):
    if r["status"] == "skip":
        print(f"[SKIP] {r['arch']:22s} {r['shape']:12s} {r['mesh']:10s} "
              f"({r['reason'][:60]})")
        return
    if r["status"] == "error":
        print(f"[FAIL] {r['arch']:22s} {r['shape']:12s} {r['mesh']:10s} "
              f"{r['error'][:120]}")
        return
    m = r["memory"]
    if "roofline" not in r:
        print(f"[ OK ] {r['arch']:22s} {r['shape']:12s} {r['mesh']:10s} "
              f"mem/dev={m['total_bytes'] / 1e9:6.2f}GB "
              f"fits={m['fits_hbm']} (compile-only pass) "
              f"({r['wall_s']}s)")
        return
    t = r["roofline"]
    print(f"[ OK ] {r['arch']:22s} {r['shape']:12s} {r['mesh']:10s} "
          f"mem/dev={m['total_bytes'] / 1e9:6.2f}GB "
          f"fits={m['fits_hbm']} "
          f"C={t['compute_s'] * 1e3:8.2f}ms M={t['memory_s'] * 1e3:8.2f}ms "
          f"N={t['collective_s'] * 1e3:8.2f}ms -> {t['dominant']:10s} "
          f"useful={r['useful_ratio']:.2f}/{r.get('useful_ratio_attn', 0):.2f} "
          f"({r['wall_s']}s)")


def cell_path(arch: str, shape: str, mesh: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--no-optimizer", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    base_opts = DryrunOptions(remat=args.remat,
                              include_optimizer=not args.no_optimizer)

    built = {}
    n_fail = 0
    for mp in meshes:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        # roofline terms are reported single-pod only (brief §Roofline);
        # the multi-pod pass proves the 'pod' axis shards + memory.
        opts = dataclasses.replace(base_opts, cost_fit=not mp)
        if mp not in built:
            built[mp] = make_production_mesh(multi_pod=mp)
        for a in archs:
            for s in shapes:
                path = cell_path(a, s, mesh_name)
                if os.path.exists(path) and not args.force:
                    r = json.load(open(path))
                    _print_cell(r)
                    if r["status"] == "error":
                        n_fail += 1
                    continue
                r = run_cell(a, s, mp, opts, mesh=built[mp])
                r.pop("traceback", None) if r["status"] == "ok" else None
                with open(path, "w") as f:
                    json.dump(r, f, indent=1)
                if r["status"] == "error":
                    n_fail += 1
    print(f"\ndone; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
