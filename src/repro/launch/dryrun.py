import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the production
mesh WITHOUT allocating real tensors (ShapeDtypeStruct inputs only).

For each cell this records, into benchmarks/results/dryrun/:
  * compiled.memory_analysis()  — proves the per-device footprint fits,
  * compiled.cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective operand bytes parsed from the partitioned HLO,
  * lower/compile wall times and an opcode histogram.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, active_param_count, param_count
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.models import batch_struct, build_model
from repro.models import layers as layers_mod
from repro.models.sharding import rules_for, spec as lspec, use_rules
from repro.optim import adam as adam_lib
from repro.utils import hlo as hlo_utils

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

# measure true FLOPs/collectives via unrolled reduced-depth compiles (see lower_cell)
_UNROLL_MEASURE = True

_BATCH_LOGICAL = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "loss_mask": ("batch", "seq"),
    "patch_embeds": ("batch", None, None),
    "frames": ("batch", None, None),
}


def batch_specs(batch: dict, rules) -> dict:
    return {k: lspec(*_BATCH_LOGICAL[k], rules=rules) for k in batch}


def param_structs(model):
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def opt_structs(p_struct):
    return {
        "m": p_struct, "v": p_struct,
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }


def opt_specs(p_specs):
    return {"m": p_specs, "v": p_specs, "count": P()}


def _ns(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda v: isinstance(v, P),
    )


def _measure_layers(cfg: ModelConfig) -> tuple[int, int, float]:
    """(a, b, eval_at): reduced layer counts for the unrolled FLOP fit and the
    layer count to evaluate the affine fit at.  Exact for homogeneous stacks;
    zamba's 2-layer tail makes the fit overcount by ~1/3 shared-attn application
    (documented in EXPERIMENTS.md)."""
    if cfg.family == "hybrid":
        e = cfg.attn_every
        return e, 2 * e, cfg.n_layers
    return 2, 4, cfg.n_layers


def _with_layers(cfg: ModelConfig, n: int) -> ModelConfig:
    import dataclasses
    kw = {"n_layers": n}
    if cfg.family == "encdec":
        kw["n_dec_layers"] = n
    return dataclasses.replace(cfg, **kw)


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               lr: float = 1e-4, extra_rules: dict | None = None,
               cfg_override: ModelConfig | None = None, micro_batches: int = 1,
               bf16_params: bool = False):
    """Returns (lowered, compiled, record_dict).

    Three compiles per cell:
      1. ROLLED full config — the deployable artifact: must compile; provides
         memory_analysis (loop liveness is realistic) and the HLO schedule.
      2./3. UNROLLED reduced-layer configs (a, b) — XLA cost_analysis counts
         while bodies once, so true FLOPs/collective-bytes come from unrolled
         graphs; an affine fit in n_layers extrapolates to the full depth.
    """
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    if not cfg.supports(shape):
        return None, None, {"arch": arch, "shape": shape_name, "skipped": True,
                            "reason": "quadratic attention at 524288 (see DESIGN.md)"}

    layers_mod.set_unroll_scans(False)
    lowered, compiled, rec = _lower_one(cfg, arch, shape, multi_pod, lr, extra_rules,
                                        micro_batches, bf16_params)

    if _UNROLL_MEASURE:
        a, b, L = _measure_layers(cfg)
        layers_mod.set_unroll_scans(True)
        try:
            fa = _lower_one(_with_layers(cfg, a), arch, shape, multi_pod, lr,
                            extra_rules, micro_batches, bf16_params)[2]
            fb = _lower_one(_with_layers(cfg, b), arch, shape, multi_pod, lr,
                            extra_rules, micro_batches, bf16_params)[2]
            for key in ("flops_per_device", "bytes_per_device"):
                slope = (fb[key] - fa[key]) / (b - a)
                rec[key + "_rolled_raw"] = rec[key]
                rec[key] = fa[key] + slope * (L - a)
            ca, cb = fa["collectives"], fb["collectives"]
            fit = {}
            for kind in set(ca["bytes_by_kind"]) | set(cb["bytes_by_kind"]):
                ya, yb = ca["bytes_by_kind"].get(kind, 0.0), cb["bytes_by_kind"].get(kind, 0.0)
                fit[kind] = max(0.0, ya + (yb - ya) / (b - a) * (L - a))
            rec["collectives_rolled_raw"] = rec["collectives"]
            rec["collectives"] = {"bytes_by_kind": fit,
                                  "total_bytes": float(sum(fit.values())),
                                  "counts": cb["counts"]}
            rec["flop_fit"] = {"a": a, "b": b, "eval_at": L,
                               "flops_a": fa["flops_per_device"],
                               "flops_b": fb["flops_per_device"]}
        finally:
            layers_mod.set_unroll_scans(False)
        _finalize_roofline(rec, arch, shape)
    return lowered, compiled, rec


def _lower_one(cfg: ModelConfig, arch: str, shape: ShapeConfig, multi_pod: bool,
               lr: float, extra_rules: dict | None, micro_batches: int = 1,
               bf16_params: bool = False):
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(multi_pod=multi_pod,
                      long_context=(shape.name == "long_500k"),
                      decode=(shape.kind == "decode"))
    if extra_rules:
        rules.update(extra_rules)

    rec = {"arch": arch, "shape": shape.name, "kind": shape.kind,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "n_devices": int(np.prod(mesh.devices.shape))}

    with jax.set_mesh(mesh), use_rules(rules):
        p_struct = param_structs(model)
        if bf16_params and shape.kind != "train":
            # serving checkpoints stored bf16: no per-use converts, half the reads
            p_struct = jax.tree.map(
                lambda s_: jax.ShapeDtypeStruct(s_.shape, jnp.bfloat16)
                if s_.dtype == jnp.float32 else s_, p_struct)
        p_specs = model.param_specs(rules)
        b_struct = batch_struct(cfg, shape)
        b_specs = batch_specs(b_struct, rules)

        t0 = time.time()
        if shape.kind == "train":
            o_struct = opt_structs(p_struct)

            def train_step(params, opt, batch):
                if micro_batches > 1:
                    # gradient accumulation: per-microbatch fwd+bwd, fp32 grad
                    # accumulator sharded like the params (memory lever)
                    def split(x):
                        m = micro_batches
                        return x.reshape((m, x.shape[0] // m) + x.shape[1:])
                    mb = jax.tree.map(split, batch)

                    def acc_fn(carry, mbatch):
                        g_acc, l_acc = carry
                        l, g = jax.value_and_grad(model.loss)(params, mbatch)
                        g_acc = jax.tree.map(jnp.add, g_acc, g)
                        return (g_acc, l_acc + l), None

                    g0 = jax.tree.map(jnp.zeros_like, params)
                    # unroll under measurement mode (cost_analysis counts scan
                    # bodies once; see layers_mod.set_unroll_scans)
                    (grads, loss), _ = jax.lax.scan(
                        acc_fn, (g0, 0.0), mb,
                        unroll=layers_mod._unroll(micro_batches))
                    grads = jax.tree.map(lambda g: g / micro_batches, grads)
                    loss = loss / micro_batches
                else:
                    loss, grads = jax.value_and_grad(model.loss)(params, batch)
                new_p, new_o = adam_lib.adam_update(grads, opt, params, lr)
                return new_p, new_o, loss

            fn = jax.jit(
                train_step,
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, opt_specs(p_specs)),
                              _ns(mesh, b_specs)),
                out_shardings=(_ns(mesh, p_specs), _ns(mesh, opt_specs(p_specs)),
                               NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            lowered = fn.lower(p_struct, o_struct, b_struct)
        elif shape.kind == "prefill":
            def prefill_step(params, batch):
                return model.prefill(params, batch)

            fn = jax.jit(
                prefill_step,
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, b_specs)),
                out_shardings=NamedSharding(mesh, lspec("batch", None, "vocab", rules=rules)),
            )
            lowered = fn.lower(p_struct, b_struct)
        else:  # decode
            c_struct = model.cache_struct(shape.global_batch, shape.seq_len)
            c_specs = model.cache_specs(rules)

            def serve_step(params, cache, batch, pos):
                logits, new_cache = model.decode_step(params, cache, batch, pos)
                return logits, new_cache

            fn = jax.jit(
                serve_step,
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, c_specs),
                              _ns(mesh, b_specs), NamedSharding(mesh, P())),
                out_shardings=(NamedSharding(mesh, lspec("batch", None, "vocab", rules=rules)),
                               _ns(mesh, c_specs)),
                donate_argnums=(1,),
            )
            lowered = fn.lower(p_struct, c_struct, b_struct,
                               jax.ShapeDtypeStruct((), jnp.int32))
        rec["lower_s"] = round(time.time() - t0, 2)

        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

    # ---- analyses ------------------------------------------------------------
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["flops_per_device"] = float(ca.get("flops", 0.0))
        rec["bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        rec["cost_analysis_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(ma, k)
        }
    except Exception as e:  # pragma: no cover
        rec["memory_analysis_error"] = repr(e)

    txt = compiled.as_text()
    rec["collectives"] = hlo_utils.collective_bytes(txt)
    rec["hlo_ops"] = hlo_utils.op_histogram(txt, top=15)
    _finalize_roofline(rec, arch, shape)
    return lowered, compiled, rec


def _finalize_roofline(rec: dict, arch: str, shape: ShapeConfig) -> None:
    n_dev = rec["n_devices"]
    flops = rec.get("flops_per_device", 0.0)
    membytes = rec.get("bytes_per_device", 0.0)
    coll = rec["collectives"]["total_bytes"]
    rec["roofline"] = {
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": membytes / HBM_BW,
        "collective_s": coll / ICI_BW,
    }
    dom = max(rec["roofline"], key=rec["roofline"].get)
    rec["roofline"]["dominant"] = dom
    # useful-FLOP ratio: MODEL_FLOPS / (per-device HLO flops * n_devices)
    cfg_n = active_param_count(get_config(arch))
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = (6 if shape.kind == "train" else 2) * cfg_n * tokens
    rec["model_flops"] = float(mf)
    rec["model_flops_ratio"] = float(mf / max(flops * n_dev, 1.0))
    rec["param_count"] = param_count(get_config(arch))
    rec["active_param_count"] = cfg_n
    rec["ok"] = True


def run_cell(arch, shape_name, multi_pod, out_dir, skip_existing=False):
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
    path = os.path.join(out_dir, tag + ".json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        if old.get("ok") or old.get("skipped"):
            print(f"[dryrun] {tag}: cached")
            return old
    try:
        _, compiled, rec = lower_cell(arch, shape_name, multi_pod)
        if compiled is not None:
            print(f"[dryrun] {tag}: OK lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"dom={rec['roofline']['dominant']}")
            ma = rec.get("memory", {})
            print(f"  memory_analysis: {ma}")
            print(f"  cost_analysis: flops/dev={rec.get('flops_per_device', 0):.3e} "
                  f"bytes/dev={rec.get('bytes_per_device', 0):.3e} "
                  f"coll/dev={rec['collectives']['total_bytes']:.3e}")
        else:
            print(f"[dryrun] {tag}: SKIP ({rec['reason']})")
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "ok": False, "error": repr(e), "traceback": traceback.format_exc()}
        print(f"[dryrun] {tag}: FAIL {e!r}")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--no-unroll", action="store_true",
                    help="skip the unrolled reduced-depth FLOP-measurement passes")
    args = ap.parse_args()
    global _UNROLL_MEASURE
    _UNROLL_MEASURE = not args.no_unroll

    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    n_fail = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mp, args.out, args.skip_existing)
                if not (rec.get("ok") or rec.get("skipped")):
                    n_fail += 1
    print(f"[dryrun] done, failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
