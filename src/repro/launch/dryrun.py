import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

For every (architecture x input shape x mesh) cell: lower + compile the step
function on the production mesh with ShapeDtypeStruct inputs (no allocation),
record memory_analysis / cost_analysis / collective schedule, and derive the
three roofline terms. Results land in benchmarks/results/dryrun/*.json.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, SHAPES, get_arch
from repro.launch import roofline as rl
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import (batch_specs, build_model, cache_specs, decode_specs,
                          param_specs)
from repro.optim import AdamWConfig, init_opt_state
from repro.sharding import (batch_pspecs, cache_pspecs, opt_pspecs,
                            param_pspecs, shardings)
from repro.sharding.act import activation_sharding
from repro.training import make_prefill_step, make_serve_step, make_train_step

from jax.sharding import PartitionSpec as P, NamedSharding

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" \
    / "dryrun"


def _spec_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               overrides=None, mesh_shape=None):
    """Lower+compile one cell; returns (compiled, lowered, meta).

    mesh_shape: optional (dp, tp) logical reshape of the single-pod 256
    chips for §Perf sharding iterations (the baseline mesh is 16x16)."""
    arch = get_arch(arch_name)
    cfg = arch.config
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    if mesh_shape is not None:
        assert not multi_pod
        dp, tp = mesh_shape
        assert dp * tp == 256, "single-pod perf runs keep 256 chips"
        mesh = jax.make_mesh((dp, tp), ("data", "model"),
                             devices=jax.devices()[:256])
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    with jax.set_mesh(mesh), activation_sharding(mesh):
        return _lower_cell_inner(cfg, shape, mesh, multi_pod)


def _lower_cell_inner(cfg, shape, mesh, multi_pod):
    model = build_model(cfg)
    p_specs = param_specs(cfg)
    p_sh = shardings(param_pspecs(cfg, p_specs, mesh), mesh)

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        o_specs = jax.eval_shape(lambda: init_opt_state(p_specs, opt_cfg))
        o_sh = shardings(
            opt_pspecs(cfg, param_pspecs(cfg, o_specs, mesh), mesh), mesh)
        b_specs = batch_specs(cfg, shape)
        b_sh = shardings(batch_pspecs(b_specs, mesh), mesh)
        step = make_train_step(model, opt_cfg)
        lowered = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1)).lower(
            _spec_tree(p_specs), _spec_tree(o_specs), _spec_tree(b_specs))
    elif shape.kind == "prefill":
        b_specs = batch_specs(cfg, shape)
        b_sh = shardings(batch_pspecs(b_specs, mesh), mesh)
        from repro.sharding.partition import batch_entry
        c_specs = cache_specs(cfg, shape.global_batch, shape.seq_len)
        c_sh = shardings(cache_pspecs(cfg, c_specs, mesh), mesh)
        logits_sh = NamedSharding(
            mesh, P(batch_entry(mesh, shape.global_batch), None))
        step = make_prefill_step(model, shape.seq_len)
        lowered = jax.jit(step, in_shardings=(p_sh, b_sh),
                          out_shardings=(logits_sh, c_sh)).lower(
            _spec_tree(p_specs), _spec_tree(b_specs))
    else:  # decode
        from repro.sharding.partition import batch_entry
        c_specs, tok_spec, pos_spec = decode_specs(cfg, shape)
        c_ps = cache_pspecs(cfg, c_specs, mesh)
        c_sh = shardings(c_ps, mesh)
        ba = batch_entry(mesh, shape.global_batch)
        tok_sh = NamedSharding(mesh, P(ba))
        pos_sh = NamedSharding(mesh, P())
        logits_sh = NamedSharding(mesh, P(ba, None))
        step = make_serve_step(model)
        lowered = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
                          out_shardings=(logits_sh, c_sh),
                          donate_argnums=(1,)).lower(
            _spec_tree(p_specs), c_specs, tok_spec, pos_spec)
    compiled = lowered.compile()
    return compiled, lowered, {"cfg": cfg, "shape": shape,
                               "p_specs": p_specs, "mesh": mesh}


def analyze(compiled, meta, multi_pod: bool, elapsed: float):
    cfg, shape, p_specs = meta["cfg"], meta["shape"], meta["p_specs"]
    chips = 512 if multi_pod else 256
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # loop-aware static analysis (XLA CPU cost_analysis counts while bodies
    # once and reports unfused traffic — see hlo_analysis.py)
    s = analyze_hlo(hlo)
    flops_dev = s.dot_flops
    # HBM-traffic proxy: dot operand/output traffic (perfect elementwise
    # fusion) + per-step argument/output IO (params, opt state, caches)
    bytes_dev = (s.dot_bytes + ma.argument_size_in_bytes
                 + ma.output_size_in_bytes)
    csum = {"by_op": s.collectives,
            "effective_bytes": s.collective_effective_bytes,
            "effective_bytes_bf16adj": s.collective_effective_bytes_bf16adj,
            "loops": s.loops[:40]}
    terms = rl.roofline_terms(flops_dev, bytes_dev, csum["effective_bytes"])
    terms["collective_s_bf16adj"] = (s.collective_effective_bytes_bf16adj
                                     / rl.ICI_BW)
    mflops = rl.model_flops(cfg, shape, p_specs)
    hlo_flops_global = flops_dev * chips
    n_total = rl.tree_param_count(p_specs)
    n_active = rl.active_param_count(cfg, p_specs)
    return {
        "arch": cfg.name, "shape": shape.name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "kind": shape.kind,
        "compile_s": round(elapsed, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            "fits_16g": (ma.argument_size_in_bytes - ma.alias_size_in_bytes
                         + ma.temp_size_in_bytes
                         + ma.output_size_in_bytes) < 16e9,
        },
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "xla_cost_analysis": {k: ca.get(k) for k in ("flops",
                                                     "bytes accessed")},
        "hlo_flops_global": hlo_flops_global,
        "model_flops_global": mflops,
        "useful_flop_ratio": (mflops / hlo_flops_global
                              if hlo_flops_global else None),
        "params_total": n_total,
        "params_active": n_active,
        "collectives": csum,
        "roofline": terms,
    }


def run_cell(arch_name, shape_name, multi_pod, out_dir: Path,
             overrides=None, tag="", mesh_shape=None):
    key = f"{arch_name}_{shape_name}_{'multipod' if multi_pod else 'pod'}"
    if tag:
        key += f"_{tag}"
    arch = get_arch(arch_name)
    if shape_name in arch.skip_shapes:
        rec = {"arch": arch_name, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "skipped": arch.skip_shapes[shape_name]}
        _save(out_dir, key, rec)
        print(f"[skip] {key}: {arch.skip_shapes[shape_name][:60]}...")
        return rec
    t0 = time.time()
    try:
        compiled, lowered, meta = lower_cell(arch_name, shape_name,
                                             multi_pod, overrides,
                                             mesh_shape)
        rec = analyze(compiled, meta, multi_pod, time.time() - t0)
        _save(out_dir, key, rec)
        r = rec["roofline"]
        print(f"[ok]   {key}: compile={rec['compile_s']}s "
              f"dominant={r['dominant']} "
              f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
              f"coll={r['collective_s']:.3e}s "
              f"frac={r['roofline_fraction']:.2f} "
              f"fits={rec['memory']['fits_16g']}")
        return rec
    except Exception as e:  # noqa: BLE001 - record failures per cell
        rec = {"arch": arch_name, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        _save(out_dir, key, rec)
        print(f"[FAIL] {key}: {type(e).__name__}: {str(e)[:200]}")
        return rec


def _save(out_dir: Path, key: str, rec: dict):
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{key}.json").write_text(json.dumps(rec, indent=1,
                                                    default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    ap.add_argument("--dp", type=int, default=0,
                    help="perf iteration: logical mesh reshape (dp, tp)")
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    out_dir = Path(args.out)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    if not args.all and not (args.arch or args.shape):
        ap.error("pass --arch/--shape or --all")
    mesh_shape = (args.dp, args.tp) if args.dp else None
    for mp in meshes:
        for a in archs:
            for s in shapes:
                key = f"{a}_{s}_{'multipod' if mp else 'pod'}"
                if args.tag:
                    key += f"_{args.tag}"
                if args.skip_existing and (out_dir / f"{key}.json").exists():
                    continue
                run_cell(a, s, mp, out_dir, tag=args.tag,
                         mesh_shape=mesh_shape)


if __name__ == "__main__":
    main()
