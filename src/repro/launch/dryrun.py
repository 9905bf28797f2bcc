import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST be the first two lines: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and extract memory / cost / collective-schedule evidence.

  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh multi

Results are cached as JSON under experiments/dryrun/<mesh>/<arch>__<shape>.json
and aggregated by benchmarks/roofline.py into roofline tables.  The roofline
terms use the published peaks of the chip the production mesh models
(``repro.launch.peaks.TARGET_KIND``), not of the host that compiles it.
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.launch import hlo_analysis
from repro.launch.peaks import roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, input_specs, shape_applicable
from repro.launch.steps import build_decode_step, build_prefill_step, build_train_step
from repro.models import abstract_params, param_shardings
from repro.models import sharding as shd
from repro.training.optim import AdamWConfig, abstract_adamw_state

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

#: archs whose fp32 Adam state would overflow a single pod's HBM -> 8-bit
EIGHTBIT = {"arctic-480b", "mistral-large-123b", "qwen3-moe-235b-a22b"}

#: gradient-accumulation microbatches per train step (activation memory
#: control; chosen per-arch from the dry-run iteration log)
MICROBATCH = {
    "arctic-480b": 16,
    "qwen3-moe-235b-a22b": 32,
    "mistral-large-123b": 8,
    "qwen1.5-32b": 4,
    "qwen3-14b": 2,
    "recurrentgemma-9b": 4,
    "mamba2-370m": 4,
    "whisper-large-v3": 4,
    "chatglm3-6b": 2,
}


def _attach(tree_abs, tree_shard):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
        if s is not None else a, tree_abs, tree_shard)


def _opt_shardings(cfg, state_abs):
    """m/v follow the param logical axes; 8-bit q/s blocks inherit them too
    (the quantization splits only the last axis, so leading shardings
    survive -- see training/optim.py)."""
    from repro.models.schema import Spec, model_schema
    sch = model_schema(cfg)

    def mv(sub):
        def leaf(spec, a):
            if isinstance(a, dict):            # q8 {q, s}
                ql = tuple(spec.logical) + (None,)
                return {"q": shd.sharding_for(ql, a["q"].shape),
                        "s": shd.sharding_for(spec.logical, a["s"].shape)}
            return shd.sharding_for(spec.logical, a.shape)
        return jax.tree.map(leaf, sch, sub,
                            is_leaf=lambda v: isinstance(v, Spec))
    return {"m": mv(state_abs["m"]), "v": mv(state_abs["v"]),
            "count": shd.replicated()}


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: pathlib.Path,
             force: bool = False, extra_tag: str = "", step_overrides=None):
    cell_dir = out_dir / mesh_kind
    cell_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{shape_name}" + (f"__{extra_tag}" if extra_tag else "")
    path = cell_dir / f"{tag}.json"
    if path.exists() and not force:
        return json.loads(path.read_text())

    cfg = get_config(arch)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "ok": False,
           "tag": extra_tag}
    ok, why = shape_applicable(cfg, shape_name)
    if not ok:
        rec.update({"skipped": True, "reason": why, "ok": True})
        path.write_text(json.dumps(rec, indent=1))
        return rec

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    shd.set_mesh(mesh, rules={"optflat": ("data", "model")})
    t0 = time.time()
    try:
        p_abs = _attach(abstract_params(cfg), param_shardings(cfg))
        specs = input_specs(cfg, shape_name)
        kind = SHAPES[shape_name]["kind"]
        overrides = step_overrides or {}
        if kind == "train":
            ocfg = AdamWConfig(eightbit=arch in EIGHTBIT)
            s_abs = abstract_adamw_state(p_abs, ocfg)
            s_abs = _attach(s_abs, _opt_shardings(cfg, s_abs))
            # microbatch must stay divisible by the batch-sharding axes
            bdiv = 1
            for ax in ("pod", "data"):
                bdiv *= mesh.shape.get(ax, 1)
            B_glob = SHAPES[shape_name]["batch"]
            micro = overrides.get("microbatches", MICROBATCH.get(arch, 1))
            while micro > 1 and (B_glob % micro or (B_glob // micro) % bdiv):
                micro //= 2
            step = build_train_step(
                cfg, ocfg,
                remat=overrides.get("remat", "full"),
                block_skip=overrides.get("block_skip", False),
                microbatches=max(micro, 1))
            args = (p_abs, s_abs, specs["batch"])
        elif kind == "prefill":
            step = build_prefill_step(cfg)
            args = (p_abs, specs["batch"])
        else:
            step = build_decode_step(cfg)
            args = (p_abs, specs["batch"], specs["caches"], specs["pos"])

        lowered = jax.jit(step).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        st = hlo_analysis.analyze(txt)
        n_dev = mesh.size

        N = cfg.num_params()
        Na = cfg.num_active_params()
        B, S = SHAPES[shape_name]["batch"], SHAPES[shape_name]["seq"]
        if kind == "train":
            model_flops = 6.0 * Na * B * S
        elif kind == "prefill":
            model_flops = 2.0 * Na * B * S
        else:
            model_flops = 2.0 * Na * B
        model_flops_dev = model_flops / n_dev

        rec.update({
            "ok": True,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "devices": n_dev,
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_per_device": ma.argument_size_in_bytes
                + ma.temp_size_in_bytes,
                "fits_16GB": (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
                < 16e9,
            },
            "xla_cost": {"flops": ca.get("flops"),
                         "bytes": ca.get("bytes accessed")},
            "hlo": {
                "flops_per_device": st.flops,
                "traffic_bytes_per_device": st.traffic_bytes,
                "collective_bytes": dict(st.collective_bytes),
                "collective_counts": dict(st.collective_counts),
                "total_collective_bytes": st.total_collective_bytes,
            },
            "params": {"total": N, "active": Na},
            "model_flops_per_device": model_flops_dev,
            "roofline": {
                **roofline_terms(st),
                "model_flops_ratio": (model_flops_dev / st.flops
                                      if st.flops else None),
            },
        })
        terms = rec["roofline"]
        dom = max(("t_compute_s", "t_memory_s", "t_collective_s"),
                  key=lambda k: terms[k])
        rec["roofline"]["dominant"] = dom
    except Exception as e:  # noqa: BLE001 -- record the failure for triage
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    out = pathlib.Path(args.out)

    cells = []
    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))
    for a, s in cells:
        t0 = time.time()
        rec = run_cell(a, s, args.mesh, out, force=args.force)
        status = ("SKIP" if rec.get("skipped")
                  else "ok" if rec.get("ok") else "FAIL")
        extra = ""
        if rec.get("ok") and not rec.get("skipped"):
            mem = rec["memory"]["peak_per_device"] / 1e9
            dom = rec["roofline"]["dominant"]
            extra = f"mem/dev={mem:.2f}GB dom={dom}"
        if status == "FAIL":
            extra = rec.get("error", "")[:160]
        print(f"[{args.mesh}] {a:24s} {s:12s} {status:4s} "
              f"({time.time()-t0:6.1f}s) {extra}", flush=True)


if __name__ == "__main__":
    main()
