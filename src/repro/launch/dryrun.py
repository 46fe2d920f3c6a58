import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import: jax locks the device
# count at first initialization, and the dry-run needs 512 placeholder CPU
# devices to build the production meshes. It is a CPU-only tool: pinning
# the platform (inherited by the per-cell child processes) keeps it, and
# every child, off an accelerator another process may hold.

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (lower+compile succeeds, no sharding
    mismatch / unsupported collective),
  * the memory plan fits (compiled.memory_analysis() per-device bytes),
  * and it extracts the roofline terms (cost_analysis + the trip-count-
    aware HLO parser in launch/hlo_cost.py).

Artifacts: one JSON per cell under --out (default artifacts/dryrun/),
consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all            # every assigned cell, both meshes
"""
import argparse
import json
import math
import subprocess
import sys
import time
import traceback
from typing import Dict, Optional

DEFAULT_OUT = "artifacts/dryrun"


def cell_name(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}__{shape}__{'multipod' if multi_pod else 'singlepod'}"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False,
             rule_overrides_json: Optional[str] = None,
             tag: str = "") -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_bundle
    from repro.configs.base import SHAPES
    from repro.launch import hlo_cost, steps
    from repro.launch.mesh import make_production_mesh, make_rules
    from repro.models import model as M
    from repro.parallel.sharding import use_rules

    bundle = get_bundle(arch)
    cfg = bundle.model
    shape = SHAPES[shape_name]
    pcfg = bundle.parallel_for(shape_name)
    if rule_overrides_json:
        pcfg = pcfg.replace(rule_overrides={**dict(pcfg.rule_overrides),
                                            **json.loads(rule_overrides_json)})

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, cfg, shape, pcfg, multi_pod=multi_pod)
    rep = rules.sharding(())

    result: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "n_chips": int(math.prod(mesh.devices.shape)),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "n_params": M.n_params(cfg),
        "n_active_params": n_active_params(cfg),
        "parallel": {
            "fsdp": pcfg.fsdp, "microbatches": pcfg.microbatches,
            "remat": pcfg.remat, "optimizer": pcfg.optimizer,
            "opt_state_dtype": pcfg.opt_state_dtype,
            "seq_shard_activations": pcfg.seq_shard_activations,
            "rule_overrides": dict(pcfg.rule_overrides),
        },
        "tag": tag,
    }

    with use_rules(rules), mesh:
        if shape.kind == "train":
            step_fn = steps.make_train_step(cfg, pcfg)
            in_sh = (steps.state_shardings(cfg, rules, pcfg),
                     steps.batch_shardings(cfg, shape, rules))
            out_sh = (steps.state_shardings(cfg, rules, pcfg), rep)
            args = (steps.state_structs(cfg, pcfg, None),
                    steps.batch_structs(cfg, shape, None))
        else:  # prefill / decode share the (params, batch, caches) signature
            if shape.kind == "prefill":
                step_fn = steps.make_prefill_step(cfg)
            else:
                step_fn = steps.make_decode_step(cfg)
            if cfg.family == "audio":
                logits_sh = rules.sharding(("batch", None, "act_vocab"))
            else:
                logits_sh = rules.sharding(("batch", "act_vocab"))
            in_sh = (steps.param_shardings(cfg, rules),
                     steps.batch_shardings(cfg, shape, rules),
                     steps.cache_shardings(cfg, shape, rules))
            out_sh = (logits_sh, steps.cache_shardings(cfg, shape, rules))
            args = (steps.params_structs(cfg),
                    steps.batch_structs(cfg, shape, None),
                    steps.cache_structs(cfg, shape, None))

        t_lower0 = time.time()
        lowered = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        t_lower = time.time() - t_lower0
        t_c0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t_c0

        ma = compiled.memory_analysis()
        mem = {
            k: int(getattr(ma, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if hasattr(ma, k)
        }
        print("memory_analysis:", mem)
        ca = compiled.cost_analysis() or {}
        print("cost_analysis: flops=%s bytes=%s" % (
            ca.get("flops"), ca.get("bytes accessed")))

        hlo = compiled.as_text()
        summary = hlo_cost.analyze(hlo)
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(
                    out_dir, cell_name(arch, shape_name, multi_pod) + ".hlo"), "w") as f:
                f.write(hlo)

    result.update({
        "timings": {"mesh_s": t_lower0 - t0, "lower_s": t_lower, "compile_s": t_compile},
        "memory_analysis": mem,
        "cost_analysis_raw": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "hlo_cost": {
            "flops_per_device": summary.flops,
            "dot_bytes_per_device": summary.dot_bytes,
            "collective_bytes_per_device": dict(summary.collective_bytes),
            "total_collective_bytes_per_device": summary.total_collective_bytes,
        },
        "status": "ok",
    })
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_name(arch, shape_name, multi_pod) +
                        (f".{tag}" if tag else "") + ".json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"[dryrun] OK {cell_name(arch, shape_name, multi_pod)} "
          f"compile={t_compile:.1f}s -> {path}")
    return result


def run_snn_cell(arch: str, multi_pod: bool, out_dir: str,
                 batch: int = 256, n_ticks: int = 8) -> Dict:
    """Dry-run the paper's technique at production scale: one synchronous
    tick-rollout of the all-to-all SNN core, sharded across the mesh.

    Synapse matrix W (and connection list C) shard 2-D over
    (model=presynaptic, data=postsynaptic); spike state shards over batch.
    Proves the universal-interconnect maps onto the pod (DESIGN.md §4).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_bundle
    from repro.core.lif import LIFParams, LIFState
    from repro.core.network import SNNParams, SNNState, rollout
    from repro.launch import hlo_cost
    from repro.launch.mesh import make_production_mesh

    cfg = get_bundle(arch).model
    n = cfg.n_neurons
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    s = lambda *spec: NamedSharding(mesh, P(*spec))

    def tick_rollout(params, state, ext):
        final, raster = rollout(params, state, ext, n_ticks, mode=cfg.snn_mode,
                                backend=cfg.snn_backend)
        return final.lif.v, raster.sum(axis=(0, 1))

    f32 = jnp.float32
    params = SNNParams(
        w=jax.ShapeDtypeStruct((n, n), f32, sharding=s("model", batch_axes)),
        c=jax.ShapeDtypeStruct((n, n), f32, sharding=s("model", batch_axes)),
        w_in=jax.ShapeDtypeStruct((n, n), f32, sharding=s("model", batch_axes)),
        lif=LIFParams(
            v_th=jax.ShapeDtypeStruct((n,), f32, sharding=s(None)),
            leak=jax.ShapeDtypeStruct((n,), f32, sharding=s(None)),
            r_ref=jax.ShapeDtypeStruct((n,), jnp.int32, sharding=s(None)),
            gain=jax.ShapeDtypeStruct((n,), f32, sharding=s(None)),
            i_bias=jax.ShapeDtypeStruct((n,), f32, sharding=s(None)),
            v_reset=jax.ShapeDtypeStruct((n,), f32, sharding=s(None)),
        ))
    bsh = s(batch_axes, None)
    state = SNNState(
        lif=LIFState(
            v=jax.ShapeDtypeStruct((batch, n), f32, sharding=bsh),
            r=jax.ShapeDtypeStruct((batch, n), jnp.int32, sharding=bsh),
            y=jax.ShapeDtypeStruct((batch, n), f32, sharding=bsh)),
        delay_buf=jax.ShapeDtypeStruct((batch, 1, n), f32,
                                       sharding=s(batch_axes, None, None)),
        tick=jax.ShapeDtypeStruct((), jnp.int32, sharding=s()),
    )
    ext = jax.ShapeDtypeStruct((n_ticks, batch, n), f32,
                               sharding=s(None, batch_axes, None))
    t0 = time.time()
    with mesh:
        lowered = jax.jit(tick_rollout).lower(params, state, ext)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "temp_size_in_bytes") if hasattr(ma, k)}
        summary = hlo_cost.analyze(compiled.as_text())
    result = {
        "arch": arch, "shape": f"tick_rollout_b{batch}_t{n_ticks}",
        "mesh": "2x16x16" if multi_pod else "16x16", "kind": "snn_tick",
        "n_chips": int(math.prod(mesh.devices.shape)),
        "seq_len": n_ticks, "global_batch": batch,
        "n_params": n * n, "n_active_params": n * n,
        "parallel": {}, "tag": "",
        "timings": {"compile_s": time.time() - t0},
        "memory_analysis": mem,
        "cost_analysis_raw": {},
        "hlo_cost": {
            "flops_per_device": summary.flops,
            "dot_bytes_per_device": summary.dot_bytes,
            "collective_bytes_per_device": dict(summary.collective_bytes),
            "total_collective_bytes_per_device": summary.total_collective_bytes,
        },
        "status": "ok",
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_name(arch, result["shape"], multi_pod) + ".json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"[dryrun] OK snn cell {arch} ({result['mesh']}) "
          f"mem={mem} flops/dev={summary.flops/1e12:.2f}TF -> {path}")
    return result


def n_active_params(cfg) -> int:
    """Parameters touched per token: MoE experts count at top_k/E (+shared)."""
    from repro.models import model as M
    from repro.models.common import is_spec
    import jax

    total = 0.0
    for leaf in jax.tree.leaves(M.specs(cfg), is_leaf=is_spec):
        n = math.prod(leaf.shape)
        if "experts" in leaf.axes and cfg.n_experts:
            n = n * cfg.top_k / cfg.n_experts
        total += n
    return int(total)


def all_cells():
    from repro.configs import ASSIGNED_ARCHS, get_bundle
    from repro.configs.base import applicable_shapes

    cells = []
    for arch in ASSIGNED_ARCHS:
        cfg = get_bundle(arch).model
        for shape_name in applicable_shapes(cfg):
            for multi_pod in (False, True):
                cells.append((arch, shape_name, multi_pod))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--rule-overrides", default=None,
                    help="JSON dict of logical-axis overrides (hillclimb)")
    ap.add_argument("--tag", default="", help="artifact suffix (hillclimb iters)")
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch, shape_name, multi_pod in all_cells():
            name = cell_name(arch, shape_name, multi_pod)
            path = os.path.join(args.out, name + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip existing {name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name, "--out", args.out]
            if multi_pod:
                cmd.append("--multi-pod")
            if args.save_hlo:
                cmd.append("--save-hlo")
            print(f"[dryrun] === {name} ===", flush=True)
            rc = subprocess.run(cmd).returncode
            if rc != 0:
                failures.append(name)
                print(f"[dryrun] FAIL {name} (rc={rc})", flush=True)
        if failures:
            print("[dryrun] FAILURES:", failures)
            sys.exit(1)
        print("[dryrun] all cells passed")
        return

    try:
        if args.arch and args.arch.endswith("snn") or args.arch == "snn-64k":
            run_snn_cell(args.arch, args.multi_pod, args.out)
        else:
            run_cell(args.arch, args.shape, args.multi_pod, args.out,
                     save_hlo=args.save_hlo,
                     rule_overrides_json=args.rule_overrides, tag=args.tag)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
