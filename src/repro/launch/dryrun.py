import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  512 placeholder host devices back the production
# mesh; smoke tests / benches never import this module and see 1 device.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production mesh and record memory / cost / collective stats.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
      --shape train_4k [--multi-pod] [--units N] [--remat full] ...
  PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep (subprocesses)

Results are cached as JSON under experiments/dryrun/.
"""

import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             units: int | None = None, remat: str = "full",
             microbatch: int = 0, rule_overrides: dict | None = None,
             flash_kv_chunk: int | None = None,
             metering: bool = False, scan_param_fsdp: bool = False,
             grad_accum_dtype: str = "float32") -> dict:
    import jax
    import repro  # noqa: F401  (x64 etc.)
    from repro.configs.base import SHAPES, get_config, shape_applicable
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import DEFAULT_RULES, ShardingRules
    from repro.launch.inputs import input_specs
    from repro.launch.steps import (TrainConfig, build_serve_step,
                                    build_train_step, opt_state_specs)
    from repro.launch.hlo_parse import collective_breakdown
    from repro.models import forward
    from repro.models.layers import shard as shard_act

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    res = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "units": units, "remat": remat, "microbatch": microbatch}
    if not ok:
        res["skipped"] = why
        return res
    if units is not None:
        cfg = cfg.scaled(units)
    if flash_kv_chunk is not None:
        import repro.models.attention as att
        att.FLASH_KV_CHUNK = flash_kv_chunk
    if metering:
        # metering build: unrolled layers AND unrolled (real-size) chunk
        # loops, so cost_analysis — which counts each while body ONCE — is
        # exact for both flops and bytes.  memory_analysis of metering
        # builds is ignored; the full (scanned) build provides memory.
        # Remaining undercount: sLSTM's per-timestep scan (documented).
        import repro.models.attention as att
        import repro.models.ssm as ssm_mod
        att.UNROLL_CHUNKS = True
        ssm_mod.UNROLL_CHUNKS = True
        microbatch = 1
        res["metering"] = True

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = ShardingRules(DEFAULT_RULES)
    if rule_overrides:
        rules.update(rule_overrides)
    res["rules"] = {k: v for k, v in rules.items()}
    if microbatch == 0:  # auto: one sequence per data shard per microstep
        data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        microbatch = max(1, shape.global_batch // data_shards) \
            if (shape.kind == "train" and cfg.d_model >= 2048) else 1
        res["microbatch"] = microbatch
    tcfg = TrainConfig(remat=remat, microbatch=microbatch, unroll=metering,
                       scan_param_fsdp=scan_param_fsdp,
                       grad_accum_dtype=grad_accum_dtype)
    res["scan_param_fsdp"] = scan_param_fsdp
    res["grad_accum_dtype"] = grad_accum_dtype

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = build_train_step(cfg, tcfg, rules, mesh)
            pspec, bspec = input_specs(cfg, shape, mesh, rules)
            ospec = opt_state_specs(cfg, mesh, rules, tcfg)
            fn = jax.jit(step, donate_argnums=(0, 1))
            args = (pspec, ospec, bspec)
        elif shape.kind == "prefill":
            pspec, bspec = input_specs(cfg, shape, mesh, rules)
            from repro.launch.sharding import rules_ctx

            def prefill(params, batch):
                with rules_ctx(rules, mesh):
                    # serving prefill: logits for the last position only
                    from repro.models.model import (_dtype, apply_norm,
                                                    execution_runs)
                    logits, _ = forward(
                        params, cfg, tokens=batch.get("tokens"),
                        embeds=batch.get("embeds"),
                        aux={k: v for k, v in batch.items()
                             if k == "image_embed"},
                        remat="none", last_only=True, unroll=metering)
                    return logits
            fn = jax.jit(prefill)
            args = (pspec, bspec)
        else:  # decode
            step = build_serve_step(cfg, rules, mesh, unroll=metering)
            pspec, cspec, bspec = input_specs(cfg, shape, mesh, rules)
            fn = jax.jit(step, donate_argnums=(1,))
            args = (pspec, cspec, bspec)

        lowered = fn.lower(*args)
        res["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        res["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        res["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        res["cost"] = {k: float(v) for k, v in ca.items()
                       if k in ("flops", "bytes accessed")}
        hlo = compiled.as_text()
        res["hlo_chars"] = len(hlo)
        if os.environ.get("DRYRUN_DUMP_HLO"):
            pathlib.Path(os.environ["DRYRUN_DUMP_HLO"]).write_text(hlo)
        res["collectives"] = collective_breakdown(hlo)
        res["n_devices"] = mesh.size
    return res


def run_store_cell(*, multi_pod: bool = False, n_keys: int = 1 << 30,
                   probe_batch: int = 1 << 20, seg_search: str = "bisect",
                   combine: str = "reduce_scatter") -> dict:
    """Dry-run the distributed Bourbon store (the paper's own workload):
    range-partitioned snapshot over every mesh device, one batched GET."""
    import jax
    import jax.numpy as jnp
    import repro  # noqa: F401
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import (DistStoreConfig, build_dist_get,
                                        dist_state_specs)
    from repro.launch.hlo_parse import collective_breakdown
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = DistStoreConfig(n_keys=n_keys, probe_batch=probe_batch)
    res = {"arch": "bourbon_kv", "shape": f"get_{probe_batch}",
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_keys": n_keys, "probe_batch": probe_batch,
           "seg_search": seg_search, "combine": combine}
    t0 = time.time()
    with jax.set_mesh(mesh):
        specs = dist_state_specs(mesh, cfg)
        probes = jax.ShapeDtypeStruct(
            (probe_batch,), jnp.int64,
            sharding=NamedSharding(mesh, P(tuple(mesh.axis_names))))
        fn = build_dist_get(mesh, cfg, seg_search=seg_search,
                            combine=combine)
        lowered = fn.lower(specs, probes)
        res["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        res["compile_s"] = round(time.time() - t1, 2)
        ma = compiled.memory_analysis()
        res["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes)}
        ca = compiled.cost_analysis() or {}
        res["cost"] = {k: float(v) for k, v in ca.items()
                       if k in ("flops", "bytes accessed")}
        res["collectives"] = collective_breakdown(compiled.as_text())
        res["n_devices"] = mesh.size
    return res


def _cache_path(out_dir, arch, shape, mesh_tag, suffix=""):
    return pathlib.Path(out_dir) / f"{arch}__{shape}__{mesh_tag}{suffix}.json"


def sweep(out_dir: str, multi_pod: bool, with_depth_variants: bool,
          jobs: list | None = None):
    """Run every cell in a subprocess (isolates compile memory), cache JSON."""
    from repro.configs.base import ARCHS, SHAPES
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    mesh_tag = "multi" if multi_pod else "single"
    todo = jobs or [(a, s) for a in ARCHS for s in SHAPES]
    for arch, shape in todo:
        variants = [("", None)]
        if with_depth_variants:
            variants += [("__u1", 1), ("__u2", 2)]
        for suffix, units in variants:
            path = _cache_path(out, arch, shape, mesh_tag, suffix)
            if path.exists():
                print(f"[cached] {path.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", str(path)]
            if multi_pod:
                cmd.append("--multi-pod")
            if units is not None:
                cmd += ["--units", str(units), "--metering"]
            print(f"[run] {' '.join(cmd[3:])}", flush=True)
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=3600)
            if r.returncode != 0:
                err = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                       "units": units, "error": r.stderr[-4000:]}
                path.write_text(json.dumps(err, indent=1))
                print(f"  FAILED ({time.time()-t0:.0f}s): "
                      f"{r.stderr.strip().splitlines()[-1] if r.stderr else '?'}")
            else:
                print(f"  ok ({time.time()-t0:.0f}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--units", type=int, default=None)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="0 = auto (one seq per data shard for >=2B trains)")
    ap.add_argument("--rule", action="append", default=[],
                    help="logical=mesh_axis override, e.g. seq=model")
    ap.add_argument("--flash-kv-chunk", type=int, default=None)
    ap.add_argument("--metering", action="store_true")
    ap.add_argument("--scan-param-fsdp", action="store_true")
    ap.add_argument("--grad-accum-dtype", default="float32")
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--store", action="store_true",
                    help="dry-run the distributed bourbon_kv store cell")
    ap.add_argument("--store-seg-search", default="bisect")
    ap.add_argument("--store-combine", default="reduce_scatter")
    ap.add_argument("--depth-variants", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args()

    if args.store:
        res = run_store_cell(multi_pod=args.multi_pod,
                             seg_search=args.store_seg_search,
                             combine=args.store_combine)
        js = json.dumps(res, indent=1, default=str)
        print(js)
        if args.out:
            pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            pathlib.Path(args.out).write_text(js)
        return
    if args.all:
        sweep(args.out_dir, args.multi_pod, args.depth_variants)
        return

    overrides = {}
    for r in args.rule:
        k, _, v = r.partition("=")
        overrides[k] = None if v in ("", "none", "None") else (
            tuple(v.split("+")) if "+" in v else v)
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   units=args.units, remat=args.remat,
                   microbatch=args.microbatch, rule_overrides=overrides or None,
                   flash_kv_chunk=args.flash_kv_chunk,
                   metering=args.metering,
                   scan_param_fsdp=args.scan_param_fsdp,
                   grad_accum_dtype=args.grad_accum_dtype)
    js = json.dumps(res, indent=1, default=str)
    print(js)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(js)


if __name__ == "__main__":
    main()
