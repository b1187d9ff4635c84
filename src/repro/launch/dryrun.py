"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell this lowers + compiles the real
jitted step (train_step for train shapes, prefill/serve_step for inference
shapes) against the production mesh — (16,16)=256 chips single-pod and
(2,16,16)=512 chips multi-pod — and records:

  * compiled.memory_analysis()  (bytes per device: proves it fits)
  * compiled.cost_analysis()    (XLA's own flops/bytes, while-bodies once)
  * trip-count-weighted HLO totals + the 3-term roofline (analysis/)

Usage:
  python -m repro.launch.dryrun --arch phi4-mini-3.8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out artifacts/dryrun]

--all spawns one subprocess per cell (fresh XLA, bounded memory, isolated
failures) and writes one JSON per cell plus a summary table.

This is a CPU projection over 512 fake host devices: the module pins
`JAX_PLATFORMS=cpu` as it is imported, so neither it nor its children ever
load the TPU library (on a chip machine they would hold the chip).
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import subprocess
import sys
import time

from repro.launch.cell_variant import DEFAULTS, variant_key

CELL_TIMEOUT_S = 1500


def _parse_shape(shape_name: str):
    """SHAPES name or ad-hoc 'kind:seq:batch' (benchmark variants)."""
    from repro.configs import SHAPES
    from repro.configs.base import ShapeConfig
    if shape_name in SHAPES:
        return SHAPES[shape_name]
    kind, seq, batch = shape_name.split(":")
    return ShapeConfig(shape_name, kind, int(seq), int(batch))


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             reduce_method: str = DEFAULTS["reduce"], policy: str = "",
             tag: str = "baseline", naive: bool = False,
             ssm_seqp: bool = False,
             kv_cache_dtype: str = DEFAULTS["kv_cache_dtype"],
             weight_dtype: str = DEFAULTS["weight_dtype"],
             attn_sharding: str = "", comm_fp8: bool = False,
             mlp_ws: bool = False, fuse: bool = True) -> dict:
    import jax
    from repro.analysis.hlo import parse_hlo
    from repro.analysis.roofline import model_flops, roofline_from_summary
    from repro.configs import get_config, supports_shape
    from repro.core.precision import get_policy
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = _parse_shape(shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
           "variant": variant_key(policy=policy, naive=naive,
                                  reduce_method=reduce_method, fuse=fuse,
                                  ssm_seqp=ssm_seqp,
                                  kv_cache_dtype=kv_cache_dtype,
                                  weight_dtype=weight_dtype,
                                  attn_sharding=attn_sharding,
                                  comm_fp8=comm_fp8, mlp_ws=mlp_ws),
           "ok": False}
    if not supports_shape(cfg, shape):
        rec.update(skipped=True, reason="shape unsupported for this arch "
                   "(DESIGN.md §5: long_500k needs sub-quadratic attention)")
        return rec

    mesh = (None if mesh_kind == "none"
            else make_production_mesh(multi_pod=(mesh_kind == "multi")))
    pol = get_policy(policy) if policy else None
    t0 = time.time()
    if shape.kind == "train":
        bundle = steps.make_train_step(cfg, shape, mesh, policy=pol,
                                       reduce_method=reduce_method,
                                       naive_attention=naive,
                                       ssm_seq_parallel=ssm_seqp,
                                       fuse_epilogues=fuse)
    elif shape.kind == "prefill":
        bundle = steps.make_prefill_step(cfg, shape, mesh, policy=pol,
                                         reduce_method=reduce_method,
                                         naive_attention=naive,
                                         ssm_seq_parallel=ssm_seqp,
                                         kv_cache_dtype=kv_cache_dtype,
                                         weight_dtype=weight_dtype,
                                         attention_sharding=attn_sharding,
                                         comm_fp8=comm_fp8,
                                         mlp_weight_stationary=mlp_ws,
                                         fuse_epilogues=fuse)
    else:
        bundle = steps.make_decode_step(cfg, shape, mesh, policy=pol,
                                        reduce_method=reduce_method,
                                        kv_cache_dtype=kv_cache_dtype,
                                        weight_dtype=weight_dtype,
                                        fuse_epilogues=fuse)
    lowered = bundle.lower()
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k, 0)) for k in
           ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")}
    ca = compiled.cost_analysis() or {}
    cost = {k: float(ca[k]) for k in ("flops", "bytes accessed") if k in ca}

    import gzip
    import numpy as np
    dt_name = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
               "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2"}[
                   np.dtype(bundle.policy.compute_dtype).name]
    hlo_text = compiled.as_text()
    if out_dir:                         # archive for offline re-analysis
        hdir = os.path.join(out_dir, "hlo")
        os.makedirs(hdir, exist_ok=True)
        hname = f"{arch}__{shape_name.replace(':', '-')}__{mesh_kind}__{tag}"
        hfile = os.path.join(hdir, hname + ".hlo.gz")
        with gzip.open(hfile, "wt") as f:
            f.write(hlo_text)
        # Record repo-relative so cached cells stay valid across checkouts.
        repo_root = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "..", ".."))
        hrel = os.path.relpath(os.path.abspath(hfile), repo_root)
        rec["hlo_path"] = (os.path.abspath(hfile)
                           if hrel.startswith("..") else hrel)
    from repro.core.nn import act_dtype as _ad
    summary = parse_hlo(
        hlo_text, default_dot_dtype=dt_name,
        act_bytes=np.dtype(_ad(bundle.policy)).itemsize,
        param_bytes=np.dtype(bundle.policy.param_dtype).itemsize,
        gather_act_bytes=1 if comm_fp8 else None)
    roof = roofline_from_summary(summary)
    mf = model_flops(cfg, shape)
    n_dev = mesh.devices.size if mesh is not None else 1
    hlo_total = roof.flops * n_dev
    rec.update(
        ok=True, lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        devices=n_dev, policy=bundle.policy.name,
        memory_analysis=mem, cost_analysis=cost,
        roofline=roof.as_dict(),
        model_flops=mf,
        useful_flops_ratio=(mf / hlo_total if hlo_total else 0.0),
        hbm_per_device_gb=round((mem["argument_size_in_bytes"]
                                 + mem["temp_size_in_bytes"]) / 2**30, 3),
    )
    return rec


def cell_list():
    from repro.configs import ASSIGNED, SHAPES
    return [(a, s) for a in sorted(ASSIGNED) for s in
            ("train_4k", "prefill_32k", "decode_32k", "long_500k")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both", "none"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--reduce", default=DEFAULTS["reduce"],
                    choices=["ring", "tree"])
    ap.add_argument("--policy", default="")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--naive", action="store_true")
    ap.add_argument("--ssm-seqp", action="store_true")
    ap.add_argument("--kv-dtype", default=DEFAULTS["kv_cache_dtype"])
    ap.add_argument("--weight-dtype", default=DEFAULTS["weight_dtype"],
                    choices=["bfloat16", "int8"])
    ap.add_argument("--attn-sharding", default="",
                    choices=["", "head_tp", "seq_sp"])
    ap.add_argument("--comm-fp8", action="store_true")
    ap.add_argument("--mlp-ws", action="store_true")
    ap.add_argument("--no-fuse", action="store_true",
                    help="disable the fused prologue/epilogue pipeline "
                         "(A/B baseline for the fusion benchmark)")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if not args.all:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mk in meshes:
            rec = run_cell(args.arch, args.shape, mk, args.out,
                           reduce_method=args.reduce, policy=args.policy,
                           tag=args.tag, naive=args.naive,
                           ssm_seqp=args.ssm_seqp,
                           kv_cache_dtype=args.kv_dtype,
                           weight_dtype=args.weight_dtype,
                           attn_sharding=args.attn_sharding,
                           comm_fp8=args.comm_fp8, mlp_ws=args.mlp_ws,
                           fuse=not args.no_fuse)
            safe = args.shape.replace(":", "-")
            fname = os.path.join(
                args.out, f"{args.arch}__{safe}__{mk}__{args.tag}.json")
            with open(fname, "w") as f:
                json.dump(rec, f, indent=1)
            print(json.dumps(rec, indent=1))
        return 0

    # orchestrate: one subprocess per cell
    want = variant_key(policy=args.policy, naive=args.naive,
                       reduce_method=args.reduce, fuse=not args.no_fuse,
                       ssm_seqp=args.ssm_seqp, kv_cache_dtype=args.kv_dtype,
                       weight_dtype=args.weight_dtype,
                       attn_sharding=args.attn_sharding,
                       comm_fp8=args.comm_fp8, mlp_ws=args.mlp_ws)
    results = []
    for arch, shape in cell_list():
        for mk in meshes:
            fname = os.path.join(
                args.out, f"{arch}__{shape}__{mk}__{args.tag}.json")
            if os.path.exists(fname):
                cached = json.load(open(fname))
                if cached.get("variant") == want:
                    results.append(cached)
                    print(f"[cached] {arch} {shape} {mk}")
                    continue
                os.remove(fname)   # tag collision or legacy cache
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mk,
                   "--out", args.out, "--reduce", args.reduce,
                   "--tag", args.tag, "--kv-dtype", args.kv_dtype,
                   "--weight-dtype", args.weight_dtype]
            if args.policy:
                cmd += ["--policy", args.policy]
            if args.attn_sharding:
                cmd += ["--attn-sharding", args.attn_sharding]
            for flag, on in [("--naive", args.naive),
                             ("--ssm-seqp", args.ssm_seqp),
                             ("--comm-fp8", args.comm_fp8),
                             ("--mlp-ws", args.mlp_ws),
                             ("--no-fuse", args.no_fuse)]:
                if on:
                    cmd += [flag]
            t0 = time.time()
            try:
                p = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=CELL_TIMEOUT_S)
                ok = p.returncode == 0 and os.path.exists(fname)
                rec = (json.load(open(fname)) if ok else
                       {"arch": arch, "shape": shape, "mesh": mk, "ok": False,
                        "error": (p.stderr or "")[-2000:]})
            except subprocess.TimeoutExpired:
                rec = {"arch": arch, "shape": shape, "mesh": mk, "ok": False,
                       "error": "timeout"}
            results.append(rec)
            status = ("SKIP" if rec.get("skipped")
                      else "ok" if rec.get("ok") else "FAIL")
            print(f"[{status:4s}] {arch:18s} {shape:12s} {mk:6s} "
                  f"({time.time()-t0:.0f}s)")
            if status == "FAIL":
                print("      ", rec.get("error", "")[-500:].replace("\n", " ")[-300:])
    n_ok = sum(1 for r in results if r.get("ok"))
    n_skip = sum(1 for r in results if r.get("skipped"))
    n_fail = len(results) - n_ok - n_skip
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_fail} FAILED "
          f"of {len(results)} cells")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
