"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without TPU hardware: for the
single-pod (16, 16) mesh and the 2-pod (2, 16, 16) mesh, every assigned
architecture x input-shape cell must ``jit(step).lower(**specs).compile()``
under the production shardings.  Failures here (sharding mismatch, OOM at
compile, unsupported collective) are bugs in the system.

Outputs, per cell (cached incrementally in results/dryrun/*.json):

* ``memory_analysis()``   — per-device bytes (args/outputs/temps) — fit proof
* ``cost_analysis()``     — HLO FLOPs + bytes for the roofline terms
* collective schedule     — op counts + operand bytes parsed from the
  post-SPMD HLO (all-gather/all-reduce/reduce-scatter/all-to-all/permute)
* the 3-term roofline summary (core.roofline)

Usage:
    python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k
    python -m repro.launch.dryrun --all [--mesh both] [--seq-par]

``main()`` splits the host CPU into 512 devices through ``XLA_FLAGS`` before
JAX starts its backend; importing this module changes no flag.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Dict


from repro.configs.base import (ARCH_IDS, SHAPES, applicable_shapes,
                                get_config)
from repro.core import roofline as rl
from repro.launch.common import build_cell
from repro.launch.mesh import make_production_mesh
from repro.api.options import options as sma_options
from repro.models.layers import Runtime


def _cost_dict(compiled) -> Dict[str, float]:
    """Normalize Compiled.cost_analysis() across JAX versions (list/dict)."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); fwd-only = 2*N*D."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def _slstm_scan_correction(cfg, shape, mesh) -> float:
    """Analytic per-device FLOPs for sLSTM *time* scans.

    The sLSTM step recurrence is a while loop over seq_len that no probe can
    unroll (4096+ iterations); its body FLOPs (recurrent gate matmul +
    elementwise cell math) are added analytically.  Training multiplies by 4
    (forward + remat recompute + ~2x backward).
    """
    n_slstm = sum(1 for b in cfg.block_pattern if b == "slstm")
    if n_slstm == 0 or shape.kind == "decode":
        return 0.0
    sizes = dict(mesh.shape)
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    d = cfg.d_model
    h = cfg.num_heads
    dh = d // h
    b_local = shape.global_batch / dp
    per_step = 2.0 * b_local * h * dh * (4 * dh) + 20.0 * b_local * d
    mult = 4.0 if shape.kind == "train" else 1.0
    return per_step * shape.seq_len * n_slstm * cfg.num_groups * mult


def _mlstm_scan_correction(cfg, shape, mesh) -> float:
    """Analytic per-device FLOPs for the chunks a probe's mLSTM scan skips.

    The chunkwise-mLSTM lax.scan stays rolled even in probes (unrolling
    7 blocks x 32-256 chunk bodies is compile-prohibitive), so cost_analysis
    counts ONE chunk per block.  The remaining (n_chunks - 1) chunks are
    added analytically from the chunkwise algebra (S = qk^T, (S.D)v, qC,
    state update); training multiplies by 4 (fwd + remat + ~2x bwd).
    """
    n_mlstm = sum(1 for b in cfg.block_pattern if b == "mlstm")
    if n_mlstm == 0 or shape.kind == "decode":
        return 0.0
    sizes = dict(mesh.shape)
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    inner = int(cfg.d_model * cfg.mlstm_proj_factor)
    h = cfg.num_heads
    dh = inner // h
    L = min(cfg.mlstm_chunk, shape.seq_len)
    n_chunks = -(-shape.seq_len // L)
    b_local = shape.global_batch / dp
    per_chunk = (2.0 * b_local * h * L * L * dh      # S = q k^T
                 + 2.0 * b_local * h * L * L * dh    # (S.D) v
                 + 4.0 * b_local * h * L * dh * dh   # q C0 + state update
                 + 12.0 * b_local * h * L * (L + dh))  # gates/decay/norm
    mult = 4.0 if shape.kind == "train" else 1.0
    return (per_chunk * (n_chunks - 1) * n_mlstm * cfg.num_groups * mult)


def _probe(cfg, shape, mesh, n_groups: int, *, sequence_parallel: bool,
           remat: bool, attention_chunk: int = 1024,
           remat_policy: str = "full") -> Dict[str, float]:
    """Small unrolled compile for exact per-layer cost accounting."""
    cfg_n = dataclasses.replace(cfg, num_groups=n_groups)
    rt = Runtime(remat=remat,
                 sequence_parallel=sequence_parallel, scan_unroll=True,
                 attention_chunk=attention_chunk,
                 remat_policy=remat_policy)
    fn, args = build_cell(cfg_n, shape, mesh, rt=rt,
                          sequence_parallel=sequence_parallel, remat=remat)
    # The dry-run always lowers the SIMD-substrate (xla) paths: the CPU
    # backend cannot lower Mosaic kernels, and accounting must stay
    # mesh-representative.  Ambient options scope it for this trace only.
    with mesh, sma_options(backend="xla"):
        compiled = fn.lower(*args).compile()
    cost = _cost_dict(compiled)
    coll = rl.collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        **{f"coll_{k}": v for k, v in coll.items()},
    }


def extrapolated_costs(cfg, shape, mesh, *, sequence_parallel: bool,
                       remat: bool,
                       attention_chunk: int = 1024,
                       remat_policy: str = "full") -> Dict[str, float]:
    """Exact totals via L=1 / L=2 unrolled probes: t(L) = t1 + (L-1)(t2-t1).

    XLA's cost_analysis counts a while-loop body once; the layer-group scan
    (and inner attention/mLSTM chunk scans) therefore undercount by the trip
    count.  The probes unroll every scan at 1 and 2 groups; the difference is
    one group's exact cost and extrapolation over num_groups is exact because
    groups are homogeneous.
    """
    p1 = _probe(cfg, shape, mesh, 1, sequence_parallel=sequence_parallel,
                remat=remat, attention_chunk=attention_chunk,
                remat_policy=remat_policy)
    p2 = _probe(cfg, shape, mesh, 2, sequence_parallel=sequence_parallel,
                remat=remat, attention_chunk=attention_chunk,
                remat_policy=remat_policy)
    L = cfg.num_groups
    out = {}
    for key in p1:
        out[key] = p1[key] + (L - 1) * (p2[key] - p1[key])
    out["flops"] += _slstm_scan_correction(cfg, shape, mesh)
    out["flops"] += _mlstm_scan_correction(cfg, shape, mesh)
    out["per_group_flops"] = p2["flops"] - p1["flops"]
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             sequence_parallel: bool = False,
             remat: bool = True,
             attention_chunk: int = 1024,
             remat_policy: str = "full",
             tag: str = "") -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mesh_name = "2x16x16" if multi_pod else "16x16"

    t0 = time.time()
    rt = Runtime(remat=remat,
                 sequence_parallel=sequence_parallel,
                 attention_chunk=attention_chunk,
                 remat_policy=remat_policy)
    fn, args = build_cell(cfg, shape, mesh, rt=rt,
                          sequence_parallel=sequence_parallel, remat=remat)
    with mesh, sma_options(backend="xla"):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled)
    hlo = compiled.as_text()
    coll = rl.collective_bytes_from_hlo(hlo)
    bytes_per_device = (
        (getattr(mem, "argument_size_in_bytes", 0)
         + getattr(mem, "output_size_in_bytes", 0)
         + getattr(mem, "temp_size_in_bytes", 0)
         - getattr(mem, "alias_size_in_bytes", 0)))

    # Exact FLOP/byte/collective totals via unrolled L=1/L=2 probes.
    # NOTE: cost_analysis and the HLO text describe the PER-DEVICE SPMD
    # program, so the roofline divides by per-chip peaks (chips=1) and the
    # useful-FLOPs numerator is MODEL_FLOPS / chips.
    ex = extrapolated_costs(cfg, shape, mesh,
                            sequence_parallel=sequence_parallel, remat=remat,
                            attention_chunk=attention_chunk,
                            remat_policy=remat_policy)
    ex_coll = {k[5:]: v for k, v in ex.items() if k.startswith("coll_")}

    terms = rl.RooflineTerms(
        flops=ex["flops"],
        hbm_bytes=ex["bytes"],
        collective_bytes=ex_coll.get("total", 0.0),
        chips=1,
        model_flops=model_flops_for(cfg, shape) / chips,
        collectives=ex_coll,
        bytes_per_device=bytes_per_device,
    )
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": chips,
        "kind": shape.kind,
        "compile_seconds": round(compile_s, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            "bytes_per_device": bytes_per_device,
        },
        "cost": {k: cost.get(k, 0.0)
                 for k in ("flops", "bytes accessed", "transcendentals")},
        # body-once collective *schedule* of the real (scanned) executable:
        "collectives": coll,
        # probe-extrapolated per-step collective totals (roofline input):
        "collectives_extrapolated": ex_coll,
        "cost_extrapolated": {"flops": ex["flops"], "bytes": ex["bytes"],
                              "per_group_flops": ex["per_group_flops"]},
        "roofline": terms.summary(),
        "options": {"sequence_parallel": sequence_parallel, "remat": remat},
        "status": "ok",
    }
    if tag:
        record["tag"] = tag
    return record


def cell_path(arch: str, shape: str, mesh_name: str, tag: str = "") -> str:
    suffix = f"__{tag}" if tag else ""
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_name}{suffix}.json")


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (or --all)")
    ap.add_argument("--shape", default=None, help="shape cell name")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--seq-par", action="store_true",
                    help="Megatron-SP activation sharding")
    ap.add_argument("--attn-chunk", type=int, default=1024,
                    help="XLA-path online-softmax KV chunk size")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--tag", default="", help="variant tag for results file")
    ap.add_argument("--force", action="store_true", help="ignore cache")
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([args.shape] if args.shape
                  else list(applicable_shapes(cfg)))
        for shape_name in shapes:
            if shape_name not in applicable_shapes(cfg):
                print(f"SKIP {arch} x {shape_name}: inapplicable "
                      f"(full attention at 500k — see DESIGN.md)")
                n_skip += 1
                continue
            for multi_pod in meshes:
                mesh_name = "2x16x16" if multi_pod else "16x16"
                path = cell_path(arch, shape_name, mesh_name, args.tag)
                if os.path.exists(path) and not args.force:
                    print(f"CACHED {arch} x {shape_name} x {mesh_name}")
                    n_ok += 1
                    continue
                print(f"RUN    {arch} x {shape_name} x {mesh_name} ...",
                      flush=True)
                try:
                    rec = run_cell(arch, shape_name, multi_pod,
                                   sequence_parallel=args.seq_par,
                                   remat=not args.no_remat,
                                   attention_chunk=args.attn_chunk,
                                   remat_policy=args.remat_policy,
                                   tag=args.tag)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    if os.path.exists(path + ".fail"):
                        os.remove(path + ".fail")  # stale failure marker
                    r = rec["roofline"]
                    print(f"  ok in {rec['compile_seconds']}s | "
                          f"bytes/dev={rec['memory']['bytes_per_device']/1e9:.2f}GB | "
                          f"dominant={r['dominant']} | "
                          f"roofline_frac={r['roofline_fraction']:.3f}",
                          flush=True)
                    n_ok += 1
                except Exception as e:  # noqa: BLE001 - report and continue
                    n_fail += 1
                    err = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "fail",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    with open(path + ".fail", "w") as f:
                        json.dump(err, f, indent=1)
                    print(f"  FAIL: {type(e).__name__}: {str(e)[:300]}",
                          flush=True)
    print(f"\ndry-run summary: ok={n_ok} fail={n_fail} "
          f"documented-skips={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
