import os
# A CPU-only rehearsal over 512 fake devices: pin this process and every
# per-cell child (they inherit the environment) to the CPU backend so the
# sweep never takes a TPU, then ask for the fake devices.  Both MUST run
# before any jax import (platform and device count lock at first backend
# init); everything below may import jax freely.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) cell, on the single-pod 16x16 mesh and
the 2x16x16 multi-pod mesh:

    lowered  = jax.jit(step, ...).lower(*arg_specs)      # ShapeDtypeStructs
    compiled = lowered.compile()
    memory_analysis(), cost_analysis(), collective-bytes(HLO)

and writes one JSON artifact per cell under experiments/dryrun/. Roofline
terms (benchmarks/roofline.py) and EXPERIMENTS.md §Dry-run read these
artifacts.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --multipod
  python -m repro.launch.dryrun --all            # every cell, subprocess each
  python -m repro.launch.dryrun --list

(note: no ``from __future__`` here — the environment lines must stay first.)
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _measure(cell):
    """lower+compile one cell variant -> (metrics dict, mem stats, compile_s)."""
    from repro.launch import hlo_analysis

    t0 = time.time()
    compiled = cell.lower().compile()
    t_compile = time.time() - t0
    cost = compiled.cost_analysis()
    coll = hlo_analysis.collective_bytes(compiled.as_text())
    metrics = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "collective_total": float(coll.total_bytes),
        "collective_by_kind": dict(coll.by_kind),
        "collective_counts": dict(coll.by_kind_count),
    }
    return metrics, compiled.memory_analysis(), t_compile


def _extrapolate(m1: dict, mu: dict, u: int, n_layers: int) -> dict:
    """XLA prices a while-loop body once. With partial unroll u the body
    appears u times, so body = (F(u) - F(1)) / (u - 1) and the true total is
    F(1) + (L - 1) * body — exact for every additive metric."""
    out = {}
    for k in ("flops", "bytes_accessed", "transcendentals", "collective_total"):
        body = (mu[k] - m1[k]) / (u - 1)
        out[k] = m1[k] + (n_layers - 1) * max(body, 0.0)
    by_kind = {}
    kinds = set(m1["collective_by_kind"]) | set(mu["collective_by_kind"])
    for kk in kinds:
        a = m1["collective_by_kind"].get(kk, 0)
        b = mu["collective_by_kind"].get(kk, 0)
        body = (b - a) / (u - 1)
        by_kind[kk] = a + (n_layers - 1) * max(body, 0.0)
    out["collective_by_kind"] = by_kind
    out["collective_counts"] = m1["collective_counts"]
    return out


def run_one(arch: str, shape: str, multi_pod: bool, out_dir: pathlib.Path) -> dict:
    import jax

    from repro.launch import cells as cells_mod
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_production_mesh

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = cells_mod.build_cell(arch, shape, mesh)
    t_build = time.time() - t0

    t0 = time.time()
    lowered = cell.lower()
    t_lower = time.time() - t0
    del lowered

    metrics1, mem, t_compile = _measure(cell)
    loop_len = cells_mod.layer_loop_length(arch)
    accounting = "exact"
    if loop_len and loop_len > 1:
        u = cells_mod.small_divisor(loop_len)
        cell_u = cells_mod.build_cell(arch, shape, mesh, layer_unroll=u)
        metrics_u, _, t_compile_u = _measure(cell_u)
        metrics = _extrapolate(metrics1, metrics_u, u, loop_len)
        accounting = f"loop-differential(u={u}, L={loop_len})"
        t_compile += t_compile_u
    else:
        metrics = metrics1

    n_chips = mesh.devices.size
    flops = metrics["flops"]
    bytes_accessed = metrics["bytes_accessed"]
    terms = hlo_analysis.roofline_terms(
        hlo_flops=flops,
        hlo_bytes=bytes_accessed,
        collective_bytes_per_device=metrics["collective_total"],
        n_chips=n_chips,
    )

    record = {
        "arch": arch,
        "shape": shape,
        "mesh": list(mesh.devices.shape),
        "mesh_axes": list(mesh.axis_names),
        "n_chips": n_chips,
        "note": cell.note,
        "timings_s": {
            "build": t_build, "lower": t_lower, "compile": t_compile,
        },
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
            "peak_per_device_bytes": (
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        },
        "cost": {
            "flops_per_device": flops,
            "bytes_accessed_per_device": bytes_accessed,
            "transcendentals": metrics["transcendentals"],
            "accounting": accounting,
        },
        "collectives": {
            "per_device_bytes_by_kind": metrics["collective_by_kind"],
            "counts_by_kind": metrics["collective_counts"],
            "per_device_total_bytes": metrics["collective_total"],
        },
        "roofline": terms,
        "jax_version": jax.__version__,
    }

    out_dir.mkdir(parents=True, exist_ok=True)
    tag = "pod2" if multi_pod else "pod1"
    path = out_dir / f"{arch}__{shape}__{tag}.json"
    path.write_text(json.dumps(record, indent=2))
    print(
        f"[dryrun] {arch}/{shape} mesh={record['mesh']} OK  "
        f"compile={t_compile:.1f}s flops/dev={flops:.3e} "
        f"coll/dev={metrics['collective_total']:.3e}B "
        f"dominant={terms['dominant']} [{accounting}]"
    )
    return record


def run_all(multi_pod: bool, out_dir: pathlib.Path, only_missing: bool) -> int:
    """Run every cell in a subprocess (isolation: one bad cell can't take the
    sweep down; also resets XLA memory between 33B-param lowerings)."""
    from repro.configs import base as cfg_base  # light import; no jax devices

    failures = []
    tag = "pod2" if multi_pod else "pod1"
    for arch_id, spec in cfg_base.all_archs().items():
        for cell in spec.shapes:
            path = out_dir / f"{arch_id}__{cell.name}__{tag}.json"
            if only_missing and path.exists():
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch_id, "--shape", cell.name,
            ]
            if multi_pod:
                cmd.append("--multipod")
            print(f"[dryrun] >>> {arch_id}/{cell.name} ({tag})", flush=True)
            r = subprocess.run(cmd)
            if r.returncode != 0:
                failures.append((arch_id, cell.name))
                print(f"[dryrun] FAILED {arch_id}/{cell.name}", flush=True)
    if failures:
        print(f"[dryrun] {len(failures)} failures: {failures}")
        return 1
    print("[dryrun] all cells passed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)

    if args.list:
        from repro.configs import base as cfg_base

        for arch_id, spec in cfg_base.all_archs().items():
            for cell in spec.shapes:
                print(f"{arch_id:24s} {cell.name:16s} {cell.kind}")
        return 0
    if args.all:
        return run_all(args.multipod, out_dir, args.only_missing)
    assert args.arch and args.shape, "--arch and --shape (or --all/--list)"
    run_one(args.arch, args.shape, args.multipod, out_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
