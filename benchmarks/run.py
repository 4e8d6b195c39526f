"""Benchmark harness entry point: one function per paper table + the JAX
measured benchmarks.  Prints ``name,us_per_call,
derived`` CSV rows per the repo contract, then the table reproductions.

``--emit-json DIR`` instead runs the serving/ingress regression harness
and writes machine-readable ``BENCH_serve.json`` and
``BENCH_ingress.json`` (cls/s per path and bucket, the ingress,
dispatch, wait and fetch latency split) so the perf trajectory is comparable across PRs; CI
smoke-runs it at ``--tiny`` geometry and uploads the artifact.

Run:  PYTHONPATH=src python -m benchmarks.run [--quick]
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.run --emit-json bench_out [--tiny]
"""

from __future__ import annotations

import argparse
import json
import os
import time

from repro.launch.compile_cache import enable_compile_cache


def _mesh_rows(*, tiny: bool) -> list:
    """Per-device-count ``serve_mesh`` rows for BENCH_serve.json.

    Runs in this process, over the devices it has: counts beyond them
    are skipped.  One process holds the chip, so no child is started;
    on CPU, run the whole command with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get the
    1/2/8 sweep.
    """
    from benchmarks.bench_serve import bench_serve_mesh

    return bench_serve_mesh(
        device_counts=(1, 2, 8),
        buckets=(8,) if tiny else (8, 64),
        n_requests=3 if tiny else 10,
        tiny=tiny,
    )


def _csv(rows):
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


def _json_payload(rows, *, tiny: bool) -> dict:
    """The cross-PR regression schema: stable row names + typed fields."""
    import jax

    return {
        "schema": 1,
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "geometry": "tiny" if tiny else "paper",
        "rows": [
            {
                "name": r["name"],
                "us_per_call": r["us_per_call"],
                "derived": r["derived"],
                **({"fields": r["fields"]} if "fields" in r else {}),
            }
            for r in rows
        ],
    }


def emit_json(out_dir: str, *, tiny: bool) -> None:
    """Write BENCH_serve.json + BENCH_ingress.json to ``out_dir``."""
    from benchmarks.bench_ingress import bench_ingress
    from benchmarks.bench_serve import bench_serve, bench_sparsity_sweep
    from benchmarks.bench_service import bench_service

    os.makedirs(out_dir, exist_ok=True)
    buckets = (1, 8) if tiny else (1, 8, 64)
    # Tiny calls cost microseconds — the run time is all compiles — so
    # high rep counts are free and keep the trajectory gate's numbers
    # out of single-timer-tick noise.
    reps = 20 if tiny else 10

    # The registered fused path and its sparse twin, side by side, so
    # the JSON shows the per-bucket sparse win (or loss) every PR.
    serve_rows = bench_serve(
        buckets=buckets, n_requests=reps, tiny=tiny,
        paths=("fused", "fused_sparse"),
    )
    serve_rows += bench_sparsity_sweep(
        active_fractions=(0.25, 1.0) if tiny else (0.0625, 0.25, 0.5, 1.0),
        pairs=(("fused", "fused_sparse"),),
        bucket=max(buckets),
        n_requests=reps,
        tiny=tiny,
    )
    # Per-device-count sharded-serving rows, over this process's devices.
    serve_rows += _mesh_rows(tiny=tiny)
    serve_rows += bench_service(
        rates=(500.0,) if tiny else (500.0, 2000.0),
        delays_us=(200.0,),
        raw_rates=(1000.0,) if tiny else (2000.0,),
        n_requests=60 if tiny else 300,
        tiny=tiny,
    )
    with open(os.path.join(out_dir, "BENCH_serve.json"), "w") as f:
        json.dump(_json_payload(serve_rows, tiny=tiny), f, indent=2)

    ingress_rows = bench_ingress(
        methods=("threshold",) if tiny else ("threshold", "adaptive", "none"),
        buckets=buckets,
        n_iter=reps,
        tiny=tiny,
    )
    with open(os.path.join(out_dir, "BENCH_ingress.json"), "w") as f:
        json.dump(_json_payload(ingress_rows, tiny=tiny), f, indent=2)

    # Trajectory artifact: the committed cross-PR rows plus an
    # uncommitted "current" row distilled from this run's serve sweep,
    # so the artifact shows this run against history at a glance.  The
    # committed file itself is only updated via
    # ``benchmarks/trajectory.py --update`` (see its docstring).
    from benchmarks import trajectory as traj

    current = {
        "pr": "current (uncommitted)",
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": __import__("jax").default_backend(),
        "geometries": {
            "tiny" if tiny else "paper": {
                "best_cls_per_s": traj.distill_serve_rows(serve_rows)
            }
        },
    }
    with open(os.path.join(out_dir, "BENCH_trajectory.json"), "w") as f:
        json.dump(
            traj.upsert_row(traj.load_trajectory(), current),
            f, indent=2, sort_keys=True,
        )
    for name in ("BENCH_serve.json", "BENCH_ingress.json",
                 "BENCH_trajectory.json"):
        print(f"wrote {os.path.join(out_dir, name)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip wall-clock benches")
    ap.add_argument(
        "--emit-json", metavar="DIR", default=None,
        help="write BENCH_serve.json/BENCH_ingress.json to DIR and exit",
    )
    ap.add_argument(
        "--tiny", action="store_true",
        help="CI-smoke geometry for --emit-json (small clause pool/patches)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    if args.emit_json:
        emit_json(args.emit_json, tiny=args.tiny)
        return

    print("name,us_per_call,derived")

    # --- measured JAX benchmarks -----------------------------------------
    if not args.quick:
        from benchmarks.bench_inference import bench_inference_paths, csrf_skip_stats

        _csv(bench_inference_paths())
        stats = csrf_skip_stats()
        print(
            f"csrf_skip_stats,0,"
            f"tile_skip={stats['tile_skip_fraction']:.2f} "
            f"clausewise_saving={stats['clausewise_eval_saving']:.2f} "
            f"fired={stats['fired_fraction']:.2f}"
        )
        from benchmarks.bench_train import bench_tm_train

        _csv(bench_tm_train())

        from benchmarks.bench_serve import bench_serve

        _csv(bench_serve(buckets=(8, 64), n_requests=5))

    # --- Table II: ASIC characteristics (analytic model vs paper) --------
    from benchmarks.tables import (
        table2_rows,
        table3_rows,
        table4_rows,
        table5_rows,
        table6_rows,
    )

    print("\n== Table II: ConvCoTM ASIC characteristics (model vs paper) ==")
    for r in table2_rows():
        print(
            f"  {r['clock_mhz']:5.1f} MHz {r['vdd']:.2f} V | "
            f"P {r['power_mw_model']:7.3f} / {r['power_mw_paper']:7.3f} mW | "
            f"EPC {r['epc_nj_model']:6.2f} / {r['epc_nj_paper']:6.2f} nJ | "
            f"rate {r['rate_model']:8.0f} / {r['rate_paper']:8.0f} /s"
        )
        print(f"    (model vs paper; latency model {r['latency_us_model']} us)")

    print("\n== Table III: envisaged CIFAR-10 TM-Composites scale-up ==")
    for r in table3_rows():
        print(f"  {r['parameter']:32s} model={r['model']} paper={r['paper']}")

    print("\n== Table IV: MNIST ULP accelerator comparison ==")
    for r in table4_rows():
        print(
            f"  {r['design']:45s} {r['type']:18s} acc={r['mnist_acc_pct']}% "
            f"rate={r['cls_per_s']} EPC={r['epc_nj']} nJ"
        )

    print("\n== Table V: CIFAR-10 ULP accelerator comparison ==")
    for r in table5_rows():
        acc = f"{r['cifar10_acc_pct']}%" if r["cifar10_acc_pct"] else "n/a"
        fps = r["fps"] if r["fps"] else "n/a"
        epc = f"{r['epc_uj']} uJ" if r["epc_uj"] else "n/a"
        print(f"  {r['design']:48s} {r['algorithm']:10s} acc={acc} rate={fps} EPC={epc}")

    print("\n== Table VI: TM hardware overview ==")
    for r in table6_rows():
        epc = f"{r['epc_j']*1e9:.1f} nJ" if r["epc_j"] else "n/a"
        rate = f"{r['cls_per_s']:,}" if r["cls_per_s"] else "n/a"
        print(f"  {r['design']:45s} {r['algorithm']:10s} {r['operation']:12s} "
              f"rate={rate} EPC={epc}")


if __name__ == "__main__":
    main()
