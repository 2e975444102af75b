"""Benchmark harness — one entry per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV lines (plus human-readable tables
on stderr-adjacent stdout sections) and writes the machine-readable perf
trajectory:

- ``BENCH_kernels.json``  — kernel/strategy micro-bench timings
  (op, backend, strategy, MPix/s, wall-ms).
- ``BENCH_imgproc.json``  — the imgproc corpus, the plan-fused vs
  sequential pipeline comparison, and the megapixel tiled/streamed
  throughput cells with the requant PSNR gate.
- ``BENCH_table1.json``   — the EXACT Table-1 error rows, the
  exact-vs-Monte-Carlo sweep timings/speedups, and the full
  design-space Pareto point cloud (exact error x hw cost per
  (kind, N, m, k)).
- ``BENCH_faults.json``   — the fault-injection campaign (PSNR/SSIM
  vs defect kind/bit/rate) and the self-healing recovery cell
  (``repro.resilience``).
- ``BENCH_serve.json``    — the serving-layer traffic cells
  (``repro.serving``): latency/goodput/shed/reject rates per load
  factor, plus the breaker-trip recovery cell.

The JSON files are a TRAJECTORY: every run MERGES into the committed
file instead of overwriting it — records whose identity (all
non-metric fields) matches an existing entry update it in place, new
configurations append, and nothing is ever dropped.  CI enforces this
with ``benchmarks/check_trajectory.py`` (fails the build if a run
loses committed entries).

``--quick`` shrinks every section (1e6 Monte-Carlo samples, small
batches, ONE megapixel tiled cell) — the CI smoke configuration, which
runs under an explicit memory cap and uploads both JSON files as
artifacts so the perf trajectory is recorded per commit.
"""

from __future__ import annotations

import json
import os
import sys

#: Fields that carry measurements; everything else identifies a cell.
METRIC_FIELDS = frozenset({
    "mpix_per_s", "wall_ms", "msamples_per_s", "psnr", "ssim",
    "psnr_stage", "psnr_fused", "psnr_delta_db", "bit_identical",
    "seconds", "speedup", "gmac_per_s",
    # exact error analytics + hw cost model (BENCH_table1/BENCH_mac)
    "med", "mred", "nmed", "er", "wce",
    "energy_fj", "delay_ns", "power_uw", "transistors",
    # timing-quality and telemetry metrics (repro.obs instrumentation)
    "wall_ms_spread", "jitter_pct", "overhead_pct",
    "p50_ms", "p95_ms", "p99_ms",
    # fault-injection campaign + self-healing recovery (BENCH_faults)
    "psnr_nofallback", "psnr_fallback", "recovery_db",
    "degrade_level", "trips", "batches_degraded",
    # serving traffic cells (BENCH_serve)
    "completed", "goodput_mpix_per_s", "reject_rate", "shed_rate",
    "deadline_miss_rate", "retries", "breaker_trips",
    # integrity detection campaign (BENCH_faults, op=fault_detection)
    "detected", "cells", "coverage", "detection_latency_s",
    "false_positive_rate",
})

#: Fields that describe the MACHINE a record was measured on.  They are
#: provenance, not identity: excluded from ``record_key`` so a record
#: stamped on one host updates the committed cell measured on another
#: instead of forking the trajectory — and so records written before
#: stamping existed merge cleanly with stamped re-measurements.
PROVENANCE_FIELDS = frozenset({
    "host_platform", "jax_version", "device_kind",
})


def provenance() -> dict:
    """The machine stamp added to every record at dump time."""
    import platform

    import jax
    return {
        "host_platform": platform.platform(),
        "jax_version": jax.__version__,
        "device_kind": jax.devices()[0].device_kind,
    }


def record_key(rec: dict):
    """The identity of a trajectory record: its non-metric,
    non-provenance fields."""
    return tuple(sorted((k, json.dumps(v, sort_keys=True))
                        for k, v in rec.items()
                        if k not in METRIC_FIELDS
                        and k not in PROVENANCE_FIELDS))


def merge_records(existing, new):
    """Append/update semantics: records in ``new`` replace same-key
    entries of ``existing`` (fresher measurement of the same cell) and
    otherwise append.  No key of ``existing`` is ever lost."""
    merged = {record_key(r): r for r in existing}
    for rec in new:
        merged[record_key(rec)] = rec
    return list(merged.values())


def _dump(path: str, records) -> None:
    stamp = provenance()
    records = [{**rec, **stamp} for rec in records]
    existing = []
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    merged = merge_records(existing, records)
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    print(f"wrote {path} ({len(existing)} -> {len(merged)} records, "
          f"{len(records)} measured this run)")


def main() -> None:
    from repro.ioutil import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    quick = "--quick" in sys.argv
    from benchmarks import (bench_faults, bench_imgproc, bench_kernels,
                            bench_mac, bench_serve, fig5_image,
                            fig6_tradeoff, roofline, table1_error,
                            table1_hw)
    lines = []
    lines += table1_hw.run()
    t1_lines, t1_records = table1_error.run(
        n_samples=1_000_000 if quick else 10_000_000, validate=True,
        mc_rounds=1 if quick else 2)
    lines += t1_lines
    lines += fig5_image.run(size=256 if quick else 512)
    lines += fig6_tradeoff.run(size=256)
    par_lines, par_records = fig6_tradeoff.pareto(
        max_lsm=8 if quick else None)
    lines += par_lines
    pmul_lines, pmul_records = fig6_tradeoff.pareto_mul()
    lines += pmul_lines
    mac_lines, mac_records = bench_mac.run(quick=quick)
    lines += mac_lines
    img_lines, img_records = bench_imgproc.run(
        n_images=4 if quick else 8, size=64 if quick else 128,
        mega_images=1 if quick else 4,
        gate_kinds=("haloc_axa",) if quick else None)
    lines += img_lines
    kern_lines, kern_records = bench_kernels.run()
    lines += kern_lines
    flt_lines, flt_records = bench_faults.run(quick=quick)
    lines += flt_lines
    srv_lines, srv_records = bench_serve.run(quick=quick)
    lines += srv_lines
    lines += roofline.run()
    _dump("BENCH_kernels.json", kern_records)
    _dump("BENCH_faults.json", flt_records)
    _dump("BENCH_serve.json", srv_records)
    _dump("BENCH_imgproc.json", img_records)
    _dump("BENCH_table1.json", t1_records + par_records)
    _dump("BENCH_mac.json", pmul_records + mac_records)
    print("\n== CSV (name,us_per_call,derived) ==")
    for ln in lines:
        print(ln)


if __name__ == "__main__":
    main()
