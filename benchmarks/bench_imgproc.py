"""imgproc corpus + pipeline + megapixel-throughput benchmark.

Four sections:

1. **Corpus**: {Table-I adder kinds} x {batched image workloads,
   pipelines included} on a synthetic batch, scored against the ideal
   float references (PSNR/SSIM + warm-call throughput).
2. **Plan fusion**: every stock pipeline (``repro.imgproc.plan``)
   timed as ONE compiled dispatch vs the same stages run individually
   through the workload registry (one jit dispatch + host round-trip
   per stage) — the fused/sequential MPix/s pair is the plan API's
   headline number.
3. **Megapixel**: the blur→sharpen→downsample chain on a megapixel
   batch — the PR-3 plan-fused path (stage requant, untiled) vs the
   integer-domain fast path (``requant="fused"`` + halo-aware tiling +
   ``strategy="auto"``), the per-Table-1-kind PSNR gate between the
   two requant modes, and the async double-buffered stream runner at
   several depths.  The acceptance bar lives here: fast path >= 2x the
   PR-3 MPix/s with the gate within 0.1 dB for every kind.
4. **Telemetry overhead**: the ``repro.obs`` layer measured on the
   fast path — pristine jitted callable vs instrumented-but-disabled
   vs fully enabled — plus a stream under a profiler capture that
   writes the ``OBS_profile/`` / ``OBS_metrics.json`` artifacts.
   ``benchmarks/check_overhead.py`` bounds the disabled overhead.

All timing through ``benchmarks.timing.timeit_jax`` (compile excluded,
device-synced, best-of-rounds).  ``--quick`` (via benchmarks/run.py)
shrinks the batch and runs ONE megapixel cell; standalone runs use
8 x 128x128 and the full 4 x 1024x1024 sweep.  Returns
(csv_lines, json_records); records go to ``BENCH_imgproc.json``
(merged into the committed trajectory, never overwritten).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.timing import timeit_jax
from repro.imgproc import (PIPELINES, compile_pipeline, compile_tiled,
                           format_table, fused_psnr_gate, get_workload,
                           run_corpus, run_streaming, synthetic_batch)

#: The megapixel benchmark's pipeline (the acceptance chain) and tile.
MEGA_STAGES = PIPELINES["pipe_blur_sharpen_down"]
MEGA_TILE = (256, 256)

#: Where the telemetry section writes its profiler capture.
PROFILE_DIR = "OBS_profile"


def _pipeline_records(batches, kind: str, backend: str,
                      strategy) -> Tuple[List[str], List[Dict]]:
    """Fused (one compiled dispatch) vs sequential (one workload call,
    with its jit dispatch and host round-trip, per stage), per stock
    pipeline and batch size.  The small batch is the dispatch-bound
    regime the plan API targets; the large one the compute-bound end."""
    lines: List[str] = []
    records: List[Dict] = []
    for batch in batches:
        mpix = batch.size / 1e6
        shape = "x".join(map(str, batch.shape))
        x = jnp.asarray(batch)
        print(f"\n== plan fusion (batch {shape}, kind={kind}, "
              f"backend={backend}) ==")
        for name, stages in PIPELINES.items():
            pipe = compile_pipeline(stages, kind=kind, backend=backend,
                                    strategy=strategy)

            def sequential(b):
                y = b
                for st in stages:
                    op, kw = (st, {}) if isinstance(st, str) else st
                    y = get_workload(op).run(y, kind=kind, backend=backend,
                                             strategy=strategy, **kw)
                return y

            # Bit-identity first: the plan must equal its unfused stages.
            np.testing.assert_array_equal(np.asarray(pipe(x)),
                                          sequential(batch))
            t_fused = timeit_jax(pipe, x, reps=10, rounds=5)
            t_seq = timeit_jax(sequential, batch, reps=10, rounds=5)
            speed = t_seq / t_fused
            print(f"  {name:24s} fused {mpix / t_fused:8.1f} MPix/s   "
                  f"sequential {mpix / t_seq:8.1f} MPix/s   "
                  f"({speed:.2f}x, bit-identical)")
            lines.append(f"imgproc/{name}/fused@{shape},"
                         f"{t_fused * 1e6:.0f},MPix/s="
                         f"{mpix / t_fused:.2f};vs_sequential="
                         f"{speed:.2f}x")
            for label, t in (("plan-fused", t_fused),
                             ("sequential", t_seq)):
                records.append({
                    "op": f"pipeline/{name}", "backend": backend,
                    "strategy": label, "batch": shape,
                    "mpix_per_s": mpix / t, "wall_ms": t * 1e3,
                })
    return lines, records


def _mega_configs():
    """(label, requant, strategy, tile) — the PR-3 baseline first."""
    return (("pr3-plan-fused", "stage", "reference", None),
            ("fused-requant", "fused", "reference", None),
            ("fused-tiled-auto", "fused", "auto", MEGA_TILE))


def _megapixel_records(n_images: int, size: int, backend: str, kind: str,
                       gate_kinds: Sequence[str],
                       ) -> Tuple[List[str], List[Dict]]:
    """Section 3: megapixel throughput + the requant PSNR gate."""
    batch = synthetic_batch(n_images, size)
    x = jnp.asarray(batch)
    mpix = batch.size / 1e6
    shape = "x".join(map(str, batch.shape))
    lines: List[str] = []
    records: List[Dict] = []
    print(f"\n== megapixel ({shape}, kind={kind}, backend={backend}, "
          f"chain={'->'.join(MEGA_STAGES)}) ==")
    times = {}
    for label, requant, strategy, tile in _mega_configs():
        pipe = compile_pipeline(MEGA_STAGES, kind=kind, backend=backend,
                                strategy=strategy, requant=requant)
        fn = pipe if tile is None else compile_tiled(pipe, batch.shape,
                                                     tile=tile)
        t = timeit_jax(fn, x, reps=2, rounds=4)
        times[label] = t
        speed = times["pr3-plan-fused"] / t
        print(f"  {label:20s} {mpix / t:8.1f} MPix/s   "
              f"({speed:.2f}x vs PR-3, jitter {t.jitter:.1%})")
        lines.append(f"imgproc/mega/{label}@{shape},{t * 1e6:.0f},"
                     f"MPix/s={mpix / t:.2f};vs_pr3={speed:.2f}x")
        records.append({
            "op": "mega/pipe_blur_sharpen_down", "backend": backend,
            "strategy": strategy, "requant": requant, "kind": kind,
            "batch": shape, "config": label,
            "tile": None if tile is None else list(tile),
            "mpix_per_s": mpix / t, "wall_ms": t * 1e3,
            "wall_ms_spread": t.spread * 1e3,
            "jitter_pct": t.jitter * 100,
        })

    # The requant PSNR gate, per adder kind: the fused+tiled fast path
    # must stay within 0.1 dB of the stage-requant result against the
    # ideal float reference — scored by THE gate implementation
    # (`repro.imgproc.fused_psnr_gate`, fused side tiled), which also
    # reports the stronger bit-identity the built-in chains achieve.
    print(f"  requant gate ({shape}): PSNR stage vs fused+tiled, dB")
    for k in gate_kinds:
        gate = fused_psnr_gate(MEGA_STAGES, batch, kind=k,
                               backend=backend, strategy="auto",
                               tile=MEGA_TILE)
        assert gate.admissible(), (k, gate)
        print(f"    {k:10s} stage={gate.psnr_stage:6.2f}  "
              f"fused={gate.psnr_fused:6.2f}  "
              f"delta={gate.delta_db:+.4f}  "
              f"bit_identical={gate.bit_identical}")
        records.append({
            "op": "mega/requant_gate", "backend": backend, "kind": k,
            "batch": shape, "psnr_stage": gate.psnr_stage,
            "psnr_fused": gate.psnr_fused,
            "psnr_delta_db": gate.delta_db,
            "bit_identical": gate.bit_identical,
        })

    # The async double-buffered stream runner: a steady stream of
    # batches through the fast path, naive blocking loop vs pipelined.
    n_stream = 6
    stream = [synthetic_batch(max(1, n_images // 2), size, seed=11 + i)
              for i in range(n_stream)]
    pipe = compile_pipeline(MEGA_STAGES, kind=kind, backend=backend,
                            strategy="auto", requant="fused")
    tiled = compile_tiled(pipe, stream[0].shape, tile=MEGA_TILE)
    fn = lambda b: tiled(jnp.asarray(b))  # noqa: E731
    np.asarray(fn(stream[0]))  # warm the jit/tile caches untimed
    for depth in (1, 2):
        best = None
        for _ in range(3):
            r = run_streaming(fn, stream, depth=depth)
            best = r if best is None or r.seconds < best.seconds else best
        label = "blocking" if depth == 1 else f"depth{depth}"
        stream_shape = "x".join(map(str, stream[0].shape))
        print(f"  stream {label:9s} {best.mpix_per_s:8.1f} MPix/s "
              f"({n_stream} batches of {stream[0].shape}, "
              f"p50/p95/p99 {best.p50_s * 1e3:.1f}/"
              f"{best.p95_s * 1e3:.1f}/{best.p99_s * 1e3:.1f} ms)")
        lines.append(f"imgproc/mega/stream-{label}@{stream_shape},"
                     f"{best.seconds / n_stream * 1e6:.0f},"
                     f"MPix/s={best.mpix_per_s:.2f};"
                     f"p95_ms={best.p95_s * 1e3:.2f}")
        records.append({
            "op": "mega/stream", "backend": backend, "strategy": "auto",
            "requant": "fused", "kind": kind, "depth": depth,
            "batch": "x".join(map(str, stream[0].shape)),
            "mpix_per_s": best.mpix_per_s,
            "wall_ms": best.seconds * 1e3,
            "p50_ms": best.p50_s * 1e3,
            "p95_ms": best.p95_s * 1e3,
            "p99_ms": best.p99_s * 1e3,
        })
    return lines, records


def _telemetry_records(size: int, backend: str, kind: str,
                       ) -> Tuple[List[str], List[Dict]]:
    """Section 4: the cost of the telemetry layer itself, measured.

    Three configs on the fused+tiled fast path over one ``size``-square
    image, same process, same compiled executor:

    - ``baseline-raw``: the pristine jitted callable (``tiled.raw``) —
      no dispatch wrapper, no flag branch.  The true hook-free cost.
    - ``telemetry-off``: the instrumented dispatch wrapper with the
      module flag OFF — what every normal run pays.  The acceptance
      bound (``benchmarks/check_overhead.py``) is its ``overhead_pct``
      against baseline-raw: <= 2%, asserted from these records so the
      check is same-process/same-machine and immune to host drift.
    - ``telemetry-on``: metrics enabled, so every span is built (it
      records only under a profiler capture) — the price of a
      telemetry run (informational; no bound).

    The enabled config then streams a few batches with telemetry on
    under a profiler capture and writes the artifacts next to the BENCH
    json: ``OBS_profile/`` (the capture, spans and operations on one
    clock; open it in TensorBoard's profile plugin or its trace in
    Perfetto) and ``OBS_metrics.json`` (counters/gauges/histograms +
    cache stats).
    """
    from repro import obs
    batch = synthetic_batch(1, size)
    x = jnp.asarray(batch)
    mpix = batch.size / 1e6
    shape = "x".join(map(str, batch.shape))
    pipe = compile_pipeline(MEGA_STAGES, kind=kind, backend=backend,
                            strategy="auto", requant="fused")
    tiled = compile_tiled(pipe, batch.shape, tile=MEGA_TILE)
    lines: List[str] = []
    records: List[Dict] = []
    print(f"\n== telemetry overhead ({shape}, fused+tiled fast path) ==")
    configs = (("baseline-raw", tiled.raw, False),
               ("telemetry-off", tiled, False),
               ("telemetry-on", tiled, True))
    # Interleave the configs' rounds: frequency scaling and host
    # contention drift on the tens-of-ms scale, so measuring each
    # config's rounds back-to-back would let that drift masquerade as
    # (or mask) the sub-percent wrapper overhead.  Round-robin puts
    # every config under the same noise, and best-of-rounds does the
    # rest.  One merged TimingResult per config at the end.
    rounds_per = {label: [] for label, _, _ in configs}
    for label, fn, flag in configs:  # untimed warm-up, all configs
        with obs.telemetry(flag):
            timeit_jax(fn, x, reps=1, rounds=1, warmup=1)
    for _ in range(6):
        for label, fn, flag in configs:
            with obs.telemetry(flag):
                t1 = timeit_jax(fn, x, reps=4, rounds=1, warmup=0)
            rounds_per[label].extend(t1.rounds)
    times = {}
    for label, fn, flag in configs:
        from benchmarks.timing import TimingResult
        t = TimingResult(rounds_per[label])
        times[label] = t
        overhead = (float(t) / float(times["baseline-raw"]) - 1.0) * 100
        print(f"  {label:14s} {mpix / t:8.1f} MPix/s   "
              f"overhead {overhead:+5.2f}%   jitter {t.jitter:.1%}")
        lines.append(f"imgproc/mega/telemetry-{label}@{shape},"
                     f"{t * 1e6:.0f},MPix/s={mpix / t:.2f};"
                     f"overhead={overhead:+.2f}%")
        records.append({
            "op": "mega/telemetry", "backend": backend,
            "strategy": "auto", "requant": "fused", "kind": kind,
            "batch": shape, "config": label, "tile": list(MEGA_TILE),
            "mpix_per_s": mpix / t, "wall_ms": t * 1e3,
            "wall_ms_spread": t.spread * 1e3,
            "jitter_pct": t.jitter * 100,
            "overhead_pct": overhead,
        })

    # A short telemetry-enabled stream under a profiler capture: the
    # profiling artifacts CI uploads, next to the BENCH json files.
    obs.reset_all()
    stream = [synthetic_batch(1, size, seed=31 + i) for i in range(4)]
    with obs.telemetry(True), jax.profiler.trace(PROFILE_DIR):
        res = run_streaming(lambda b: tiled(jnp.asarray(b)), stream,
                            depth=2)
    obs.write_metrics("OBS_metrics.json")
    print(f"  traced stream: {res.mpix_per_s:.1f} MPix/s, "
          f"profile -> {PROFILE_DIR}/, metrics -> OBS_metrics.json")
    obs.reset_all()
    return lines, records


def run(n_images: int = 8, size: int = 128, backend: str = "jax",
        fast: bool = False, strategy=None, kind: str = "haloc_axa",
        mega_images: int = 4, mega_size: int = 1024,
        gate_kinds: Optional[Sequence[str]] = None,
        ) -> Tuple[List[str], List[Dict]]:
    from repro.ax.backends import resolve_strategy
    strategy = resolve_strategy(strategy, fast)
    batch = synthetic_batch(n_images, size)
    rows = run_corpus(batch=batch, backend=backend, strategy=strategy)
    print(f"\n== imgproc corpus ({n_images} x {size}x{size}, "
          f"backend={backend}, strategy={strategy}) — PSNR dB / SSIM ==")
    print(format_table(rows))
    slowest = min(rows, key=lambda r: r.mpix_per_s)
    fastest = max(rows, key=lambda r: r.mpix_per_s)
    print(f"throughput: {fastest.workload}/{fastest.kind} "
          f"{fastest.mpix_per_s:.1f} MPix/s ... {slowest.workload}/"
          f"{slowest.kind} {slowest.mpix_per_s:.1f} MPix/s")
    lines = [r.csv() for r in rows]
    shape = "x".join(map(str, batch.shape))
    records = [{
        "op": r.workload, "backend": backend, "strategy": strategy,
        "batch": shape,
        "mpix_per_s": r.mpix_per_s, "wall_ms": r.seconds * 1e3,
        "kind": r.kind, "psnr": None if np.isinf(r.psnr) else r.psnr,
        "ssim": r.ssim,
    } for r in rows]
    batches = [synthetic_batch(4, 64)]
    if (n_images, size) != (4, 64):
        batches.append(batch)
    pl, pr = _pipeline_records(batches, kind, backend, strategy)
    if gate_kinds is None:
        from repro.core.specs import TABLE1_KINDS
        gate_kinds = tuple(TABLE1_KINDS)
    ml, mr = _megapixel_records(mega_images, mega_size, backend, kind,
                                gate_kinds)
    tl, tr = _telemetry_records(mega_size, backend, kind)
    return lines + pl + ml + tl, records + pr + mr + tr


if __name__ == "__main__":
    run()
