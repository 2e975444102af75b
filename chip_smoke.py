#!/usr/bin/env python3
"""Smoke run of the system's main path on one TPU chip.

    python chip_smoke.py

One process drives one chip through the entry points a user calls, at
megapixel size, on the ``pallas_tpu`` backend (Pallas kernels compiled
through Mosaic), and checks every output bit for bit:

* image: the stock pipelines ``pipe_blur_sharpen_down`` and
  ``pipe_blur_sobel`` (``compile_pipeline``, ``requant="fused"``,
  ``strategy="auto"``) on a 4 x 1024 x 1024 uint8 batch, untiled and
  through ``compile_tiled`` at 256 x 256 tiles, then 8 batches through
  ``run_streaming``.  Equal to the ``jax`` backend on the chip over the
  whole batch, and to the ``numpy`` reference on one full image.
* mac: MAC engines (the HALOC-AxA adder with a truncated n8t4 and a
  Mitchell multiplier): int8 ``matmul`` at 1024^3 and a 3x3 ``conv2d``
  on 4 x 256 x 256.  Equal to the ``jax`` backend on the chip, and to
  ``numpy`` at 256^3 and on the whole conv batch.
* serve: ``PlanExecutor`` under a ``Scheduler`` on the wall clock, 8
  requests of 1024 x 1024.  Every outcome is ``Completed`` on its first
  attempt and equal to a direct call of the plan.

Every image and MAC program must hold a Mosaic kernel
(``tpu_custom_call`` in its compiled HLO), so neither XLA alone nor the
Pallas interpreter can pass.  Per-phase lines give cold seconds
(compile included) and warm seconds: set-up times, not metrics.

Any failure, or a first device that is not a TPU, exits non-zero
without the result line.  On success the last line of standard output
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}``.  JAX's compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache`` next to this file.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

BACKEND = "pallas_tpu"
PIPES = ("pipe_blur_sharpen_down", "pipe_blur_sobel")
MEGA = (4, 1024, 1024)
TILE = (256, 256)
STREAM_BATCHES = 8
MAC_MNK = 1024
MAC_REF_MNK = 256
CONV = (4, 256, 256)
SERVE_REQUESTS = 8
SEED = 0


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def same(got, want, what: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape and got.dtype == want.dtype,
          f"{what}: {got.dtype}{got.shape} vs {want.dtype}{want.shape}")
    bad = int(np.count_nonzero(got != want))
    check(bad == 0, f"{what}: {bad} of {got.size} elements differ")


def timed(fn):
    """(host result, seconds) of one call, the device sync included."""
    t0 = time.perf_counter()
    out = np.asarray(fn())
    return out, time.perf_counter() - t0


def kernel_proof(fn, *args) -> None:
    """The compiled program of ``fn`` holds a Mosaic kernel."""
    import jax
    text = jax.jit(fn).lower(*args).compile().as_text()
    check("tpu_custom_call" in text,
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
          f"compiled program")


def setup_line(name: str, **seconds) -> None:
    parts = " ".join(f"{k}={v:.3f}s" for k, v in seconds.items())
    print(f"{name}: {parts} (set-up times, not metrics)", flush=True)


def image_phase(backend: str, shape, tile, n_stream: int) -> None:
    import jax
    from repro.imgproc import (PIPELINES, compile_pipeline, compile_tiled,
                               run_streaming, synthetic_batch)
    batch = synthetic_batch(shape[0], shape[1], seed=SEED)
    x = jax.device_put(batch)
    for name in PIPES:
        stages = PIPELINES[name]
        pipe = compile_pipeline(stages, backend=backend, requant="fused",
                                strategy="auto")
        check(pipe.engine.backend.name == backend,
              f"{name} resolved backend {pipe.engine.backend.name!r}")
        out, cold = timed(lambda: pipe(x))
        _, warm = timed(lambda: pipe(x))
        kernel_proof(pipe.fn, x)
        ref = compile_pipeline(stages, backend="jax", requant="fused",
                               strategy="auto")
        same(out, ref(x), f"{name} {backend} vs jax")
        host = compile_pipeline(stages, backend="numpy", requant="fused",
                                strategy="reference")
        same(out[:1], host(batch[:1]), f"{name} {backend} vs numpy")

        tiled = compile_tiled(pipe, shape, tile=tile)
        tout, tcold = timed(lambda: tiled(x))
        _, twarm = timed(lambda: tiled(x))
        kernel_proof(tiled.raw, x)
        same(tout, out, f"{name} tiled vs untiled")

        batches = [np.roll(batch, i, axis=0) for i in range(n_stream)]
        res = run_streaming(tiled, batches)
        check(len(res.outputs) == n_stream, f"{name} stream lost batches")
        for i, o in enumerate(res.outputs):
            same(o, np.roll(tout, i, axis=0), f"{name} stream batch {i}")
        setup_line(f"image {name} {shape}", cold=cold, warm=warm,
                   tiled_cold=tcold, tiled_warm=twarm,
                   stream=res.seconds)


def mac_phase(backend: str, mnk: int, ref_mnk: int, conv_shape) -> None:
    import jax
    from repro.ax import make_engine
    from repro.ax.mul import MacSpec, MulSpec
    from repro.core.specs import AdderSpec, paper_spec
    from repro.imgproc.workloads import CONV3X3_KERNEL
    from repro.numerics.fixed_point import FixedPointFormat
    gemm_adder = paper_spec("haloc_axa")
    conv_adder = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8,
                           const_bits=4)
    rng = np.random.default_rng(SEED)
    a = rng.integers(-128, 128, (mnk, mnk), np.int8)
    b = rng.integers(-128, 128, (mnk, mnk), np.int8)
    q = rng.integers(-255, 256, conv_shape).astype(np.int32)
    aj, bj, qj = jax.device_put(a), jax.device_put(b), jax.device_put(q)
    r = ref_mnk
    for mul in (MulSpec("truncated", 8, 4), MulSpec("mitchell", 8)):
        strategies = {backend: "auto", "jax": "auto", "numpy": "reference"}
        gemm = {be: make_engine(MacSpec(gemm_adder, mul), backend=be,
                                strategy=st)
                for be, st in strategies.items()}
        conv = {be: make_engine(MacSpec(conv_adder, mul),
                                fmt=FixedPointFormat(16, 0), backend=be,
                                strategy=st)
                for be, st in strategies.items()}
        eng, ceng = gemm[backend], conv[backend]
        tag = f"mac {mul.short_name}"

        out, cold = timed(lambda: eng.matmul(aj, bj))
        _, warm = timed(lambda: eng.matmul(aj, bj))
        kernel_proof(eng.matmul, aj, bj)
        same(out, gemm["jax"].matmul(aj, bj), f"{tag} matmul vs jax")
        same(eng.matmul(aj[:r, :r], bj[:r, :r]),
             gemm["numpy"].matmul(a[:r, :r], b[:r, :r]),
             f"{tag} matmul {r}^3 vs numpy")

        cout, ccold = timed(lambda: ceng.conv2d(qj, CONV3X3_KERNEL))
        _, cwarm = timed(lambda: ceng.conv2d(qj, CONV3X3_KERNEL))
        kernel_proof(lambda v: ceng.conv2d(v, CONV3X3_KERNEL), qj)
        same(cout, conv["jax"].conv2d(qj, CONV3X3_KERNEL),
             f"{tag} conv2d vs jax")
        # the host oracle returns int64 containers of 16-bit values
        same(cout, np.asarray(conv["numpy"].conv2d(q, CONV3X3_KERNEL),
                              np.int32), f"{tag} conv2d vs numpy")
        setup_line(f"{tag} matmul {mnk}^3 conv2d {conv_shape}",
                   matmul_cold=cold, matmul_warm=warm, conv_cold=ccold,
                   conv_warm=cwarm)


def serve_phase(backend: str, size: int, n_requests: int) -> None:
    from repro.imgproc import synthetic_batch
    from repro.serving import (Completed, PlanExecutor, Request, Scheduler,
                               WallClock)
    name = "pipe_blur_sharpen_down"
    executor = PlanExecutor.compile((name,), backend=backend,
                                    requant="fused")
    plan = executor.plan(name)
    check(plan.engine.backend.name == backend,
          f"serve resolved backend {plan.engine.backend.name!r}")
    sched = Scheduler(executor, clock=WallClock())
    imgs = synthetic_batch(n_requests, size, seed=SEED + 1)
    t0 = time.perf_counter()
    rids = []
    for img in imgs:
        req = Request(img, pipeline=name)
        check(sched.submit(req) is None, f"request {req.rid} rejected")
        rids.append(req.rid)
    sched.drain()
    seconds = time.perf_counter() - t0
    outcomes = {o.rid: o for o in sched.outcomes}
    check(len(sched.outcomes) == n_requests and set(outcomes) == set(rids),
          f"serve: {len(sched.outcomes)} outcomes for {n_requests} "
          f"requests")
    for i, rid in enumerate(rids):
        o = outcomes[rid]
        check(isinstance(o, Completed) and o.attempts == 1,
              f"serve request {i}: {o!r:.300}")
    per = sched.batcher.cfg.max_batch
    for i in range(0, n_requests, per):
        direct = np.asarray(plan(imgs[i:i + per]))
        for j, want in enumerate(direct):
            same(outcomes[rids[i + j]].output, want,
                 f"serve request {i + j} vs direct call")
    setup_line(f"serve {name} {n_requests}x{size}^2", total=seconds)


def run() -> dict:
    sys.path.insert(0, SRC)
    import jax

    from repro import ioutil
    check(os.path.abspath(ioutil.__file__).startswith(SRC + os.sep),
          f"repro imported from {ioutil.__file__}, not from {SRC}")
    print(f"compile cache: {ioutil.enable_compile_cache(ROOT)}",
          flush=True)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}", flush=True)
    check(device["platform"] == "tpu",
          f"the first device is {device['platform']!r}, not a TPU")
    image_phase(BACKEND, MEGA, TILE, STREAM_BATCHES)
    mac_phase(BACKEND, MAC_MNK, MAC_REF_MNK, CONV)
    serve_phase(BACKEND, MEGA[1], SERVE_REQUESTS)
    return device


def main() -> int:
    try:
        device = run()
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
