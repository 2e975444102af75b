"""repro.resilience: fault injection, stream teardown, self-healing.

Acceptance (PR 8): a seeded stuck-at-1 campaign on the three-stage
pipeline where (a) the faulted datapath is bit-identical across
numpy/jax/pallas, (b) the drift monitor trips within its sample
budget, (c) the degradation ladder recovers >= 5 dB PSNR versus
serving the fault unmitigated, and (d) a poisoned batch leaves zero
leaked in-flight futures.  Long campaigns ride the ``slow`` marker.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.ax.lut import compile_lut, error_delta_table
from repro.core.specs import AdderSpec
from repro.imgproc.corpus import (StreamResult, run_streaming,
                                  synthetic_batch)
from repro.imgproc.plan import PIPELINES, compile_pipeline, run_pipeline
from repro.resilience.faults import (FaultSpec, apply_fault, corrupt_lut,
                                     faulted_delta_table,
                                     faulted_mean_abs_error,
                                     transient_flip_mask, validate_fault)

PIPE = PIPELINES["pipe_blur_sharpen_down"]
SPEC = AdderSpec("haloc_axa", 16, lsm_bits=8, const_bits=4)


@pytest.fixture()
def fresh_obs():
    obs.reset_all()
    obs.enable()
    yield
    obs.disable()
    obs.reset_all()


# ----------------------------------------------------- FaultSpec API --

def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("stuck_high", bits=(1,))
    with pytest.raises(ValueError, match="at least one target bit"):
        FaultSpec("stuck_at_1", bits=())
    with pytest.raises(ValueError, match="duplicate fault bit"):
        FaultSpec("stuck_at_1", bits=(3, 3))
    with pytest.raises(ValueError, match="fault bit position"):
        FaultSpec("stuck_at_1", bits=(64,))
    with pytest.raises(ValueError, match="rate must be in"):
        FaultSpec("bit_flip", bits=(1,), rate=0.0)
    with pytest.raises(ValueError, match="rate must be in"):
        FaultSpec("bit_flip", bits=(1,), rate=1.5)
    with pytest.raises(ValueError, match="fault seed"):
        FaultSpec("bit_flip", bits=(1,), seed=-1)
    # Scalar bit positions are coerced to a tuple.
    assert FaultSpec("stuck_at_1", bits=5).bits == (5,)
    assert FaultSpec("stuck_at_1", bits=(1, 4)).mask == 0b10010


def test_validate_fault_checks_bus_width():
    f = FaultSpec("stuck_at_1", bits=(40,))
    with pytest.raises(ValueError, match=r"N=16"):
        validate_fault(f, 16)
    assert validate_fault(f, 64) is f
    assert validate_fault(None, 16) is None
    with pytest.raises(ValueError, match="FaultSpec or None"):
        validate_fault("stuck_at_1", 16)


def test_fault_entry_point_validation_at_compile():
    """Input validation at the plan/engine fault entry points: a bit
    outside the 16-bit image bus is rejected before anything compiles."""
    from repro.ax import make_engine
    with pytest.raises(ValueError, match="fault bit position"):
        compile_pipeline(PIPE, kind="haloc_axa", backend="numpy",
                         fault=FaultSpec("stuck_at_1", bits=(40,)))
    with pytest.raises(ValueError, match="fault bit position"):
        make_engine(SPEC, backend="numpy",
                    fault=FaultSpec("stuck_at_0", bits=(16,)))


# ------------------------------------------- cross-backend identity --

@pytest.mark.parametrize("fault", [
    FaultSpec("stuck_at_1", bits=(11,)),
    FaultSpec("stuck_at_0", bits=(3, 11)),
    FaultSpec("bit_flip", bits=(4, 11), rate=0.25, seed=3),
], ids=lambda f: f.short_name)
def test_apply_fault_numpy_jax_bit_identity(fault):
    rng = np.random.default_rng(0)
    x64 = rng.integers(0, 1 << 16, 256, dtype=np.uint64)
    out_np = np.asarray(apply_fault(x64, fault, 16))
    out_jx = np.asarray(apply_fault(jnp.asarray(x64, jnp.uint32),
                                    fault, 16))
    np.testing.assert_array_equal(out_np.astype(np.uint32), out_jx)


def test_apply_fault_signed_sign_extension():
    q = np.array([-5, -1, 0, 1, 2000, -2000], dtype=np.int64)
    fault = FaultSpec("stuck_at_1", bits=(15,))
    out = apply_fault(q, fault, 16, signed=True)
    # Forcing the sign bit makes every value negative, still a valid
    # 16-bit two's-complement container.
    assert (out < 0).all()
    assert (out >= -(1 << 15)).all()
    out_jx = np.asarray(apply_fault(jnp.asarray(q, jnp.int32), fault, 16,
                                    signed=True))
    np.testing.assert_array_equal(out.astype(np.int32), out_jx)


@pytest.mark.parametrize("fault", [
    FaultSpec("stuck_at_1", bits=(31,)),
    FaultSpec("stuck_at_0", bits=(31,)),
    FaultSpec("bit_flip", bits=(31,), rate=1.0, seed=5),
], ids=lambda f: f.short_name)
def test_apply_fault_int32_container_boundary(fault):
    """Satellite 2 regression: faulting bit 31 on an int32 container.

    ``1 << 31`` (and the all-ones clear mask) exceed int32's positive
    range, so the old per-dtype constant casts raised OverflowError —
    exactly at the n_bits == container-width boundary the jax/Pallas
    lanes use for N=32 buses.  The constants must wrap two's-complement
    instead; the wide-container numpy path is the ground truth."""
    rng = np.random.default_rng(1)
    x64 = rng.integers(0, 1 << 32, 512, dtype=np.uint64)
    want = np.asarray(apply_fault(x64, fault, 32)).astype(np.uint32)
    got_i32 = np.asarray(apply_fault(
        jnp.asarray(x64.astype(np.uint32)).astype(jnp.int32), fault, 32))
    np.testing.assert_array_equal(got_i32.view(np.uint32), want)
    got_np_i32 = apply_fault(x64.astype(np.uint32).astype(np.int32),
                             fault, 32)
    np.testing.assert_array_equal(got_np_i32.view(np.uint32), want)


@pytest.mark.parametrize("n_bits,dtype", [(16, np.int16), (32, np.int32)])
def test_apply_fault_signed_sign_bit_at_container_width(n_bits, dtype):
    """Forcing the sign bit (bit n_bits-1) on a signed container whose
    width equals n_bits: the sign-extension shift must not overflow and
    every output stays a valid n_bits two's-complement value."""
    lo, hi = -(1 << (n_bits - 1)), (1 << (n_bits - 1)) - 1
    q = np.array([lo, -1, 0, 1, hi], dtype=np.int64)
    fault = FaultSpec("stuck_at_1", bits=(n_bits - 1,))
    want = apply_fault(q, fault, n_bits, signed=True)
    assert (want < 0).all() and (want >= lo).all()
    got = apply_fault(q.astype(dtype), fault, n_bits, signed=True)
    np.testing.assert_array_equal(got.astype(np.int64), want)
    # and clearing it makes everything non-negative
    clear = FaultSpec("stuck_at_0", bits=(n_bits - 1,))
    got0 = apply_fault(q.astype(dtype), clear, n_bits, signed=True)
    assert (got0.astype(np.int64) >= 0).all() and \
        (got0.astype(np.int64) <= hi).all()


@pytest.mark.parametrize("fault", [
    FaultSpec("stuck_at_1", bits=(11,), seed=0),
    FaultSpec("bit_flip", bits=(4, 11), rate=0.25, seed=3),
], ids=lambda f: f.short_name)
def test_faulted_pipeline_cross_backend_bit_identity(fault):
    """Acceptance: the FAULTED blur->sharpen->downsample datapath is
    bit-identical across numpy uint64 containers, jax int32 lanes, and
    the Pallas tile kernels — same contract as the healthy path."""
    batch = synthetic_batch(2, 32, seed=1)
    outs = {b: np.asarray(run_pipeline(PIPE, batch, kind="haloc_axa",
                                       backend=b, fault=fault))
            for b in ("numpy", "jax", "pallas")}
    np.testing.assert_array_equal(outs["numpy"], outs["jax"])
    np.testing.assert_array_equal(outs["numpy"], outs["pallas"])
    # And the defect actually bites: the faulted output differs from
    # the healthy one.
    healthy = np.asarray(run_pipeline(PIPE, batch, kind="haloc_axa",
                                      backend="numpy"))
    assert not np.array_equal(outs["numpy"], healthy)


def test_transient_flip_mask_inside_pallas_kernel():
    """The counter-based flip hash runs inside a Pallas kernel body and
    reproduces the host mask bit for bit."""
    fault = FaultSpec("bit_flip", bits=(2, 9), rate=0.5, seed=11)
    shape = (8, 128)

    def kernel(x_ref, o_ref):
        x = x_ref[...]
        xu = jax.lax.bitcast_convert_type(x, jnp.uint32)
        idx = jax.lax.broadcasted_iota(
            jnp.uint32, shape, 0) * jnp.uint32(shape[1]) + \
            jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        o_ref[...] = jax.lax.bitcast_convert_type(
            xu ^ transient_flip_mask(idx, fault), jnp.int32)

    x = np.arange(shape[0] * shape[1], dtype=np.int32).reshape(shape)
    out = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
        interpret=True)(jnp.asarray(x))
    idx = np.arange(x.size, dtype=np.uint32).reshape(shape)
    want = x.view(np.uint32) ^ transient_flip_mask(idx, fault)
    np.testing.assert_array_equal(np.asarray(out).view(np.uint32), want)


# ------------------------------------------------- LUT-layer faults --

def test_corrupt_lut_never_pollutes_shared_cache():
    before = compile_lut(SPEC)
    bad = corrupt_lut(SPEC, FaultSpec("stuck_at_1", bits=(3,)))
    after = compile_lut(SPEC)
    assert after is before  # same cached object, untouched
    np.testing.assert_array_equal(bad, before | np.uint16(1 << 3))
    assert not bad.flags.writeable
    with pytest.raises(ValueError, match="packed LUT entries"):
        # Bits above the m+1-wide packed entry are not representable.
        corrupt_lut(SPEC, FaultSpec("stuck_at_1", bits=(12,)))


def test_faulted_delta_table_predicts_drift_trip():
    """The corrupted table's exact mean |error| exceeds the healthy
    drift threshold — the closed-form prediction that the monitor MUST
    trip on this defect (within sampling slack)."""
    from repro.obs.drift import DriftMonitor
    fault = FaultSpec("stuck_at_1", bits=(7,))
    healthy = error_delta_table(SPEC)
    faulted = faulted_delta_table(SPEC, fault)
    assert faulted.shape == healthy.shape
    assert not np.array_equal(faulted, healthy)
    mon = DriftMonitor(SPEC)
    assert faulted_mean_abs_error(SPEC, fault) > mon.threshold(10 ** 6)


# ---------------------------------------------- hardened streaming --

class _Fut:
    """A future-like handle that records whether it was ever settled."""

    def __init__(self, arr, raise_on_drain=False):
        self.arr = np.asarray(arr)
        self.raise_on_drain = raise_on_drain
        self.settled = False

    def __array__(self, dtype=None, copy=None):
        self.settled = True
        if self.raise_on_drain:
            raise RuntimeError("device poisoned")
        return self.arr


def _poisoned_stream(n=6, bad=2):
    futs = []

    def fn(batch):
        fut = _Fut(batch, raise_on_drain=int(batch[0, 0, 0]) == bad)
        futs.append(fut)
        return fut

    batches = []
    for i in range(n):
        b = np.zeros((1, 8, 8), np.uint8)
        b[0, 0, 0] = i
        batches.append(b)
    return fn, batches, futs


@pytest.mark.parametrize("depth", [1, 3, 64])
def test_poisoned_batch_leaves_no_pending_futures(fresh_obs, depth):
    """Satellite 1 + acceptance: a mid-stream raise re-raises with the
    failing batch index AND every dispatched future is settled (drained
    or dropped) before the exception escapes — zero leaks, gauge at 0."""
    fn, batches, futs = _poisoned_stream(n=6, bad=2)
    with pytest.raises(RuntimeError, match=r"batch 2"):
        run_streaming(fn, batches, depth=depth)
    assert futs and all(f.settled for f in futs)
    snap = obs.metrics_snapshot()
    assert snap["gauges"]["stream.batches_in_flight"]["value"] == 0
    assert snap["counters"]["stream.failed_batches"] == 1


def test_dispatch_failure_names_batch_index():
    def fn(batch):
        if int(batch[0, 0, 0]) == 1:
            raise ValueError("compile exploded")
        return batch

    _, batches, _ = _poisoned_stream(n=3)
    with pytest.raises(RuntimeError, match=r"batch 1 failed during"):
        run_streaming(fn, batches, depth=2)


@pytest.mark.parametrize("depth", [1, 2, 4, 64])
def test_stream_keeps_at_most_depth_in_flight(depth):
    """The pipelining contract: dispatches run ahead of the drain by at
    most ``depth`` batches, and reach that many whenever the stream is
    long enough."""
    live = [0, 0]                     # undrained dispatches, high water

    class Counted(_Fut):
        drained = False

        def __array__(self, dtype=None, copy=None):
            if not self.drained:
                self.drained = True
                live[0] -= 1
            return super().__array__(dtype, copy)

    def fn(batch):
        live[0] += 1
        live[1] = max(live[1], live[0])
        return Counted(batch)

    n = 10
    _, batches, _ = _poisoned_stream(n=n)
    r = run_streaming(fn, batches, depth=depth)
    assert live == [0, min(depth, n)]
    for got, want in zip(r.outputs, batches):
        np.testing.assert_array_equal(got, want)


def _stops_early(batches, stop):
    for j, b in enumerate(batches):
        if j == stop:
            return
        yield b


@pytest.mark.parametrize("n,stop", [(2, None), (9, None), (8, 5)],
                         ids=["fewer-than-depth", "more-than-depth",
                              "generator-stops-early"])
def test_stream_matches_sequential_for_any_length(n, stop):
    """Outputs in input order, one per batch the iterable yields, equal
    to the blocking loop's — however the stream's length compares with
    ``depth``, and when a generator ends before its pool does (as the
    benchmark's closed loop does when its window closes)."""
    depth = 4
    batches = [np.full((1, 8, 8), i, np.uint8) for i in range(n)]
    fed = batches if stop is None else _stops_early(batches, stop)
    r = run_streaming(lambda b: jnp.asarray(b) + 1, fed, depth=depth)
    served = batches[:stop]
    want = [np.asarray(jnp.asarray(b) + 1) for b in served]
    assert len(r.outputs) == len(want) == len(r.batch_seconds)
    for got, exp in zip(r.outputs, want):
        assert isinstance(got, np.ndarray)
        np.testing.assert_array_equal(got, exp)
    assert r.pixels == sum(b.size for b in served)


def test_empty_stream_is_well_formed():
    """Zero batches: a complete, zero-throughput StreamResult — no
    division error, no nan."""
    r = run_streaming(lambda b: b, [])
    assert r.outputs == []
    assert r.pixels == 0
    assert r.mpix_per_s == 0.0
    assert r.batch_seconds == ()
    # Direct zero-seconds guard (instantaneous streams, old pickles).
    z = StreamResult(outputs=[], seconds=0.0, pixels=0)
    assert z.mpix_per_s == 0.0


@pytest.mark.parametrize("depth", [0, -1])
def test_run_streaming_rejects_bad_depth(depth):
    batches = [np.zeros((1, 4, 4), np.uint8)]
    with pytest.raises(ValueError, match="depth"):
        run_streaming(lambda b: b, batches, depth=depth)


def test_straggler_late_is_single_source_of_truth():
    from repro.runtime.straggler import StragglerConfig, StragglerMonitor
    mon = StragglerMonitor(StragglerConfig(min_samples=4))
    for i in range(6):
        assert not mon.late(i, 0.010)
    # Outlier against its own history, no explicit deadline needed.
    assert mon.late(6, 0.200)
    # Explicit deadline verdict, independent of the history filter.
    assert mon.late(7, 0.012, deadline=0.011)
    assert not mon.late(8, 0.010, deadline=0.011)


# ------------------------------------------- self-healing degrade --

def test_pareto_ladder_monotone_and_ends_exact():
    from repro.ax.analytics import exact_error_metrics
    from repro.ax.registry import get_adder
    from repro.core.hwcost import switching_energy_fj
    from repro.resilience.degrade import pareto_ladder
    ladder = pareto_ladder(SPEC)
    assert ladder
    own = exact_error_metrics(SPEC, cache_tables=False).nmed
    nmeds = [exact_error_metrics(s, cache_tables=False).nmed
             for s in ladder]
    energies = [switching_energy_fj(s) for s in ladder]
    assert all(n < own for n in nmeds)
    assert nmeds == sorted(nmeds, reverse=True)       # accuracy improves
    assert energies == sorted(energies)               # energy climbs
    assert get_adder(ladder[-1].kind).is_exact        # ends exact
    assert nmeds[-1] == 0.0


def test_degrade_policy_requires_telemetry():
    from repro.resilience.degrade import DegradePolicy
    obs.disable()
    pipe = compile_pipeline(PIPE, kind="haloc_axa", backend="numpy")
    pol = DegradePolicy(pipe, min_samples=256)
    with pytest.raises(RuntimeError, match="telemetry"):
        pol.observe(synthetic_batch(1, 32))


def test_degrade_policy_never_degrades_healthy(fresh_obs):
    from repro.resilience.degrade import DegradePolicy
    pipe = compile_pipeline(PIPE, kind="haloc_axa", backend="numpy")
    pol = DegradePolicy(pipe, min_samples=256)
    batch = synthetic_batch(2, 32, seed=5)
    for _ in range(4):
        assert not pol.observe(batch)
    assert pol.level == 0 and pol.trips == 0
    assert pol.pipe is pipe


def test_degrade_policy_trips_within_budget_and_recovers(fresh_obs):
    """Acceptance: seeded stuck-at-1 campaign — the monitor trips inside
    its sample budget (one observed batch here), the policy recovers
    >= 5 dB PSNR versus no fallback, and the run is deterministic."""
    from repro.resilience.harness import recovery_cell
    rec = recovery_cell(min_samples=512)
    assert rec["trips"] >= 1 and rec["degrade_level"] >= 1
    assert rec["recovery_db"] >= 5.0
    assert rec["batches_degraded"] >= 1
    rec2 = recovery_cell(min_samples=512)
    assert rec == rec2  # bit-for-bit deterministic replay
    snap = obs.metrics_snapshot()
    assert snap["counters"]["degrade.trips"] >= 1
    assert snap["counters"]["degrade.fallbacks"] >= 1
    assert snap["gauges"]["degrade.level"]["value"] >= 1


def test_run_streaming_degrade_hook(fresh_obs):
    from repro.resilience.degrade import DegradePolicy
    fault = FaultSpec("stuck_at_1", bits=(11,))
    pipe = compile_pipeline(PIPE, kind="haloc_axa", backend="numpy",
                            fault=fault)
    pol = DegradePolicy(pipe, min_samples=512)
    batches = [synthetic_batch(2, 32, seed=9 + i) for i in range(3)]
    outs, degraded = pol.stream(batches)
    assert pol.level >= 1
    assert degraded and degraded[0] == 0  # tripping batch re-ran
    assert len(outs) == len(batches)
    # Every degraded output came from the recovered plan.
    for i in degraded:
        np.testing.assert_array_equal(outs[i],
                                      np.asarray(pol.pipe(batches[i])))


# -------------------------------------------------- campaign sweep --

def test_quick_campaign_curves(fresh_obs):
    from repro.resilience.harness import run_campaign
    cells = run_campaign(quick=True, backend="numpy")
    by_name = {("none" if c.fault is None else c.fault.short_name): c
               for c in cells}
    clean = by_name["none"]
    assert np.isfinite(clean.psnr) and clean.ssim > 0.9
    # Every defect costs quality, and harder defects cost more.
    for name, c in by_name.items():
        if name != "none":
            assert c.psnr < clean.psnr
    flips = sorted((c for c in cells
                    if c.fault and c.fault.kind == "bit_flip"),
                   key=lambda c: c.fault.rate)
    psnrs = [c.psnr for c in flips]
    assert psnrs == sorted(psnrs, reverse=True)  # PSNR falls with rate


@pytest.mark.slow
def test_full_campaign_grid():
    """The full (non-quick) defect grid over both stock pipelines —
    the long-running sweep CI's smoke job deliberately skips."""
    from repro.resilience.harness import run_campaign
    cells = run_campaign(quick=False, backend="numpy",
                         workloads=tuple(PIPELINES))
    assert len(cells) == len(PIPELINES) * (1 + 6)
    assert all(np.isfinite(c.psnr) and 0 <= c.ssim <= 1 for c in cells)
