"""The reduction from a profiler trace to per-layer numbers: busy union,
idle share and gaps, kernel time by stable name, the non-kernel share,
rooflines against the peak table, and the breakdown."""

from __future__ import annotations

import glob
import json
import os

import pytest

from chipbench import peaks, readings, trace as trace_lib

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

DEV = "/device:TPU:0"

#: One 3x3 filter stage on a 16-bit adder: 6 taps per pixel, 2 bytes
#: in and 2 out.
CFG = {"pipeline": ["gaussian_blur"], "adder": {"n_bits": 16}}


def _trace():
    # window 0..100; ops: conv_chain 10..30 and 25..40 (overlap),
    # an XLA op 60..70, one op straddling the end 95..120.
    return trace_lib.Trace(
        devices={DEV: [trace_lib.Op("conv_chain.1", "conv_chain", 10, 30),
                       trace_lib.Op("conv_chain.2", "conv_chain", 25, 40),
                       trace_lib.Op("fusion.3", "", 60, 70),
                       trace_lib.Op("copy.1", "", 95, 120)]},
        spans=[("cb.window", 0, 100), ("cb.plan.call", 0, 12),
               ("cb.stream.run_streaming", 0, 100)])


def _reading(tr, calls, kind="TPU v5 lite"):
    return readings.Reading(
        trace=tr, device=DEV, window=tr.window(), device_kind=kind,
        calls=calls, config=CFG, counters={})


def test_merge_clips_and_joins():
    assert trace_lib.merge([(5, 10), (8, 20), (30, 40), (-5, 2)], 0, 35) \
        == [(0, 2), (5, 20), (30, 35)]


def test_busy_and_idle_share():
    tr = _trace()
    assert trace_lib.busy_ns(tr.devices[DEV], tr.window()) == 30 + 10 + 5
    r = _reading(tr, [])
    assert readings.idle_share(r) == pytest.approx(55.0)


def test_idle_gaps_are_charged_to_the_innermost_span():
    tr = _trace()
    gaps = trace_lib.idle_gaps(tr.devices[DEV], tr.spans, tr.window())
    assert gaps == [("cb.plan.call", 10), ("cb.stream.run_streaming", 20),
                    ("cb.stream.run_streaming", 25)]


def test_breakdown_lists_ops_and_gaps_in_seconds():
    tr = _trace()
    b = trace_lib.breakdown(tr, DEV, tr.window())
    assert b["device_ops"][0] == ["conv_chain.1", 20e-9]
    assert dict(map(tuple, b["idle_gaps"])) == {
        "cb.stream.run_streaming": 45e-9, "cb.plan.call": 10e-9}


def test_glue_share_counts_non_kernel_time():
    r = _reading(_trace(), [])
    # inside the window: conv_chain 20 + 15, fusion 10, copy 25
    assert readings.glue_share(r) == pytest.approx(100 * 35 / 70)


def test_roofline_counts_logical_work_over_all_kernel_time():
    tr = _trace()
    px = 4 * 1024 * 1024
    ops, nbytes = 6 * px, 4 * px
    bound, which = peaks.bound_seconds(ops, nbytes, "TPU v5 lite")
    assert which == "bytes" and bound == pytest.approx(nbytes / 819e9)
    shape = (4, 1024, 1024)
    # both conv_chain operations count, whatever the number of calls
    for calls in ([shape], [shape, shape]):
        assert readings.roofline(_reading(tr, calls), "conv_chain") \
            == pytest.approx(100 * len(calls) * bound / 35e-9)
    assert readings.roofline(_reading(tr, []), "conv_chain") is None
    assert readings.roofline(_reading(tr, [shape], kind="cpu"),
                             "conv_chain") is None


@pytest.mark.parametrize("peak,which", [("bf16_flops", "ops"),
                                         ("int8_ops", "bytes")])
def test_step_share_counts_every_call_s_work_over_the_window(peak, which):
    """The least time for each call's work at the named peak, summed over
    the calls and over the window's length, whatever ran on the device."""
    tr = trace_lib.Trace(devices={DEV: []},
                         spans=[("cb.window", 0, 40_000_000)])
    shape = (1024, 1024, 1024)
    ops, nbytes = 2 * 1024 ** 3, 6 * 1024 ** 2
    bound, binds = peaks.bound_seconds(ops, nbytes, "TPU v5 lite", peak)
    assert binds == which
    assert bound == pytest.approx(ops / 197e12 if which == "ops"
                                  else nbytes / 819e9)
    r = readings.Reading(trace=tr, device=DEV, window=tr.window(),
                         device_kind="TPU v5 lite", calls=[shape] * 3,
                         config={}, counters={})
    assert readings.step_share(r, "mac_matmul", peak) \
        == pytest.approx(100 * 3 * bound / 0.04)
    r.calls = []
    assert readings.step_share(r, "mac_matmul", peak) is None
    r.calls, r.device_kind = [shape], "cpu"
    assert readings.step_share(r, "mac_matmul", peak) is None


def test_roofline_counts_the_part_of_a_kernel_inside_the_window():
    """The first call's kernel, put 5 ns before the window by the
    trace's clocks, still counts; a kernel wholly outside does not."""
    tr = _trace()
    tr.devices[DEV] += [trace_lib.Op("conv_chain.0", "conv_chain", -5, 15),
                        trace_lib.Op("conv_chain.9", "conv_chain", 100, 130)]
    shape = (4, 1024, 1024)
    bound = peaks.bound_seconds(6 * 4 * 1024 * 1024, 4 * 4 * 1024 * 1024,
                                "TPU v5 lite")[0]
    # 10..30 and 25..40 as before, plus 0..15 of the straddling one
    assert readings.roofline(_reading(tr, [shape]), "conv_chain") \
        == pytest.approx(100 * bound / 50e-9)


def test_roofline_reads_nothing_without_the_kernel():
    tr = _trace()
    tr.devices[DEV] = [o for o in tr.devices[DEV] if not o.kernel]
    assert readings.roofline(_reading(tr, [(4, 64, 64)]), "conv_chain") \
        is None


def test_conv_chain_work_counts_filter_stages_at_their_size():
    from chipbench.readings import work_of
    cfg = {"pipeline": ["gaussian_blur", "downsample2x", "sharpen"],
           "adder": {"n_bits": 16}}
    px = 2 * 64 * 64
    assert work_of("conv_chain")((2, 64, 64), cfg) == (
        6 * px + 6 * px // 4, 4 * px + 4 * px // 4)
    assert work_of("mac_matmul")((8, 16, 4), {}) == (
        2 * 8 * 16 * 4, 8 * 16 + 16 * 4 + 4 * 8 * 4)


#: Operation names as a v5e trace gives them: the whole HLO instruction.
V5E_NAMES = [
    ('%conv_chain.3 = s32[4,1,1024,1024]{3,2,1,0:T(8,128)S(1)} custom-call('
     's32[4,1,1024,1024]{3,2,1,0:T(8,128)S(1)} %clamp_shift-left_fusion.1), '
     'custom_call_target="tpu_custom_call", frontend_attributes='
     '{kernel_metadata={}}', "conv_chain.3", "conv_chain"),
    ('%vmap_jit__pallas_accumulate__.4 = s32[4,4096,256]{2,1,0:T(8,128)S(1)}'
     ' custom-call(s32[4,2,4096,256]{3,2,1,0:T(8,128)S(1)} %add_and_fusion),'
     ' custom_call_target="tpu_custom_call"',
     "vmap_jit__pallas_accumulate__.4", "vmap_jit__pallas_accumulate__"),
    ('%copy.8 = s32[4,1024,1024]{2,0,1:T(4,128)S(1)} copy(s32[4,1024,1024]'
     '{2,1,0:T(8,128)S(1)} %reshape.44)', "copy.8", ""),
    ("fusion.3", "fusion.3", ""),
    ("mac_matmul.1", "mac_matmul.1", "mac_matmul"),
]


@pytest.mark.parametrize("raw,name,kernel", V5E_NAMES)
def test_operation_names_reduce_to_the_instruction_and_its_kernel(
        raw, name, kernel):
    assert trace_lib.op_name(raw) == name
    assert trace_lib.kernel_of(raw) == kernel


def test_recorded_v5e_operation_names_give_the_pallas_kernels():
    """Every operation name of two short traced runs on a TPU v5e."""
    with open(os.path.join(DATA, "v5e_op_names.json")) as f:
        names = json.load(f)
    assert {trace_lib.kernel_of(n) for n in names} == {
        "", "conv_chain", "mac_matmul", "vmap_jit__pallas_accumulate__"}
    assert all(trace_lib.kernel_of(n) == "" for n in names
               if "tpu_custom_call" not in n)


@pytest.mark.parametrize("cell", ["img", "gemm"])
def test_recorded_v5e_trace_reduces_to_the_run_s_own_numbers(cell):
    """A short traced run of each cell on a TPU v5e, kept with what the
    run printed: the reduction gives the same per-layer metrics, busy
    time and breakdown."""
    from chipbench import cells
    from chipbench_testutil import REPO
    with open(os.path.join(DATA, f"v5e_{cell}_trace.json")) as f:
        rec = json.load(f)
    c = cells.resolve(REPO, rec["workload"])
    if "shapes" in c.mix:
        shapes = [tuple(s) for s in c.mix["shapes"]]
        calls = [shapes[i % len(shapes)] for i in range(rec["calls"])]
    else:
        calls = [(c.mix["batch"], c.mix["size"], c.mix["size"])] * rec["calls"]
    tr = trace_lib.Trace.from_json(rec["trace"])
    plane, window = sorted(tr.devices)[0], tr.window()
    r = readings.Reading(trace=tr, device=plane, window=window,
                         device_kind=rec["result"]["device"]["kind"],
                         calls=calls, config=c.config, counters={})
    got = {m["name"]: c.readers[m["name"]].read(r) for m in c.per_layer}
    want = {k: v["value"] for k, v in rec["result"]["metrics"].items()}
    assert got == pytest.approx(want, rel=1e-9)
    dev = rec["result"]["device"]
    assert trace_lib.busy_ns(tr.devices[plane], window) / 1e9 \
        == pytest.approx(dev["busy_s"], rel=1e-9)
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert trace_lib.breakdown(tr, plane, window) \
        == rec["result"]["breakdown"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.bound_seconds(1, 1, "cpu")


def test_trace_round_trips_through_json():
    tr = _trace()
    again = trace_lib.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again.to_json() == tr.to_json()


def test_kept_trace_reduces_to_the_run_s_own_numbers(tmp_path):
    """A traced rehearsal keeps its raw profile and its reduction; the
    reduction read back gives the busy time the run reported."""
    from chipbench_testutil import bench_copy, run_cell
    root = bench_copy(tmp_path, workloads={"gemm-r50-trunc8"})
    keep = str(tmp_path / "kept")
    res = run_cell(root, "gemm-r50-trunc8", trace=1, keep_trace=keep)
    assert glob.glob(os.path.join(keep, "**", "*.xplane.pb"),
                     recursive=True)
    with open(os.path.join(keep, "reduced.json")) as f:
        tr = trace_lib.Trace.from_json(json.load(f))
    plane = sorted(tr.devices)[0]
    busy = trace_lib.busy_ns(tr.devices[plane], tr.window()) / 1e9
    assert busy == pytest.approx(res["device"]["busy_s"])
    again = trace_lib.load(keep)
    assert again.to_json() == tr.to_json()
