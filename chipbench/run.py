#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Three stages.  Set-up: the cell's inputs are made from the seed and
every shape the window will use is warmed up; ``setup_s`` runs from the
start of this process to the start of the window.  Window: the cell's
traffic runs for ``--seconds``; with ``--trace 1`` the profiler records
it.  Result: a seeded sample of what the timed path produced is
compared with the configuration's plain reference, each compared number
is printed beside its limit on standard error, and the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``.

With no TPU, or fewer chips than the cell asks for, or without the
program next to this directory, it exits non-zero and prints no
result.  ``--control 1`` puts the reference, with the guarantee that
the configuration's ``control`` entry breaks, in the program's place
after the window (the check must then fail); the benchmark's own runs
never pass it.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import cells, compare, loops  # noqa: E402

#: Where runs keep their traces and the compile cache, in the checkout.
WORK_DIR = ".chipbench"


class NoDevice(Exception):
    """No TPU, or fewer chips than the cell asks for."""


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default=None,
                   help="copy the raw and the reduced trace to this "
                        "directory")
    args = p.parse_args(argv)
    if args.seconds <= 0:
        p.error("--seconds must be positive")
    return args


def _prepare_env(root: str) -> None:
    """The compile cache and the TPU runtime's logs stay in the
    checkout; both are set before JAX is imported."""
    work = os.path.join(root, WORK_DIR)
    os.makedirs(work, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work,
                                                          "jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _import_program(root: str) -> None:
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro import ioutil
    where = os.path.realpath(ioutil.__file__)
    if not where.startswith(os.path.realpath(src) + os.sep):
        raise ImportError(f"the program was imported from {where}, not "
                          f"from this checkout's {src}")


def _devices(cell: cells.Cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoDevice(f"the first device is {devs[0].platform!r}, "
                           f"not a TPU")
        if len(devs) < cell.chips:
            raise NoDevice(f"{cell.name} needs {cell.chips} chips; "
                           f"{len(devs)} found")
    return devs[:cell.chips]


def _memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts JAX's traces and backend compiles while armed: the window
    should hold none, since set-up warmed every shape it uses."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.counts = dict.fromkeys(("traces", "compiles"), 0)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.armed and name in self.EVENTS:
            key = "traces" if name == self.EVENTS[0] else "compiles"
            self.counts[key] += 1


def _per_layer(cell, loop, tr, plane, window, device_kind) -> dict:
    from chipbench.readings import Reading
    reading = Reading(
        trace=tr, device=plane, window=window, device_kind=device_kind,
        calls=list(loop.calls), config=cell.config,
        counters=dict(loop.counters))
    out = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]].read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, *, root: str = ROOT, require_tpu: bool = True,
        backend=None, patch=None, t_start: float = _T_START) -> dict:
    """One run; returns the result object.  ``backend`` and ``patch``
    (a function wrapping the built system) serve the CPU tests."""
    cell = cells.resolve(root, args.workload)
    if require_tpu:
        _prepare_env(root)
    _import_program(root)
    import jax
    devs = _devices(cell, require_tpu)
    kind = devs[0].device_kind
    if require_tpu:
        from chipbench import peaks
        from repro import ioutil
        peaks.peaks(kind)
        ioutil.enable_compile_cache(root)

    from chipbench import systems
    cfg = cell.config
    system = systems.build(cell.system, cfg, backend or cfg["backend"])
    if patch is not None:
        system = patch(system)
    spans = loops.Spans(bool(args.trace))
    loop = cell.loop(system, cell.mix, args.seed, spans, cfg["name"])
    loop.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(root, WORK_DIR, "trace", cell.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles = CompileCounter()
    compiles.armed = True
    try:
        with spans("window"):
            values = loop.window(args.seconds)
    finally:
        compiles.armed = False
        if args.trace:
            jax.profiler.stop_trace()
    memory_peak = _memory_peak(devs)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}

    breakdown = None
    if args.trace:
        from chipbench import trace as trace_lib
        t_read = time.perf_counter()
        tr = trace_lib.load(trace_dir)
        window = tr.window()
        if not tr.devices or window is None:
            raise RuntimeError(f"the trace under {trace_dir} holds no device "
                               f"operations or no window span")
        plane = sorted(tr.devices)[0]
        metrics = _per_layer(cell, loop, tr, plane, window, kind)
        device["busy_s"] = trace_lib.busy_ns(tr.devices[plane], window) / 1e9
        device["window_s"] = (window[1] - window[0]) / 1e9
        breakdown = trace_lib.breakdown(tr, plane, window)
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
            trace_lib.save(tr, os.path.join(args.keep_trace, "reduced.json"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace read in {time.perf_counter() - t_read:.1f} s",
              file=sys.stderr)
    else:
        values["setup_s"] = setup_s
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise cells.CellError(f"{cell.name}: the {cell.mix['loop']}"
                                      f" loop does not measure "
                                      f"{m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    if args.control:
        control = json.loads(json.dumps(cfg))
        for key, part in cfg["control"].items():
            control[key].update(part)
        loop.substitute(cell.reference, control)
    checks = loop.check(cell.reference, cfg)
    result = {"correct": compare.verdict(checks),
              "attempted": int(loop.attempted), "failed": int(loop.failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["counters"] = dict(loop.counters, **{
        f"window_{k}": v for k, v in compiles.counts.items()}, **{
        k: v for k, (v, lim) in checks.items() if lim is None})
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items() if lim is not None}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except (NoDevice, ImportError, cells.CellError) as exc:
        print(f"chipbench: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        print("chipbench: the run failed", file=sys.stderr)
        return 1
    c = result["counters"]
    print(f"in the window: {c['window_traces']} traces, "
          f"{c['window_compiles']} compiles", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']}  limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
