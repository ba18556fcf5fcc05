"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It finds everything by name: the cell's configuration file,
its traffic mix, the mix's runner (`benchmarks/runners/<runner>.py`), the
cell's limits (`benchmarks/limits/<cell>.json`) and, with `--trace 1`, one
reader per per-layer metric (`benchmarks/layer_metrics/<metric>.py`). The
last line of standard output is the result object; everything else goes
to standard error. It exits non-zero, with no result, when JAX finds no
TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.common import load_json, load_module, log  # noqa: E402


class Cell:
    """What BENCHMARK.json and the data files say about one cell."""

    def __init__(self, name: str, toy: bool):
        self.bench = load_json("BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"there are {sorted(cells)}")
        self.entry = cells[name]
        self.name, self.chips = name, self.entry["chips"]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(configs[self.entry["config"]]["file"])
        self.traffic = load_json("benchmarks", "traffic",
                                 self.entry["traffic"] + ".json")
        limits = ("benchmarks", "limits", name + ".json")
        self.limits = (load_json(*limits)
                       if os.path.exists(os.path.join(ROOT, *limits)) else {})
        if toy:   # tests only: tiny widths, so never a number to report
            self.config = _merged(self.config, self.config["toy"])
            self.traffic = _merged(self.traffic, self.traffic.get("toy", {}))
            self.limits = self.limits.get("toy", {})

    def metrics(self, group: str) -> list:
        """The cell's entries of `end_to_end` or `per_layer`."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_merged(base[k], v)
                  if isinstance(v, dict) and isinstance(base.get(k), dict)
                  else v)
    return out


class Context:
    """What a runner is given, and where it reports the window."""

    def __init__(self, cell: Cell, args, devices, peaks):
        self.config, self.traffic, self.peaks = cell.config, cell.traffic, peaks
        self.limits = cell.limits
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.toy = bool(args.trace), bool(args.toy)
        self.control, self.fault = args.control, args.fault
        self.keep_trace = bool(args.keep_trace)
        self.devices = devices
        self.setup_s = None
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)

    def open_window(self) -> float:
        """Called by the runner at the first measured request or step."""
        now = time.perf_counter()
        self.setup_s = now - PROCESS_START
        return now

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    def tracer(self):
        from benchmarks import tracing
        return tracing.TraceSlice(
            self.trace_dir, "/host:CPU" if self.toy else "/device:TPU:",
            keep=self.keep_trace)

    def phase(self, name: str) -> None:
        """Where set-up's seconds go, on standard error."""
        log(f"set-up: {name} at {time.perf_counter() - PROCESS_START:.2f} s")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # never passed by the driver: tests drive the harness at toy widths on
    # the CPU, calibration reads the control, tests plant a fault
    p.add_argument("--toy", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--control", default=None, help=argparse.SUPPRESS)
    p.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    p.add_argument("--keep-trace", type=int, default=0,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        log("benchmarks/run.py: no program beside the benchmark "
            f"({ROOT}/paddle_tpu is missing)")
        return 2
    cell = Cell(args.workload, bool(args.toy))

    import jax
    devices = jax.devices()
    if args.toy:
        if devices[0].platform != "cpu":   # a toy line never names a TPU
            log("benchmarks/run.py: --toy runs on the CPU only "
                "(JAX_PLATFORMS=cpu)")
            return 2
    elif devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"benchmarks/run.py: {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found {len(devices)} x {devices[0].platform}")
        return 2
    devices = devices[: cell.chips]

    from benchmarks import peaks
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program goes to the cache, however quick its compilation:
    # the second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ctx = Context(cell, args, devices, None if args.toy else
                  peaks.peaks_for(devices[0].device_kind))
    runner = load_module("runners", cell.traffic["runner"])
    with contextlib.redirect_stdout(sys.stderr):   # the program's chatter
        out = runner.run(ctx)

    values = dict(out["end_to_end"], setup_s=ctx.setup_s)
    if args.trace:
        observed = dict(out["observed"], end_to_end=out["end_to_end"],
                        config=cell.config, traffic=cell.traffic,
                        peaks=ctx.peaks)
        metrics = {}
        for m in cell.metrics("per_layer"):
            value = load_module("layer_metrics", m["name"]).read(observed)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if args.trace:
        device["busy_s"] = out["observed"]["busy_s"]
        device["window_s"] = out["observed"]["trace_window_s"]
    checks = out["checks"]
    line = {"correct": bool(checks) and all(c["ok"] for c in checks),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device}
    if args.trace and out["observed"].get("breakdown"):
        line["breakdown"] = out["observed"]["breakdown"]
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"],
                                  "ok": c["ok"]} for c in checks}
    for name, v in sorted(values.items()):
        log(f"measured {name} = {v}")
    for c in checks:
        log(f"check {c['name']}: value {c['value']} limit {c['limit']} "
            f"{'ok' if c['ok'] else 'NOT OK'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
