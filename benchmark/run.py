"""Run one cell of BENCHMARK.json once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix, entry or
per-layer metric is in a file of its own, found by the name in
BENCHMARK.json (see benchmark/README.md); this file names none of them.
The last line of standard output is the result. `--tiny` runs the cell at
the toy sizes its files state, on whatever platform JAX has, for
rehearsing the harness on a CPU: its line says `"tiny": true` and carries
no device metric. Without it and without the chips the cell asks for the
run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up counts from here

import argparse
import glob
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, ".bench_out")  # traces; emptied by each traced run
CACHE = os.path.join(ROOT, ".jax_cache")


def load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


class TraceSlice:
    """The profiler over a slice of the window, `lead_s` after its start
    and `seconds` long, so that a traced run stays small enough to read.
    The entry calls `poll` between operations and `close` after the window."""

    def __init__(self, on: bool, rec, lead_s: float, seconds: float):
        self.on, self.rec = on, rec
        self.lead_s, self.seconds = lead_s, seconds
        self.began = self.ended = None
        self._note = None
        self.dir = os.path.join(OUT, "trace")

    def poll(self, elapsed: float) -> None:
        if not self.on:
            return
        if self.began is None and elapsed >= self.lead_s:
            self._start()
        elif (self.began is not None and self.ended is None
              and time.monotonic() - self.began >= self.seconds):
            self.close()

    def _start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the host spans are the benchmark's own
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.rec.annotate = True
        self._note = jax.profiler.TraceAnnotation("bench.traced")
        self._note.__enter__()
        self.began = time.monotonic()

    def close(self) -> None:
        if self.began is None or self.ended is not None:
            return
        import jax

        self.ended = time.monotonic()
        self._note.__exit__(None, None, None)
        self.rec.annotate = False
        jax.profiler.stop_trace()

    def reduced(self):
        """The reduced trace, or None; the trace's files are removed."""
        from benchmark import xplane

        files = glob.glob(os.path.join(self.dir, "plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        red = xplane.reduce_file(files[-1])
        keep = os.environ.get("BENCH_KEEP_REDUCED")  # for recording testdata
        if keep:
            with open(keep, "w") as f:
                json.dump(xplane.excerpt(red, 0.25), f)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


class Run:
    """What an entry is given."""

    def __init__(self, args, cell: dict, config: dict, traffic: dict):
        from benchmark.recorder import CompileMeter, Recorder, peak_bytes

        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.tiny = args.tiny
        self.t_process = T_PROCESS
        self.rec = Recorder()
        self.meter = CompileMeter()
        self.peak_bytes = peak_bytes
        tr = {**traffic.get("trace", {}), **cell.get("trace", {})}
        length = min(float(tr.get("seconds", 4.0)), self.seconds / 2)
        lead = min(float(tr.get("lead_s", 3.0)), self.seconds / 4)
        self.trace = TraceSlice(bool(args.trace), self.rec, lead, length)


class Reading:
    """What a per-layer reader is given."""

    def __init__(self, run: Run, result: dict, trace, device: dict, peaks):
        self.cell, self.config, self.traffic = run.cell, run.config, run.traffic
        self.rec = run.rec
        self.counters = result["counters"]
        self.window = result["window"]  # (t0, t1) on time.monotonic
        self.traced = (run.trace.began, run.trace.ended)  # the same clock
        self.trace = trace  # xplane.summarise's dict, or None
        self.device, self.peaks = device, peaks

    def roofline(self, name: str):
        return importlib.import_module("benchmark.rooflines." + name)


def device_peaks(kind: str) -> dict:
    table = load("benchmark/peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"benchmark/peaks.json has no device kind {kind!r}")
    return table[kind]


def per_layer(manifest: dict, workload: str, reading: Reading) -> dict:
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        spec = load(f"benchmark/layer_metrics/{m['name']}.json")
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(reading, spec)
        if value is not None:  # a reader that finds nothing to read says nothing
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on whatever platform JAX has: a rehearsal "
                         "of the harness, never a device number")
    args = ap.parse_args(argv)

    manifest = load("BENCHMARK.json")
    workload = named(manifest["workloads"], args.workload, "workload")
    cell = load(f"benchmark/workloads/{workload['name']}.json")
    config = load(named(manifest["configs"], workload["config"], "config")["file"])
    traffic = load(f"benchmark/traffic/{workload['traffic']}.json")
    entry = importlib.import_module("benchmark.entries." + cell["entry"])

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.tiny and (device["platform"] != "tpu"
                          or device["count"] < workload["chips"]):
        print(f"[bench] {workload['name']} needs {workload['chips']} TPU chip(s); "
              f"JAX has {device}", file=sys.stderr)
        return 3
    peaks = None if args.tiny else device_peaks(device["kind"])

    run = Run(args, cell, config, traffic)
    result = entry.run(run)

    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    metrics = {"setup_s": {"value": result["setup_s"], "unit": "s"}}
    breakdown = None
    if args.trace:
        from benchmark import xplane

        red = run.trace.reduced()
        summary = xplane.summarise(red) if red else None
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
        reading = Reading(run, result, summary, device, peaks)
        metrics = per_layer(manifest, workload["name"], reading)
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        for name, value in result["end_to_end"].items():
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}

    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in result["compared"]}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    line = {
        "correct": correct,
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    if args.tiny:
        line["tiny"] = True
    line["counters"] = result["counters"]
    line["setup_spans_s"] = {
        k: sum(d for _, d in v) for k, v in run.rec.spans.items()
        if k.startswith(("setup.", "check."))}
    line["compared"] = compared
    for name, c in compared.items():
        print(f"[bench] compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
