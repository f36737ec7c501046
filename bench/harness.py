"""Run one cell of ``BENCHMARK.json`` and print the contract's result line.

The harness knows no cell, configuration, driver or metric by name.  It
reads ``BENCHMARK.json``, then for a cell ``<cell>`` with configuration
``<config>``:

* the configuration file the ``configs`` entry names;
* ``bench/traffic/<cell>.json`` — the traffic mix, the ``driver`` that
  serves it, and the ``limits`` of the numbers its comparison reports;
* ``bench/drivers/<driver>.py`` — a ``Driver`` class (the entry point the
  window drives: set-up, window, record, check);
* ``bench/metrics/<metric>.py`` — one ``read(data)`` per metric, which
  returns a number or ``None`` when the run has nothing to read.

A run: check the chip, set the compile cache, set up (weights, traffic,
warm-up — all of it counted in ``setup_s``), measure for ``--seconds``
(with ``--trace 1`` under the profiler for the first
``trace_seconds``), read the device's peak memory, free the program's
state, compare with the plain reference, print every compared number
beside its limit on standard error, then the result line on standard
output.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = "peaks.json"


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Cell:
    """One workload entry with everything its files hold."""

    name: str
    entry: dict
    config: dict
    traffic: dict
    driver: object
    root: str
    bench: dict


def load_json(path: str) -> dict:
    """A JSON file's object."""
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` as module ``name``."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_dir(root: str) -> str:
    """The benchmark's own directory under ``root``."""
    return os.path.join(root, "bench")


def load_cell(root: str, name: str) -> Cell:
    """Find cell ``name`` and its files under ``root``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(bench_dir(root), "traffic",
                                     f"{name}.json"))
    driver = load_module(os.path.join(bench_dir(root), "drivers",
                                      f"{traffic['driver']}.py"),
                         f"bench_driver_{traffic['driver']}")
    return Cell(name, entry, config, traffic, driver, root, bench)


def metrics_for(cell: Cell, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    group = cell.bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if cell.name in m.get("workloads", [cell.name])]


def read_metrics(cell: Cell, data: dict, trace: bool) -> dict:
    """Each metric's reader applied to ``data``; silent readers drop out."""
    out = {}
    for m in metrics_for(cell, trace):
        mod = load_module(os.path.join(bench_dir(cell.root), "metrics",
                                       f"{m['name']}.py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(data)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Context:
    """What a driver reports through: host spans and, with ``--trace 1``,
    the profiler over the first ``trace_seconds`` of the window."""

    def __init__(self, trace: bool, trace_seconds: float):
        self.trace = trace
        self.trace_seconds = trace_seconds
        self.spans: dict = {}
        self.profile_dir = None
        self.profiling = False
        self._window_note = None
        self._t_window = 0.0
        self.paused_s = 0.0

    def clock(self) -> float:
        """The window's clock: host seconds, less the time the profiler
        took to stop and write its trace."""
        return time.perf_counter() - self.paused_s

    @contextlib.contextmanager
    def span(self, name: str):
        """Time the block as span ``name`` (traced runs only); while the
        profiler runs it is also a host annotation in the trace."""
        if not self.trace:
            yield
            return
        t0 = time.perf_counter()
        if self.profiling:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def start_window(self) -> None:
        """Open the measured window (and the profiler when tracing)."""
        self._t_window = self.clock()
        if self.trace and self.trace_seconds > 0:
            import jax
            self.profile_dir = tempfile.mkdtemp(prefix="bench_trace_")
            # Host annotations and device ops only: tracing every Python
            # call slows the host several times over.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.profile_dir,
                                     profiler_options=opts)
            self._window_note = jax.profiler.TraceAnnotation("bench.window")
            self._window_note.__enter__()
            self.profiling = True

    def unit_done(self) -> None:
        """Called by drivers after each unit of work (a request, a
        horizon): stops the profiler once ``trace_seconds`` have run."""
        if self.profiling and \
                self.clock() - self._t_window >= self.trace_seconds:
            self.stop_profile()

    def stop_profile(self) -> None:
        """Close the profiled part of the window; the time the profiler
        takes to collect and write the trace is not the program's."""
        if self.profiling:
            import jax
            self._window_note.__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.paused_s += time.perf_counter() - t0
            self.profiling = False

    def reduce_trace(self) -> dict | None:
        """The profiled window's device busy time and breakdown."""
        from bench import xtrace

        if self.profile_dir is None:
            return None
        try:
            paths = glob.glob(os.path.join(self.profile_dir, "**",
                                           "*.xplane.pb"), recursive=True)
            return xtrace.reduce_file(paths[0]) if paths else None
        finally:
            shutil.rmtree(self.profile_dir, ignore_errors=True)


def find_chips(chips: int, require_tpu: bool = True):
    """The devices a cell runs on; raises :class:`NoChip` without a TPU
    (unless ``require_tpu`` is off, for tests) or with too few chips."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs


def device_peaks(root: str, kind: str) -> dict:
    """This device's peak rates from ``bench/peaks.json``."""
    table = load_json(os.path.join(bench_dir(root), PEAKS))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/{PEAKS}")
    return table[kind]


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` points), keeping
    every program however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float | None = None, require_tpu: bool = True) -> dict:
    """One run of ``workload``; returns the result object (the last line
    the command prints).  ``require_tpu=False`` (tests) runs on whatever
    JAX finds, reads no peaks and leaves the compile cache alone."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload)
    devs = find_chips(cell.entry["chips"], require_tpu)
    t_import = time.perf_counter() - t_start
    peaks = None
    if require_tpu:
        peaks = device_peaks(root, devs[0].device_kind)
        enable_cache(root)
    used = devs[:cell.entry["chips"]]
    ctx = Context(trace, float(cell.traffic.get("trace_seconds", 5.0)))
    drv = cell.driver.Driver(cell, int(seed), ctx, used)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    ctx.start_window()
    try:
        drv.window(float(seconds))
    finally:
        ctx.stop_profile()
    mem = memory_peak(used)
    data = drv.record()
    data.update(setup_s=setup_s, spans=ctx.spans, peaks=peaks,
                trace=ctx.reduce_trace() if trace else None)
    drv.release()
    checks = drv.check()
    limits = cell.traffic["limits"]
    correct = bool(checks) and all(
        math.isfinite(v) and v <= limits[k] for k, v in checks.items())
    # JSON has no infinity: a number that could not be read is a string.
    compared = {k: {"value": v if math.isfinite(v) else str(v),
                    "limit": limits[k]} for k, v in checks.items()}
    result = {
        "correct": correct,
        "attempted": int(data["attempted"]),
        "failed": int(data["failed"]),
        "metrics": read_metrics(cell, data, trace),
        "device": {"platform": used[0].platform,
                   "kind": used[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": mem},
    }
    if trace and data["trace"] is not None:
        result["device"]["busy_s"] = data["trace"]["busy_s"]
        result["device"]["window_s"] = data["trace"]["window_s"]
        result["breakdown"] = {k: data["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    result["run"] = dict(import_s=t_import, units=data.get("units"),
                         window_s=data["elapsed_s"],
                         **getattr(drv, "setup_split", {}))
    if "dispositions" in data:
        result["run"]["dispositions"] = data["dispositions"]
    result["compared"] = compared
    return result


def main(argv=None, t_start: float | None = None) -> int:
    """The command line of ``bench/run.py``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run(ROOT, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in res["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0
