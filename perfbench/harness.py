"""Shared plumbing of the benchmark: where things live, how a cell is
looked up by name, the device check and the result line.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name. ``BENCHMARK.json`` names them; each lives in a file of its own:

    perfbench/configs/<config>.json          sizes, source, reduced, assumed
    perfbench/configs/<reference>.py         the configuration's plain reference
    perfbench/traffic/<traffic>.json         generator kind + parameters
    perfbench/generators/<kind>.py           open_loop, closed_loop, train_steps
    perfbench/drivers/<kind>.py              serve, train
    perfbench/e2e_metrics/<metric>.py        one reader per end-to-end metric
    perfbench/layer_metrics/<metric>.py      one reader per per-layer metric

A later PR adds a cell by adding files and one entry each to
``BENCHMARK.json``; it edits none.
"""

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: scratch of a run (plans, client logs, traces): inside the checkout,
#: listed in .gitignore, at a fixed path
RUN_DIR = os.path.join(ROOT, ".perfbench_run")


#: sizes of the public config.json -> fields of the program's presets
PRESET_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "hidden_size",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "intermediate_size": "intermediate_size",
             "vocab_size": "vocab_size", "sliding_window": "sliding_window",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def preset_overrides(config):
    """The configuration file's sizes as fields of the program's preset,
    with the file's own ``preset_overrides`` on top."""
    out = {field: config[key] for key, field in PRESET_FIELDS.items()
           if key in config}
    out.update(config.get("preset_overrides", {}))
    return out


def start_profile(run_dir):
    """Start JAX's profiler (python tracer off: it slows the host) from
    this process, the one that holds the chip. Returns the trace's
    directory; the caller stops it with ``jax.profiler.stop_trace()``."""
    import shutil

    import jax
    trace_dir = os.path.join(run_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return trace_dir


def read_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_path(path):
    """Import a python file by path (names may hold ``-``, so they are not
    python identifiers)."""
    mod_name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.realpath(path))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_module(kind_dir, name, roots=(HERE,)):
    """``<root>/<kind_dir>/<name>.py`` of the first root that has it."""
    for root in roots:
        path = os.path.join(root, kind_dir, f"{name}.py")
        if os.path.exists(path):
            return load_path(path)
    raise SystemExit(f"perfbench: no {kind_dir}/{name}.py under {list(roots)}")


class Cell:
    """One entry of ``workloads`` with everything its name leads to. Files
    are looked for under the ``paths`` of the BENCHMARK.json in use, then
    here, so another BENCHMARK.json (a test's, a rehearsal's) brings its
    own data and readers and shares the code."""

    def __init__(self, bench, entry, bench_dir=ROOT):
        self.bench = bench
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.roots = []
        for root in [os.path.join(bench_dir, p) for p in bench["paths"]] \
                + [HERE]:
            if os.path.realpath(root) not in map(os.path.realpath, self.roots):
                self.roots.append(root)
        by_name = {c["name"]: c for c in bench["configs"]}
        if entry["config"] not in by_name:
            raise SystemExit(f"perfbench: cell {self.name} names config "
                             f"{entry['config']!r}, which BENCHMARK.json lacks")
        self.config_entry = by_name[entry["config"]]
        self.config = read_json(os.path.join(bench_dir,
                                             self.config_entry["file"]))
        self.traffic_name = entry["traffic"]
        self.traffic = read_json(self.find("traffic",
                                           f"{entry['traffic']}.json"))

    def find(self, kind_dir, filename):
        for root in self.roots:
            path = os.path.join(root, kind_dir, filename)
            if os.path.exists(path):
                return path
        raise SystemExit(f"perfbench: no {kind_dir}/{filename} under "
                         f"{self.roots}")

    def module(self, kind_dir, name):
        return load_module(kind_dir, name, self.roots)

    def metrics(self, section):
        """Entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those without a ``workloads`` list, or that list it."""
        return [m for m in self.bench[section]
                if "workloads" not in m or self.name in m["workloads"]]


def find_cell(workload, bench_path=None):
    bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    bench = read_json(bench_path)
    for entry in bench["workloads"]:
        if entry["name"] == workload:
            return Cell(bench, entry, os.path.dirname(bench_path))
    raise SystemExit(f"perfbench: BENCHMARK.json has no workload {workload!r}; "
                     f"it has {[w['name'] for w in bench['workloads']]}")


def require_program():
    """The benchmark measures the program; without it there is nothing to
    run (a directory that holds only the benchmark exits nonzero)."""
    for need in ("deepspeed_tpu/__init__.py", "bin/dstpu_serve"):
        if not os.path.exists(os.path.join(ROOT, need)):
            raise SystemExit(f"perfbench: {need} is missing: the benchmark "
                             "runs the program from the checkout it is in")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def require_devices(chips, rehearse):
    """JAX's devices, or a nonzero exit: no accelerator, or fewer chips
    than the cell asks for. A rehearsal takes what JAX has."""
    import jax
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"perfbench: JAX found no accelerator "
                         f"({devs[0].platform}); a device metric is never "
                         "taken from a CPU run")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell asks for {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts the programs this process asks XLA for, by JAX's own
    monitoring events, with the time of each: the frame programs and the
    small ones host code dispatches between frames alike. ``built`` is
    every request (JAX's backend-compile event wraps the cache lookup
    too); ``hits`` are those the persistent cache answered. Either way the
    caller waited: a load from the cache stalls a serve loop too."""

    def __init__(self):
        import jax.monitoring
        try:
            from jax._src.dispatch import BACKEND_COMPILE_EVENT as event
        except ImportError:
            event = "/jax/core/compile/backend_compile_duration"
        self.event, self.built, self.hits = event, [], []
        jax.monitoring.register_event_duration_secs_listener(self._on_built)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_built(self, event, duration, **kwargs):
        del duration, kwargs
        if event == self.event:
            self.built.append(time.monotonic())

    def _on_event(self, event, **kwargs):
        del kwargs
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.monotonic())

    def between(self, t0, t1):
        """Programs asked for in [t0, t1), compiled or loaded."""
        return sum(t0 <= t < t1 for t in self.built)


def cache_every_program():
    """By default JAX keeps only programs that took a second to compile.
    The program dispatches many small ones between frames; keep those too,
    so that a second run in a checkout compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_block(devices, trace_red=None):
    """The ``device`` object of the result line, as JAX reports it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace_red is not None:
        out["busy_s"] = trace_red["busy_s"]
        out["window_s"] = trace_red["window_s"]
    return out


def log(msg):
    """Earlier lines go to stderr; stdout carries the result line alone."""
    print(f"[perfbench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def read_metrics(cell, section, kind_dir, ctx, rehearse):
    """Call each of the cell's metric readers over the run's context. A
    reader that finds nothing to read returns None and is left out. A
    rehearsal (no chip) keeps only what needs no device: counts."""
    out = {}
    for entry in cell.metrics(section):
        if section == "per_layer":
            moved = [m for m in cell.metrics("end_to_end")
                     if m["name"] == entry["moves"]]
            if not moved:
                continue
        value = cell.module(kind_dir, entry["name"]).read(ctx)
        if value is None:
            log(f"{entry['name']}: nothing to read, left out")
            continue
        if rehearse and entry["source"] != "program_counter":
            log(f"{entry['name']}: read (rehearsal: not a measurement, not "
                "printed)")
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def print_result(correct, attempted, failed, metrics, device, breakdown=None):
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
