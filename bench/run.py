"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix; everything else is found by name:

* ``bench/configs/<config>.json``: the graph's source, sizes and generator
  (``bench/gen/<generator>.py``);
* ``bench/traffic/<mix>.json``: the closed loop (``bench/loops/<loop>.py``)
  and its parameters;
* ``bench/limits/<cell>.json``: the limit of each number that the check
  compares with the plain reference (``bench/reference``);
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

``--trace 0`` sets up, measures ``--seconds`` of the closed loop and reports
the cell's end-to-end metrics.  ``--trace 1`` sets up, traces a short steady
stretch under the profiler and reports the per-layer metrics.  Either way
the outputs of the timed calls are then compared with the reference, and
the last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Run as a script, the interpreter puts bench/ first on the path, where
# trace.py would shadow the standard library's module of that name.
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    del sys.path[0]
NO_CHIP = 3
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


AGE_AT_START = process_age()


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, traffic, limits and metrics."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = _named(spec["workloads"], name, "workload")
    entry = _named(spec["configs"], cell["config"], "config")
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(root, "bench", "limits", name + ".json")) as f:
        limits = json.load(f)

    def for_cell(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": for_cell(spec["end_to_end"]),
            "per_layer": for_cell(spec["per_layer"])}


def setup_jax(root: str = ROOT):
    """JAX with its persistent compile cache at ``<checkout>/.jax_cache``."""
    sys.path[:0] = [p for p in (root, os.path.join(root, "src"))
                    if p not in sys.path]
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The directory is this checkout's own: nothing to evict for others.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def find_chips(jax, chips: int) -> str | None:
    """None when JAX sees at least ``chips`` TPUs, else why not."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return f"JAX finds no accelerator: {e}"
    if devices[0].platform != "tpu":
        return f"JAX finds no TPU (platform {devices[0].platform!r})"
    if len(devices) < chips:
        return f"the cell asks for {chips} chips; JAX finds {len(devices)}"
    return None


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads."""

    def __init__(self, jax):
        self.compiles = 0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.loads += 1

    def read(self):
        return self.compiles, self.loads


def load_reader(name: str, root: str = ROOT):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(jax, summary=None) -> dict:
    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if summary is not None:
        info["busy_s"] = summary["busy_s"]
        info["window_s"] = summary["window_s"]
    return info


def run_traced(jax, loop):
    """Run the loop's traced stretch under the profiler; its reduction."""
    from bench import trace

    tmp = tempfile.mkdtemp(prefix="bench_trace_",
                           dir=os.environ.get("TMPDIR"))
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            loop.traced()
        finally:
            jax.profiler.stop_trace()
        return trace.reduce(trace.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def judge(numbers: dict, limits: dict):
    """(correct, failed answers, {name: [worst, limit]})."""
    checks, correct, failed = {}, True, 0
    for name, values in numbers.items():
        limit = limits[name]
        worst = max(values)
        over = sum(v > limit for v in values)
        failed = max(failed, over)
        correct = correct and over == 0
        checks[name] = [worst, limit]
    return correct, failed, checks


def main(argv=None, *, require_chip: bool = True, root: str = ROOT) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload, root)
    jax = setup_jax(root)
    if require_chip:
        why = find_chips(jax, int(cell["cell"]["chips"]))
        if why:
            print(f"bench: {why}; no result", file=sys.stderr)
            return NO_CHIP
    counter = CompileCounter(jax)
    t_jax = time.perf_counter()
    loop_mod = importlib.import_module(
        f"bench.loops.{cell['traffic']['loop']}")
    loop = loop_mod.Loop(cell["config"], cell["traffic"], args.seed)
    loop.setup()
    t_setup = time.perf_counter()
    setup_s = AGE_AT_START + t_setup - T_START
    in_setup = counter.read()
    print(f"bench: setup_s={setup_s:.3f} start_and_jax_s="
          f"{AGE_AT_START + t_jax - T_START:.3f} loop_setup_s="
          f"{t_setup - t_jax:.3f} compiles_in_setup={in_setup[0]} "
          f"cache_loads_in_setup={in_setup[1]}", file=sys.stderr)

    summary = None
    if args.trace:
        summary = run_traced(jax, loop)
    else:
        loop.window(args.seconds)
    in_window = [b - a for a, b in zip(in_setup, counter.read())]
    device = device_info(jax, summary)
    print(f"bench: compiles_in_window={in_window[0]} "
          f"cache_loads_in_window={in_window[1]}", file=sys.stderr)
    if not args.trace:
        print("bench: " + " ".join(f"{k}={v}" for k, v in
                                   loop.notes().items()), file=sys.stderr)
    loop.release()

    metrics = {}
    if args.trace:
        ctx = dict(loop.trace_context(),
                   device_kind=jax.devices()[0].device_kind)
        for m in cell["per_layer"]:
            value = load_reader(m["name"], root).read(summary, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(loop.end_to_end(), setup_s=(setup_s, "s"))
        for m in cell["end_to_end"]:
            value, unit = measured[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": unit}

    t_check = time.perf_counter()
    numbers = loop.check()
    print(f"bench: check_s={time.perf_counter() - t_check:.3f}",
          file=sys.stderr)
    correct, failed, checks = judge(numbers, cell["limits"])
    for name, (worst, limit) in checks.items():
        print(f"check {name}={worst!r} limit={limit!r}", file=sys.stderr)
    out = {"correct": correct, "attempted": loop.attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary["breakdown"]
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
