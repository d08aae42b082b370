#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (its file of sizes, under ``configs/``)
and a traffic mix (``traffic/<mix>.json``, a data file whose ``driver``
names the loop in ``drivers/`` that runs it).  A run builds the data on
the device from ``--seed``, warms every program the window will use
(all of that is set-up), measures for ``--seconds`` seconds, reads the
device's peak memory, frees the program's state, and then checks what
the window produced against the plain reference (``reference.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the profiler and prints the cell's per-layer
metrics, each read by its own file under ``metrics/``.  The last line
of standard output is one JSON object; the numbers compared and their
limits are also the last lines of standard error.  A host without a
TPU, or with fewer chips than the cell asks for, exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if __name__ == "__main__":
    # the checkout root, not this directory, is the import root
    sys.path[0] = ROOT


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileWatch:
    """Counts, while open, jax's compile events on any thread (tracing,
    lowering, backend compiles, persistent-cache reads): a window that
    reuses warmed programs has none."""

    _EVENTS = ("/jax/core/compile/",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __enter__(self):
        import jax.monitoring

        self._seen = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, name, secs, **_kw):
        if name.startswith(self._EVENTS):
            self._seen.append(name)

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)
        self.compiles = len(self._seen)
        return False


class GcWatch:
    """Times, while open, the interpreter's garbage collections, so that
    a stall in the window can be told from a collection."""

    def __enter__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._on)
        return self

    def _on(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)
            self._t = None

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)
        return False


def load_module(path: str, name: str):
    """Import one file of the benchmark by its path (metric files carry
    dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one cell is, found by name from the manifest."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver_path: str
    end_to_end: list
    per_layer: list
    metric_dir: str


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its
    configuration, traffic mix, driver and metrics."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json"
                         f" (known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                driver_path=os.path.join(bench, "drivers",
                                         traffic["driver"] + ".py"),
                end_to_end=e2e, per_layer=layer,
                metric_dir=os.path.join(bench, "metrics"))


@dataclasses.dataclass
class Context:
    """What a driver is handed: the cell's data files, the seed, the
    window's length, the devices, and ``annotation(name)``, a host annotation
    in traced runs."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    devices: list
    trace: bool

    def annotation(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class LayerView:
    """What a per-layer metric's reader is handed: the reduced trace
    (None when the run was not traced), the driver's layer numbers, and
    the cell."""

    trace: object
    stats: dict
    traffic: dict
    device_kind: str
    chips: int


def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of the cache key).  Every program is cached, however quickly it
    compiled, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def chips_for(n: int, require_tpu: bool) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r});"
                     " the benchmark runs only on the chip")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, root: str = ROOT) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax

    use_compile_cache(root)
    import crdt_tpu  # noqa: F401  (64-bit mode before any trace)
    from crdt_tpu.utils import tracing

    devices = chips_for(cell.chips, require_tpu)
    ctx = Context(config=cell.config, traffic=cell.traffic, seed=seed,
                  seconds=seconds, devices=devices, trace=trace)
    driver = load_module(cell.driver_path, "bench_driver").Driver(ctx)
    driver.setup()

    log_dir = None
    if trace:
        tracing.enable(True)
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        # the Python tracer would slow the host legs it measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
    setup_s = time.perf_counter() - T_START
    try:
        with CompileWatch() as watch, GcWatch() as collections, \
                ctx.annotation("bench.window"):
            result = driver.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
            tracing.enable(False)
    pauses = collections.pauses or [0.0]
    print(f"window: {json.dumps(result.get('log', {}))} "
          f"compile events {watch.compiles}; gc {len(collections.pauses)} "
          f"collections, longest {1000 * max(pauses):.1f} ms, "
          f"total {1000 * sum(pauses):.1f} ms", file=sys.stderr)

    memory = peak_bytes(devices)
    reduced = None
    if trace:
        from benchmark.tracefile import find_xplane, reduce_xplane

        reduced = reduce_xplane(find_xplane(log_dir), devices=len(devices))
        shutil.rmtree(log_dir, ignore_errors=True)
    stats = driver.layer_stats(result)
    t_check = time.perf_counter()
    driver.release()
    checks = driver.check()
    print(f"check: {time.perf_counter() - t_check:.1f} s", file=sys.stderr)

    kind = devices[0].device_kind
    metrics = {}
    if trace:
        view = LayerView(trace=reduced, stats=stats, traffic=cell.traffic,
                         device_kind=kind, chips=len(devices))
        for m in cell.per_layer:
            reader = load_module(os.path.join(cell.metric_dir,
                                              m["name"] + ".py"),
                                 "bench_metric")
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(driver.end_to_end(result), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": all(v <= lim for _, v, lim in checks)
           and result["failed"] == 0,
           "attempted": result["attempted"], "failed": result["failed"],
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.top_ops(10),
                            "idle_gaps": reduced.idle_gaps(10)}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(args.workload, root)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   require_tpu=require_tpu, root=root)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def _cli() -> None:
    """``main``, ending the process at once when it fails, so a thread a
    failed window left blocked never holds the chip."""
    try:
        code = main()
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        code = 2
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int) and e.code is not None:
            print(e.code, file=sys.stderr)
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code or 0)


if __name__ == "__main__":
    _cli()
