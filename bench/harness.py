"""One run of one cell: set-up, the measured window, the check against
the plain reference, the metrics, and the result line.

A run is one process.  It generates the cell's graph from ``--seed`` on
the host, hands it to the program, warms up the cell's own shapes
(set-up), measures for ``--seconds``, frees the program's state, and
compares every answer of the window with the reference.  With
``--trace 1`` the window runs under the JAX profiler and the metrics
are the cell's per-layer metrics; otherwise they are its end-to-end
metrics.  Earlier lines of standard output are JSON notes; the last
line is the result.  The numbers compared, each beside its limit, are
the last lines of standard error and the result's last key.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import logging
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import reference
from bench import trace as trace_mod
from bench.layout import Layout

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
XLA_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """The run found no accelerator, or fewer chips than the cell asks."""


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"bench: no TPU found: JAX sees {len(devs)} "
                     f"{d.platform} device(s) of kind {d.device_kind!r}; "
                     "the benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} TPU chips, "
                     f"JAX sees {len(devs)}")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def host_device_info() -> dict:
    """What a run without the chip check reports (tests only)."""
    import jax

    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


class Compiles:
    """Counts lowerings and XLA compiles while installed; with
    ``names=True`` also keeps the first few of JAX's compile log lines,
    which name what compiled."""

    def __init__(self, names: bool = False):
        self.lowerings = 0
        self.xla = 0
        self.names = [] if names else None
        self._handler = None

    def _listen(self, event: str, secs: float, **_) -> None:
        if event == LOWERING:
            self.lowerings += 1
        elif event == XLA_COMPILE:
            self.xla += 1

    def __enter__(self):
        import jax
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        if self.names is not None:
            names = self.names

            class Keep(logging.Handler):
                def emit(self, record):
                    msg = record.getMessage()
                    if len(names) < 8 and msg.startswith("Compiling"):
                        names.append(msg[:160])

            self._handler = Keep()
            logging.getLogger("jax").addHandler(self._handler)
            jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        import jax
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)
        if self._handler is not None:
            jax.config.update("jax_log_compiles", False)
            logging.getLogger("jax").removeHandler(self._handler)


def span(name: str):
    """A harness span in the profiler's trace (cheap when not tracing)."""
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Context:
    """What the drivers get: the cell and the harness's copy of the
    graph (the reference's input; the program gets its own)."""

    cell: object
    seed: int
    n: int
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    out_deg: np.ndarray
    span: object = span
    weights: list = dataclasses.field(default_factory=list)
    # weights[v]: the arc weights of graph version v (deltas applied)
    relabel: np.ndarray | None = None
    # relabel[v]: the id of canonical vertex v in this run's graph

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: object
    kind: str
    setup_s: float
    records: object
    arcs: int
    reduction: trace_mod.Reduction | None
    device: dict

    @property
    def peaks(self) -> dict:
        from bench.peaks import peaks
        return peaks(self.device["kind"])


def note(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, layout: Layout | None = None,
             require_chip: bool = True, system_factory=None,
             traffic: dict | None = None) -> dict:
    """One run; returns the result object (the caller prints it).

    ``system_factory(ctx)``, where given, builds what stands in the
    program's place (the control runs and the fault tests); otherwise
    the cell's driver builds the program.  ``require_chip=False`` skips
    the look for a TPU and the persistent compile cache (tests on the
    CPU).  ``traffic`` overrides keys of the cell's mix (the knee sweep,
    ``bench/sweep.py``).
    """
    layout = layout or Layout()
    cell = layout.cell(workload)
    if traffic:
        cell = dataclasses.replace(cell, traffic={**cell.traffic, **traffic})
    device = (device_info(cell.chips) if require_chip
              else host_device_info())
    cache_dir = None
    if require_chip:
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()

    graph = cell.config["graph"]
    family = layout.module("graphs", graph["family"])
    n, src, dst, w = family.make(graph, seed)
    relabel = (family.relabel(graph, seed) if hasattr(family, "relabel")
               else np.arange(n))
    ctx = Context(cell=cell, seed=seed, n=n, src=src, dst=dst, w=w,
                  out_deg=np.bincount(src, minlength=n), weights=[w],
                  relabel=relabel)
    driver = layout.module("drivers", cell.traffic["driver"])
    with Compiles() as setup_compiles:
        system = (system_factory or driver.build)(ctx)
        driver.warm(ctx, system)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        import jax.profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # harness spans need only TraceMe
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with Compiles(names=True) as window_compiles:
        records = driver.measure(ctx, system, float(seconds))
    reduction = None
    if trace:
        jax.profiler.stop_trace()
        reduction = trace_mod.reduce_file(trace_mod.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    device["memory_peak_bytes"] = _peak_bytes()
    note(note="setup", cache_dir=cache_dir, setup_s=setup_s,
         setup_lowerings=setup_compiles.lowerings,
         setup_xla_compiles=setup_compiles.xla,
         backend=getattr(system, "backend", None))
    note(note="window", compiles_in_window=window_compiles.lowerings,
         xla_compiles_in_window=window_compiles.xla,
         compiled_in_window=window_compiles.names,
         **driver.window_notes(records))
    del system
    gc.collect()

    tally = driver.check(ctx, records)
    checks = tally.checks()
    run = Run(cell=cell, kind=cell.traffic["driver"], setup_s=setup_s,
              records=records, arcs=len(src), reduction=reduction,
              device=device)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = layout.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    result = {"correct": reference.correct(checks),
              "attempted": int(records.attempted),
              "failed": int(records.failed), "metrics": metrics,
              "device": device}
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction.device_ops,
                               "idle_gaps": reduction.idle_gaps}
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    """Earlier lines are out; print the checks to standard error and
    the result as the last line of standard output."""
    sys.stdout.flush()
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


