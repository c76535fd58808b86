#!/usr/bin/env python3
"""One traced run of a cell, reduced by round pass.

    python3 bench/passes.py --workload <cell> --seed <n> --seconds <s> \
        [--out DIR]

Sets the cell up and measures its window as ``bench/run.py --trace 1``
does (the same drivers, seeds and profiler options), with the
program's optimized HLO dumped and no persistent compile cache, so
that every program compiles here and its text is kept.  Prints one
JSON line: the end-to-end metrics of the traced window, device time
per outermost ``sssp.*`` scope (``scope_s``), the fusions that mix
passes, idle time per gap label (``idle_s``), the breakdown with
pass-labelled operations and compound idle-gap labels
(``bench.scopes``), the service's counters (every
numeric one of ``SSSPService.stats``), and the cell's per-layer
metrics with the per-pass ones (``arc_ms``, ``vertex_ms``,
``rounds_per_query``, ``delta_rounds``, ``syncs_per_query``).
``--out`` keeps the trace and the HLO text there.  It does not check
the answers: ``bench/run.py`` does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PASS_METRICS = ("arc_ms", "vertex_ms", "rounds_per_query", "delta_rounds",
                "syncs_per_query")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="directory that keeps the trace and the HLO")
    args = ap.parse_args(argv)
    work = Path(args.out or tempfile.mkdtemp(prefix="bench_passes_"))
    dump = work / "hlo"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_dump_to={dump}"
                               " --xla_dump_hlo_as_text").strip()
    print(json.dumps(run(args.workload, args.seed, args.seconds, work)),
          flush=True)
    if args.out is None:
        shutil.rmtree(work, ignore_errors=True)
    return 0


def run(workload: str, seed: int, seconds: float, work: Path, *,
        layout=None, require_chip: bool = True) -> dict:
    """The traced run; ``layout`` and ``require_chip=False`` as for
    :func:`bench.harness.run_cell` (tests on the CPU)."""
    import jax
    import jax.profiler
    import numpy as np

    from bench import harness, scopes, trace
    from bench.layout import Layout

    # JAX keys its persistent cache on the program with the op_name
    # metadata stripped: a cached executable compiled without the
    # scopes would be reused and every operation would read unscoped
    jax.config.update("jax_enable_compilation_cache", False)

    layout = layout or Layout()
    cell = layout.cell(workload)
    device = (harness.device_info(cell.chips) if require_chip
              else harness.host_device_info())
    graph = cell.config["graph"]
    family = layout.module("graphs", graph["family"])
    n, src, dst, w = family.make(graph, seed)
    relabel = (family.relabel(graph, seed) if hasattr(family, "relabel")
               else np.arange(n))
    ctx = harness.Context(cell=cell, seed=seed, n=n, src=src, dst=dst, w=w,
                          out_deg=np.bincount(src, minlength=n),
                          weights=[w], relabel=relabel)
    driver = layout.module("drivers", cell.traffic["driver"])
    system = driver.build(ctx)
    if hasattr(system, "service"):
        # the window's counters: every numeric one the service keeps
        stats = system.service.stats
        system.counters = lambda: {k: v for k, v in stats.items()
                                   if isinstance(v, (int, float))}
    driver.warm(ctx, system)
    log_dir = work / "trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    records = driver.measure(ctx, system, seconds)
    jax.profiler.stop_trace()
    red = scopes.reduce_file(trace.find_xplane(str(log_dir)),
                             str(work / "hlo"))
    r = harness.Run(cell=cell, kind=cell.traffic["driver"], setup_s=setup_s,
                    records=records, arcs=len(src), reduction=red,
                    device=device)
    names = ([m["name"] for m in cell.end_to_end + cell.per_layer]
             + [f"{p}.{'teps' if r.kind == 'batch' else 'latency'}"
                for p in PASS_METRICS])
    metrics = {}
    for name in dict.fromkeys(names):
        value = layout.reader(name).read(r)
        if value is not None:
            metrics[name] = float(value)
    return dict(workload=workload, seed=seed, traced=True, metrics=metrics,
                busy_s=red.busy_s, window_s=red.window_s,
                scope_s=red.scope_s, mixed=red.mixed, idle_s=red.idle_s,
                breakdown=dict(device_ops=red.device_ops,
                               idle_gaps=red.idle_gaps),
                counters=getattr(records, "counters", None),
                notes=driver.window_notes(records), device=device)


if __name__ == "__main__":
    sys.exit(main())
