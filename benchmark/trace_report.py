#!/usr/bin/env python3
"""One traced run of a benchmark cell, with the program's spans and
scopes read from its trace beside the cell's per-layer metrics.

    python3 benchmark/trace_report.py --workload <cell> --seed <n> \\
        --seconds <s>

Runs the cell as ``run.py --trace 1`` does, keeps the trace long enough
to reduce it with :mod:`program_trace` too, and prints one JSON object:
``metrics`` (the cell's per-layer metrics, as ``run.py`` reports them),
``program`` (the six measures of :mod:`program_trace`), ``idle_by_span``
(idle device seconds by the innermost program span open, ``null`` for
none), and for the decode and train programs the device seconds by
scope and the unclaimed instructions with the most time.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402  (sets the import path of the program)


def report(workload: str, seed: int, seconds: float) -> dict:
    import jax

    import program_trace as P
    import xplane

    cell = run.resolve(workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise run.Refused(f"needs a TPU, JAX's first device is on platform "
                          f"{devices[0].platform!r}")
    devices = devices[:cell.cell["chips"]]
    peaks = run.peaks_for(devices[0].device_kind)
    run.use_compile_cache()
    h = run.Harness(cell, seed, seconds, True, devices)
    run.load_module(cell.loop).run(h)
    path = xplane.find_xplane(h.trace_dir)
    try:
        reduced = xplane.reduce_trace(path, run.WINDOW_SPAN)
        t0 = time.perf_counter()
        t = P.reduce(path, run.WINDOW_SPAN)
        reduce_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(h.trace_dir, ignore_errors=True)
    idle = sorted(t.idle_by_span().items(), key=lambda kv: -kv[1])
    out = {
        "workload": workload, "seed": seed, "setup_s": h.setup_s,
        "window_s": reduced.window_s, "busy_s": reduced.busy_s,
        "correct": h.correct,
        "metrics": run.read_metrics(cell.per_layer, h, reduced, peaks),
        "program": {f.__name__: f(t) for f in (
            P.decode_gap_p95_ms, P.lanes_per_step, P.idle_engine_share,
            P.decode_scan_copy_share, P.optimizer_share,
            P.head_loss_share)},
        "idle_by_span": idle,
        "spans": {n: len(t.named(n)) for n in sorted({s.name
                                                      for s in t.spans})},
        "top_ops": reduced.top_ops(12),
        "idle_gaps": reduced.idle_gaps,
        "reduce_s": reduce_s,
    }
    for module in ("pool_step", "train_step"):
        by_scope = t.module_s(module)
        if by_scope:
            out[module] = {"by_scope": sorted(
                by_scope.items(), key=lambda kv: -kv[1]),
                "unclaimed": t.top_unclaimed(module, 12)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = report(args.workload, args.seed, args.seconds)
    except run.Refused as e:
        print(f"trace_report.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
