#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
about it is found by name: its configuration file (``configs``), its
traffic file ``benchmark/traffic/<traffic>.json``, whose ``loop`` names
the module under ``benchmark/loops/`` that drives the program, and one
reader ``benchmark/metrics/<metric>.py`` for each metric. With
``--trace 0`` the run reports the cell's end-to-end metrics; with
``--trace 1`` it records a profiler trace of the window and reports the
per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``, each number compared with its
limit; those numbers are also the last lines of standard error. The run
exits non-zero, and prints no result, where JAX's devices are not TPUs
or are fewer than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

WINDOW_SPAN = "bench.window"


class Refused(Exception):
    """The run cannot be made here (no chip, too few chips, bad cell)."""


def load_module(path: Path):
    """The module in ``path``, loaded once per process."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def resolve(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell ``workload`` with its configuration, traffic and the
    metrics it reports, each found by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "benchmark" / "traffic" / f"{cell['traffic']}.json")
        .read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return SimpleNamespace(
        cell=cell, cfg=cfg, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
        loop=HERE / "loops" / f"{traffic['loop']}.py")


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, for every program however quickly it compiled and however
    large, whatever size limit the environment sets."""
    import jax

    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class Harness:
    """What a loop module gets: the cell, the seed and window length, the
    measured window, and where to put what it measured."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 devices, t_start: float = T_START):
        self.cell, self.cfg, self.traffic = cell.cell, cell.cfg, cell.traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.t_start = t_start
        self.setup_s = None
        self.window_s = None
        self.compiles_in_window = 0
        self.memory_peak_bytes = None
        self.attempted = self.failed = 0
        self.work: dict = {}
        self.checks: list = []
        self.trace_dir = None

    @contextmanager
    def window(self):
        """The measured window. Counts the programs compiled or loaded in
        it; with ``--trace 1`` records the profiler trace of it, marked by
        a host span."""
        import jax
        from jax._src import dispatch

        count = [0]

        def on_event(event, _secs, **_kw):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                count[0] += 1

        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        jax.monitoring.register_event_duration_secs_listener(on_event)
        # no collector pauses inside the window: what set-up left is kept
        # out of the collector's sight, and it stays off until the close
        gc.collect()
        gc.freeze()
        gc.disable()
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            gc.enable()
            gc.unfreeze()
            jax.monitoring.unregister_event_duration_listener(on_event)
            if self.trace:
                jax.profiler.stop_trace()
        self.compiles_in_window = count[0]

    def read_memory_peak(self) -> None:
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)

    def check(self, name: str, value: float, limit: float) -> None:
        """One number compared: the run is correct where it is at most
        ``limit``."""
        self.checks.append((name, float(value), float(limit)))

    def note(self, text: str) -> None:
        print(f"[bench] {text}", file=sys.stderr, flush=True)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim and v == v
                                         for _, v, lim in self.checks)


def read_metrics(entries, h: Harness, reduced, peaks) -> dict:
    ctx = SimpleNamespace(work=h.work, trace=reduced, peaks=peaks,
                          cfg=h.cfg, traffic=h.traffic, setup_s=h.setup_s,
                          window_s=h.window_s)
    out = {}
    for m in entries:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            devices=None, peaks=None, root: Path = ROOT) -> dict:
    """Run the cell and return the result line's object. ``devices`` and
    ``peaks`` default to this machine's TPUs and their row of the peaks
    table; tests pass their own to drive a run on the CPU."""
    import jax

    cell = resolve(workload, root)
    if devices is None:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise Refused(f"needs a TPU, JAX's first device is on platform "
                          f"{devices[0].platform!r}")
        if len(devices) < cell.cell["chips"]:
            raise Refused(f"the cell needs {cell.cell['chips']} chips, JAX "
                          f"finds {len(devices)}")
        devices = devices[:cell.cell["chips"]]
        peaks = peaks_for(devices[0].device_kind)
        use_compile_cache()
    h = Harness(cell, seed, seconds, trace, devices)
    load_module(cell.loop).run(h)
    gc.collect()
    h.note(f"setup {h.setup_s:.3f} s, window {h.window_s:.3f} s, "
           f"whole run {time.perf_counter() - h.t_start:.1f} s")
    reduced = None
    if trace:
        import xplane

        reduced = xplane.reduce_trace(xplane.find_xplane(h.trace_dir),
                                      WINDOW_SPAN)
        shutil.rmtree(h.trace_dir, ignore_errors=True)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           h, reduced, peaks)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": h.memory_peak_bytes}
    result = {"correct": h.correct, "attempted": h.attempted,
              "failed": h.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in reduced.top_ops(10)],
            "idle_gaps": [[n, t] for n, t in reduced.idle_gaps[:10]]}
    result["compiles_in_window"] = h.compiles_in_window
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in h.checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(f"compiles in window: {result['compiles_in_window']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
