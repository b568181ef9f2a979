#!/usr/bin/env python3
"""Readings that set a cell's fixed numbers, made once on the chip when
the cell is defined. The benchmark's own runs never call this.

    python3 benchmark/calibrate.py knee --workload W --rates 2,3,4 \\
        --seconds 20 --seed N
        Serving: the open-loop traffic at each rate in turn, one engine;
        prints how far the queue grew. The knee is the highest rate with
        no growing backlog; the cell's rate is about four fifths of it.

    python3 benchmark/calibrate.py readings --workload W --seeds a,b,c \\
        [--seconds S] [--variant V]
        The numbers that decide ``correct``, on each seed. Serving: the
        program's and the control's (the reference in the precision below
        the configuration's, float8 for bfloat16, put in the program's
        place). Training, one variant a process, so that each has the
        chip's memory to itself: ``program`` (the cell's first steps),
        ``f32`` (the reference), ``fp8`` (the control) or ``half_batch``
        (the reference fed half of each batch twice); each line holds the
        raw numbers, which ``compare`` then sets against the reference.

    python3 benchmark/calibrate.py compare FILE...
        Training: each variant's lines in the files against the ``f32``
        lines of the same seed, as the cell compares them. Needs no chip.

Each prints one JSON line per rate or seed to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

import run as harness


def _serving(cell, seed):
    from loops import serve

    engine, clock = serve.build(cell.cfg, cell.traffic, seed)
    return serve, engine, clock


def knee(cell, rates, seconds, seed) -> None:
    from stats import percentile

    serve, engine, clock = _serving(cell, seed)
    for rate in rates:
        traffic = dict(cell.traffic, rate_per_s=rate)
        reqs = serve.generate(traffic, seed, seconds, cell.cfg["vocab_size"])
        clock.arm()
        report = engine.run(serve._requests(reqs))
        w = serve.summarize(reqs, report, clock)
        half = len(reqs) // 2
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "completed": w["completed"],
            "drain_s": w["makespan_s"] - w["last_arrival_s"],
            "queue_p95_first_half_s": percentile(w["queue_s"][:half], 95),
            "queue_p95_second_half_s": percentile(w["queue_s"][half:], 95),
            "ttft_p50_s": percentile(w["ttft_s"], 50),
            "ttft_p95_s": percentile(w["ttft_s"], 95),
            "itl_p95_ms": 1e3 * percentile(w["itl_s"], 95),
            "decode_step_ms": 1e3 * sum(w["decode_s"]) / len(w["decode_s"]),
            "tokens_per_s": w["tokens_completed"] / w["makespan_s"]}),
            flush=True)


def serve_readings(cell, seeds, seconds) -> None:
    import weights as W

    serve, engine, clock = _serving(cell, seeds[0])
    cfg, traffic = cell.cfg, cell.traffic
    for seed in seeds:
        engine.params = None
        gc.collect()
        engine.params = W.make_params(cfg, seed, cfg["dtype"])
        reqs = serve.generate(traffic, seed, seconds, cfg["vocab_size"])
        clock.arm()
        w = serve.summarize(reqs, engine.run(serve._requests(reqs)), clock)
        served = w["served"]
        sample = serve.choose_sample(reqs, served, seed,
                                     traffic["check_tokens"])
        t0 = time.perf_counter()
        prog = serve.max_gap(cfg, seed, reqs, served, sample, "f32")
        t1 = time.perf_counter()
        ctrl = serve.max_gap(cfg, seed, reqs, served, sample, "fp8")
        print(json.dumps({
            "seed": seed, "requests": len(reqs), "completed": w["completed"],
            "compared_tokens": sum(len(served[i]) for i in sample),
            "program": {serve.LOGIT_GAP: prog},
            "control": {serve.LOGIT_GAP: ctrl},
            "reference_s": t1 - t0}), flush=True)


def train_readings(cell, seeds, variant: str) -> None:
    """One variant's raw numbers on each seed: losses, first gradient
    norms and changes per leaf, and for the reference its raw gradient
    norms, which decide the leaves left out of the change."""
    import jax

    from loops import train

    cfg, job = cell.cfg, cell.traffic
    steps = job["first_steps"]

    def half_batch(job, seed, step, vocab):
        b = train.batch_at(job, seed, step, vocab)
        return {k: np.concatenate([v[:len(v) // 2]] * 2) for k, v in b.items()}

    if variant == "program":
        bundle = train.build(cfg, job, jax.devices()[:1])
    for seed in seeds:
        t0 = time.perf_counter()
        if variant == "program":
            with jax.set_mesh(bundle[0]):
                row = train.start(bundle, cfg, job, seed)[0]
        elif variant == "half_batch":
            row = train.reference_steps(cfg, job, seed, steps, "f32",
                                        rows=half_batch)
        else:
            row = train.reference_steps(cfg, job, seed, steps, variant)
        gc.collect()
        print(json.dumps(dict(row, seed=seed, variant=variant,
                              seconds=time.perf_counter() - t0)), flush=True)


def compare(paths) -> None:
    """Each variant's numbers against the reference's on the same seed."""
    from loops import train

    rows = [json.loads(line) for p in paths for line in open(p)
            if line.startswith("{") and '"variant"' in line]
    ref = {r["seed"]: r for r in rows if r["variant"] == "f32"}
    for r in rows:
        if r["variant"] != "f32" and r["seed"] in ref:
            print(json.dumps({"seed": r["seed"], "variant": r["variant"],
                              **train.compare(r, ref[r["seed"]])}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("knee", "readings", "compare"))
    ap.add_argument("files", nargs="*")
    ap.add_argument("--workload")
    ap.add_argument("--variant", default="f32",
                    choices=("program", "f32", "fp8", "half_batch"))
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    if args.mode == "compare":
        compare(args.files)
        return 0
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.resolve(args.workload)
    harness.use_compile_cache()
    if args.mode == "knee":
        knee(cell, [float(r) for r in args.rates.split(",")], args.seconds,
             args.seed)
    elif cell.traffic["loop"] == "serve":
        serve_readings(cell, [int(s) for s in args.seeds.split(",")],
                       args.seconds)
    else:
        train_readings(cell, [int(s) for s in args.seeds.split(",")],
                       args.variant)
    return 0


if __name__ == "__main__":
    sys.exit(main())
