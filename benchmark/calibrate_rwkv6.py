#!/usr/bin/env python3
"""Readings that set the rwkv6 training cell's limits, made once on the
chip when the cell is defined, as ``calibrate.py`` makes granite's. The
benchmark's own runs never call this.

    python3 benchmark/calibrate_rwkv6.py readings --seeds a,b,c \\
        [--variants program,pre_finch,f32,fp8]
        Each variant's raw numbers on each seed, one JSON line each, all
        in one process, the program's variants first, each with the chip
        to itself: ``program`` (the cell's first steps), ``pre_finch``
        (the same with the time mix as it was before Finch's, see
        :func:`pre_finch_time_mix`), ``f32`` (the reference) and ``fp8``
        (the control).

    python3 benchmark/calibrate_rwkv6.py compare FILE...
        Each variant's lines in the files against the ``f32`` lines of the
        same seed, as the cell compares them. Needs no chip.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp

import run as harness

VARIANTS = ("program", "pre_finch", "f32", "fp8")


def pre_finch_time_mix(p, x, cfg, *, backend: str, state=None,
                       shift_prev=None, factored: bool = False,
                       chunk: int = None):
    """The program's time mix as it was before Finch's, run on Finch's
    weights mapped across: a static token shift (the mixes ``mix`` alone,
    no ``mix_x`` and no adapter), the decay adapter at rank max(32, d //
    32) (Finch's factors cut or padded with zeros to it), the log decay
    clipped to [-12, -1e-4], and the GroupNorm's eps 1e-5."""
    from repro.models import ssm

    B, T, d = x.shape
    hs = cfg.ssm.head_size
    H = d // hs
    prev = shift_prev if shift_prev is not None else jnp.zeros((B, d),
                                                               x.dtype)
    xx = ssm._token_shift(x, prev)
    mix = p["mix"].astype(x.dtype)
    xw, xk, xv, xr, xg = (x + (xx - x) * mix[i] for i in range(5))
    r = (xr @ p["wr"]).reshape(B, T, H, hs)
    k = (xk @ p["wk"]).reshape(B, T, H, hs)
    v = (xv @ p["wv"]).reshape(B, T, H, hs)
    g = jax.nn.silu(xg @ p["wg"])
    rank = max(32, d // 32)
    cut = min(rank, p["wa"].shape[1])
    wa = jnp.pad(p["wa"][:, :cut], ((0, 0), (0, rank - cut)))
    wb = jnp.pad(p["wb"][:cut], ((0, rank - cut), (0, 0)))
    ld = -jnp.exp(p["w0"] + jnp.tanh(xw @ wa) @ wb)
    ld = jnp.clip(ld, -12.0, -1e-4).reshape(B, T, H, hs)
    if chunk is None and backend != "pallas":
        chunk = cfg.ssm.chunk_size
    o, new_state = ssm.linear_attention(r, k, v, ld, u=p["u"],
                                        initial_state=state, backend=backend,
                                        chunk=chunk, factored=factored)
    of = o.astype(jnp.float32)
    mean = of.mean(-1, keepdims=True)
    var = ((of - mean) ** 2).mean(-1, keepdims=True)
    of = (of - mean) * jax.lax.rsqrt(var + 1e-5) * p["ln_scale"] \
        + p["ln_bias"]
    out = (of.reshape(B, T, d).astype(x.dtype) * g) @ p["wo"]
    return out, (new_state, x[:, -1])


@contextlib.contextmanager
def pre_finch():
    """The program's time mix replaced by :func:`pre_finch_time_mix` for
    whatever is traced inside."""
    from repro.models import ssm

    sound = ssm.rwkv6_time_mix
    ssm.rwkv6_time_mix = pre_finch_time_mix
    try:
        yield
    finally:
        ssm.rwkv6_time_mix = sound


def readings(cell, seeds, variants) -> None:
    from loops import train_rwkv6 as T

    cfg, job = cell.cfg, cell.traffic
    for variant in variants:
        if variant in ("program", "pre_finch"):
            faulty = pre_finch() if variant == "pre_finch" \
                else contextlib.nullcontext()
            with faulty:
                bundle = T.build(cfg, job, jax.devices()[:1])
                rows = []
                with jax.set_mesh(bundle[0]):
                    for seed in seeds:
                        t0 = time.perf_counter()
                        numbers = T.start(bundle, cfg, job, seed)[0]
                        rows.append(dict(numbers, seed=seed, variant=variant,
                                         seconds=time.perf_counter() - t0))
                        gc.collect()
                        print(json.dumps(rows[-1]), flush=True)
            del bundle
        else:
            for seed in seeds:
                t0 = time.perf_counter()
                row = T.reference_steps(cfg, job, seed, job["first_steps"],
                                        variant)
                print(json.dumps(dict(row, seed=seed, variant=variant,
                                      seconds=time.perf_counter() - t0)),
                      flush=True)
        gc.collect()


def compare(paths) -> None:
    """Each variant's numbers against the reference's on the same seed."""
    from loops import train_rwkv6 as T

    rows = [json.loads(line) for p in paths for line in open(p)
            if line.startswith("{") and '"variant"' in line]
    ref = {r["seed"]: r for r in rows if r["variant"] == "f32"}
    for r in rows:
        if r["variant"] != "f32" and r["seed"] in ref:
            print(json.dumps({"seed": r["seed"], "variant": r["variant"],
                              **T.compare(r, ref[r["seed"]])}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "compare"))
    ap.add_argument("files", nargs="*")
    ap.add_argument("--workload", default="rwkv6-3b.train.4k")
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--seeds", default="")
    args = ap.parse_args(argv)
    if args.mode == "compare":
        compare(args.files)
        return 0
    if jax.devices()[0].platform != "tpu":
        print("calibrate_rwkv6.py: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    readings(harness.resolve(args.workload),
             [int(s) for s in args.seeds.split(",")],
             args.variants.split(","))
    return 0


if __name__ == "__main__":
    sys.exit(main())
