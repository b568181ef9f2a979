#!/usr/bin/env python3
"""Smoke run of the training and paged-serving paths on a TPU.

    python chip_smoke.py                # one chip: serve and train phases
    python chip_smoke.py --four-chips   # four chips: the train step on a
                                        # (data=2, model=2) mesh vs one chip

Everything runs in this one process, through the entry points a user
calls (``repro.launch.serve.main`` and ``repro.launch.train.main``), on
granite-3-8b at its published widths with the depth cut to what one
chip's 16 GB hold. Weights are random, made from a seed.

* serve: the paged scheduler with chunked prefill and the Pallas paged
  decode kernel; every request must finish with its full token count.
  One decode step's kernel attention is also checked against the
  gather reference at granite's head layout.
* train: a few steps with the Pallas flash-attention kernels forward and
  backward; every loss must be finite, with no retries and no skipped
  steps, and the first loss must match the same step with the chunked
  jnp attention. The flash kernel's output and gradients are also
  checked against the dense reference.

The script refuses to run anywhere but on a TPU: it exits non-zero, and
prints no result, when JAX's first device is not a TPU. Any failed
check exits non-zero. The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``. Step and request times printed
on the way are smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-3-8b"

# Sizes at granite's published widths, checked with memory_analysis()
# against a described v5e chip: 8 float32 layers serve in 7.5 GB of
# arguments; one float32 layer with AdamW state trains at 4 x 2048 tokens
# in 6.4 GB of arguments plus 2.5 GB (Pallas) or 8.3 GB (chunked) of
# temporaries.
SERVE = dict(layers=8, requests=8, prompt_len=512, new_tokens=64,
             page_size=16, prefill_chunk=256)
TRAIN = dict(layers=1, batch=4, seq=2048, steps=5)
PAGED = dict(batch=8, q_heads=32, kv_heads=8, head_dim=128, page_size=16,
             pages_per_seq=36)
FLASH = dict(batch=1, seq=2048, q_heads=32, kv_heads=8, head_dim=128)

# Tolerances, set before the first chip run. Kernel outputs are compared
# with float32 references computed at the highest matmul precision:
# |kernel - ref| <= tol * (1 + |ref|), elementwise.
PAGED_TOL = {"float32": 1e-2, "bfloat16": 3e-2}
FLASH_TOL = 1e-2
# Losses near ln(49155) = 10.8: two runs of the same step agree within
# this absolute difference.
LOSS_ATOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _close(got, want, tol: float) -> float:
    """Largest |got - want| / (1 + |want|); raises past ``tol``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    _require(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    _require(bool(np.isfinite(got).all()), "non-finite kernel output")
    err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
    _require(err <= tol, f"error {err:.3g} exceeds tolerance {tol}")
    return err


# ------------------------------------------------------------------ phases
def paged_kernel_check(*, batch, q_heads, kv_heads, head_dim, page_size,
                       pages_per_seq, dtype="float32", seed=0) -> dict:
    """One decode step's attention through the Pallas paged kernel (as
    the model calls it) against the gather-then-attend reference."""
    from repro.kernels import ops
    from repro.models.attention import paged_decode_attention_ref

    rng = np.random.default_rng(seed)
    n_pages = batch * pages_per_seq + 1
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.standard_normal((batch, 1, q_heads, head_dim)), dt)
    pool = (n_pages, page_size, kv_heads, head_dim)
    k_pages = jnp.asarray(rng.standard_normal(pool), dt)
    v_pages = jnp.asarray(rng.standard_normal(pool), dt)
    # every row owns distinct pages, scattered over the pool
    tables = rng.permutation(np.arange(1, n_pages)).reshape(
        batch, pages_per_seq)
    lengths = rng.integers(1, pages_per_seq * page_size + 1, size=batch)
    args = (q, k_pages, v_pages, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))
    out = ops.paged_decode_attention(*args)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_attention_ref(*(a.astype(jnp.float32)
                                           if a.dtype == dt else a
                                           for a in args))
    return {f"paged_kernel_{dtype}_max_rel_err":
            _close(out, ref, PAGED_TOL[dtype])}


def flash_check(*, batch, seq, q_heads, kv_heads, head_dim,
                seed=0) -> dict:
    """The flash kernels forward and backward against dense attention."""
    from repro.kernels import ops
    from repro.models.attention import dense_attention

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((batch, seq, q_heads, head_dim)),
                    jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((batch, seq, kv_heads,
                                             head_dim)), jnp.float32)
            for _ in range(2))
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v) * ct)

    def kernel(q, k, v):
        return ops.flash_attention(q, k, v, causal=True)

    def ref(q, k, v):
        return dense_attention(q, k, v, causal=True)

    out = kernel(q, k, v)
    grads = jax.grad(lambda *a: loss(kernel, *a), argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = ref(q, k, v)
        want_grads = jax.grad(lambda *a: loss(ref, *a),
                              argnums=(0, 1, 2))(q, k, v)
    errs = {"flash_fwd_max_rel_err": _close(out, want, FLASH_TOL)}
    for name, g, w in zip(("dq", "dk", "dv"), grads, want_grads):
        # gradients are sums over the sequence: compare relative to scale
        scale = float(np.max(np.abs(np.asarray(w))))
        errs[f"flash_{name}_max_rel_err"] = _close(
            np.asarray(g) / scale, np.asarray(w) / scale, FLASH_TOL)
    return errs


def serve_phase(*, layers, requests, prompt_len, new_tokens, page_size,
                prefill_chunk, full=True) -> dict:
    """The paged serving CLI with the Pallas paged decode kernel."""
    from repro.launch import serve

    argv = ["--arch", ARCH, "--scheduler", "paged",
            "--attention-backend", "pallas", "--layers", str(layers),
            "--batch", str(requests), "--num-requests", str(requests),
            "--prompt-len", str(prompt_len),
            "--max-new-tokens", str(new_tokens),
            "--page-size", str(page_size),
            "--prefill-chunk", str(prefill_chunk)]
    t0 = time.perf_counter()
    report = serve.main(argv + (["--full"] if full else []))
    total = time.perf_counter() - t0
    _require(report.completed == requests,
             f"{report.completed}/{requests} requests completed")
    for m in report.metrics:
        _require(m.outcome == "completed" and m.new_tokens == new_tokens
                 and len(m.tokens) == new_tokens,
                 f"request {m.rid}: outcome {m.outcome}, "
                 f"{m.new_tokens}/{new_tokens} tokens")
    s = report.summary()
    return {"requests_completed": report.completed,
            "tokens_generated": s["total_new_tokens"],
            "setup_s_incl_compile": total - s["makespan_s"],
            "makespan_s": s["makespan_s"],
            "ttft_p50_s": s["ttft_p50_s"],
            "decode_step_p50_s": s["tok_p50_s"]}


def _train(*, layers, batch, seq, steps, backend, full=True,
           devices=None):
    from repro.launch import train

    argv = ["--arch", ARCH, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--layers", str(layers),
            "--attention-backend", backend]
    res = train.main(argv + (["--full"] if full else []), devices=devices)
    _require(res.retries == 0, f"{res.retries} retried steps")
    _require(len(res.losses) == steps,
             f"{len(res.losses)}/{steps} steps kept a finite loss")
    _require(bool(np.isfinite(res.losses).all()), "non-finite loss")
    return res


def _steady(step_times) -> float | None:
    rest = step_times[1:]
    return statistics.median(rest) if rest else None


def train_phase(*, layers, batch, seq, steps, full=True) -> dict:
    """Pallas-attention training steps, and the first step again with the
    chunked jnp attention as reference."""
    res = _train(layers=layers, batch=batch, seq=seq, steps=steps,
                 backend="pallas", full=full)
    out = {"losses": res.losses, "first_step_s_incl_compile":
           res.step_times[0], "steady_step_s": _steady(res.step_times)}
    del res
    ref = _train(layers=layers, batch=batch, seq=seq, steps=1,
                 backend="chunked", full=full)
    diff = abs(out["losses"][0] - ref.losses[0])
    _require(diff <= LOSS_ATOL,
             f"first loss pallas {out['losses'][0]} vs chunked "
             f"{ref.losses[0]}: |diff| {diff:.3g} > {LOSS_ATOL}")
    out.update(chunked_first_loss=ref.losses[0], first_loss_abs_diff=diff)
    return out


def four_chip_phase(*, layers, batch, seq, steps, full=True) -> dict:
    """The train step on a (data=2, model=2) mesh over four devices,
    against the same steps on the first of them alone."""
    devices = jax.devices()
    _require(len(devices) == 4, f"{len(devices)} devices, need 4")
    mesh_run = _train(layers=layers, batch=batch, seq=seq, steps=steps,
                      backend="pallas", full=full, devices=devices)
    w = mesh_run.params["layers"]["mlp"]["w_in"]
    on = {s.device for s in w.addressable_shards}
    shard = w.addressable_shards[0].data.shape
    _require(on == set(devices),
             f"w_in lives on {sorted(d.id for d in on)}, not on all four")
    _require(shard != w.shape, f"w_in is not split: shard {shard}")
    out = {"mesh_losses": mesh_run.losses,
           "mesh_steady_step_s": _steady(mesh_run.step_times),
           "w_in_shape": list(w.shape), "w_in_shard_shape": list(shard),
           "w_in_devices": sorted(d.id for d in on)}
    del mesh_run, w
    one = _train(layers=layers, batch=batch, seq=seq, steps=steps,
                 backend="pallas", full=full, devices=devices[:1])
    diffs = [abs(a - b) for a, b in zip(out["mesh_losses"], one.losses)]
    _require(max(diffs) <= LOSS_ATOL,
             f"mesh losses {out['mesh_losses']} vs one chip {one.losses}")
    out.update(one_chip_losses=one.losses, loss_max_abs_diff=max(diffs),
               one_chip_steady_step_s=_steady(one.step_times))
    return out


# -------------------------------------------------------------------- main
def _report(phase: str, result: dict) -> None:
    print(f"[{phase}] " + json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the train step on a (data=2, model=2) "
                         "mesh over four chips, against one chip")
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is on "
              f"platform {device.platform!r}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = Path(enable_compile_cache())
    cached = sum(1 for _ in cache_dir.glob("*")) if cache_dir.is_dir() else 0
    print(f"device_kind={device.device_kind} count={len(jax.devices())} "
          f"compile_cache={cache_dir} entries_before={cached}", flush=True)
    with tempfile.TemporaryDirectory() as no_tuned:
        # tiles come from the kernels' defaults, never from a tuned-cache
        # file the checkout happens to hold
        os.environ["REPRO_TUNED_DIR"] = no_tuned
        if args.four_chips:
            _report("four_chips", four_chip_phase(**TRAIN))
        else:
            t0 = time.perf_counter()
            res = paged_kernel_check(**PAGED)
            res.update(paged_kernel_check(**PAGED, dtype="bfloat16"))
            _report("paged_kernel", res)
            res = serve_phase(**SERVE)
            res["peak_bytes_in_use"] = _peak_bytes()
            _report("serve", res)
            gc.collect()    # the serving weights leave before training's
            res = flash_check(**FLASH)
            _report("flash_kernel", res)
            res = train_phase(**TRAIN)
            res["peak_bytes_in_use"] = _peak_bytes()
            _report("train", res)
            print(f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
