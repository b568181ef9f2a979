"""Kernel autotuner: sweep Pallas tiling configs, persist the winners.

DABench-LLM's core observation is that dataflow-accelerator performance
hinges on resource allocation and tile/block mapping, and that the
benchmark harness should *drive* those choices. This module closes the
loop: for each (kernel, shape-signature, dtype, backend) it

1. enumerates candidate tile configs (attention ``block_q``/``block_k``,
   wkv6 ``chunk``, rmsnorm ``block_rows``),
2. rejects candidates that violate MXU alignment or the VMEM-budget
   model computed from the block shapes (:func:`*_vmem_bytes`),
3. times the survivors with the harness timer (``timeit_us``; injectable
   for deterministic tests — the timed closure carries its config in
   ``fn.keywords`` so a fake timer can key on it),
4. picks the fastest config and persists it via
   :mod:`repro.kernels.tuning` to ``results/tuned/<backend>.json``.

When the default config is valid for the shape it is candidate 0, and
ties resolve to the earliest candidate, so a tuned config can never
regress the default on the swept shape — "tuned >= default" holds by
construction. A default the shape can't tile (or the budget rejects) is
skipped, not mislabeled: the result reports a neutral speedup of 1.0
with ``default_timed=False``.

Run it through the harness: ``python -m benchmarks.run --tune`` executes
the ``@scenario``-registered sweeps in :mod:`benchmarks.bench_tune`, so
tuned-vs-default deltas land in ``results/bench/latest.jsonl`` as
first-class :class:`~repro.bench.record.BenchRecord` rows.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.bench.runner import timeit_us
from repro.kernels import tuning

# ------------------------------------------------------------ VMEM model
VMEM_BYTES = 16 * 2 ** 20      # per-core VMEM on current TPUs
VMEM_SLACK = 0.9               # headroom for Mosaic spills/semaphores
MXU_LANE = 128                 # MXU tile edge: seq blocks align to this
SUBLANE = 8                    # min f32 sublane tile

_ATTN_BLOCKS = (128, 256, 512)
_WKV_CHUNKS = (16, 32, 64, 128, 256)
_NORM_ROWS = (64, 128, 256, 512, 1024)
_PAGED_PPB = (4, 8, 16, 32)


def attention_vmem_bytes(bq: int, bk: int, D: int, itemsize: int) -> int:
    """Blocks are double-buffered by the pipeline; scratch is f32."""
    blocks = (2 * bq * D + 2 * bk * D) * itemsize        # q, o, k, v
    scratch = (2 * bq + bq * D) * 4                      # m, l, acc
    lse = bq * 4
    return 2 * blocks + scratch + lse


def wkv6_vmem_bytes(c: int, K: int, V: int, itemsize: int) -> int:
    """Worst case materializes the (c, c, K) pairwise-decay tensor (the
    masked fallback path for large in-chunk decay ranges)."""
    blocks = (3 * c * K + c * V) * itemsize + c * V * itemsize
    state = 2 * K * V * 4                                # scratch + out
    pairwise = c * c * K * 4
    return 2 * blocks + state + pairwise


def rmsnorm_vmem_bytes(br: int, d: int, itemsize: int) -> int:
    blocks = 2 * br * d * itemsize + d * 4               # x, o, scale
    f32_tmp = br * d * 4
    return 2 * blocks + f32_tmp


def paged_vmem_bytes(ppb: int, ps: int, hkv: int, g: int, D: int,
                     itemsize: int) -> int:
    """The K/V pools stay in HBM: two VMEM slots of ``pages_per_block``
    pages (ps, Hkv, D) a side, the q/o row tiles double-buffered, the f32
    online-softmax scratch, and the f32 block a step scores (K and V
    cast, the scores and their exponentials)."""
    hq, cols = hkv * g, ppb * ps * hkv
    slots = 2 * 2 * ppb * ps * hkv * D * itemsize
    rows = 2 * 2 * hq * D * itemsize                     # q, o
    scratch = (2 * hq + hq * D) * 4                      # m, l, acc
    work = (2 * cols * D + 2 * hq * cols) * 4            # k, v, s, pe
    return slots + rows + scratch + work


def _budget(vmem_budget: Optional[int]) -> int:
    return int(VMEM_BYTES * VMEM_SLACK) if vmem_budget is None \
        else int(vmem_budget)


def _seq_blocks(seq: int) -> List[int]:
    """MXU-aligned block sizes that tile ``seq`` exactly: multiples of
    128 dividing seq, or seq itself when it is smaller than one tile."""
    cand = [b for b in _ATTN_BLOCKS if b <= seq and seq % b == 0]
    if not cand and seq:
        cand = [seq]
    return cand


# ------------------------------------------------------------ candidates
# Each *_candidates returns (valid candidates, rejected-by-vmem count,
# default config). The default is candidate 0 when it is itself valid for
# the shape AND fits the budget; otherwise it is None — the sweep then has
# no default baseline (speedup reports 1.0 rather than mislabeling some
# other candidate's time as "default").
def attention_candidates(Sq: int, Sk: int, D: int, itemsize: int,
                         vmem_budget: Optional[int] = None
                         ) -> Tuple[List[Dict[str, int]], int,
                                    Optional[Dict[str, int]]]:
    budget = _budget(vmem_budget)
    d0 = tuning.DEFAULTS["flash_attention_fwd"]
    default = {"block_q": min(d0["block_q"], Sq),
               "block_k": min(d0["block_k"], Sk)}
    out, rejected = [], 0
    for bq in _seq_blocks(Sq):
        for bk in _seq_blocks(Sk):
            cfg = {"block_q": bq, "block_k": bk}
            if attention_vmem_bytes(bq, bk, D, itemsize) > budget:
                rejected += 1
                continue
            if cfg != default:
                out.append(cfg)
    default_ok = (Sq % default["block_q"] == 0
                  and Sk % default["block_k"] == 0
                  and attention_vmem_bytes(default["block_q"],
                                           default["block_k"], D,
                                           itemsize) <= budget)
    if default_ok:
        out.insert(0, default)
    return out, rejected, (default if default_ok else None)


def wkv6_candidates(T: int, K: int, V: int, itemsize: int,
                    vmem_budget: Optional[int] = None
                    ) -> Tuple[List[Dict[str, int]], int,
                               Optional[Dict[str, int]]]:
    budget = _budget(vmem_budget)
    default_c = min(tuning.DEFAULTS["wkv6_fwd"]["chunk"], T)
    out, rejected = [], 0
    for c in _WKV_CHUNKS:
        if c > T or T % c or c % SUBLANE:
            continue
        if wkv6_vmem_bytes(c, K, V, itemsize) > budget:
            rejected += 1
            continue
        if c != default_c:
            out.append({"chunk": c})
    default_ok = (T % default_c == 0
                  and wkv6_vmem_bytes(default_c, K, V, itemsize) <= budget)
    if default_ok:
        out.insert(0, {"chunk": default_c})
    return out, rejected, ({"chunk": default_c} if default_ok else None)


def rmsnorm_candidates(rows: int, d: int, itemsize: int,
                       vmem_budget: Optional[int] = None
                       ) -> Tuple[List[Dict[str, int]], int,
                                  Optional[Dict[str, int]]]:
    budget = _budget(vmem_budget)
    default_r = min(tuning.DEFAULTS["rmsnorm_fwd"]["block_rows"], rows)
    out, rejected = [], 0
    for br in _NORM_ROWS:
        if br > rows or br % SUBLANE:
            continue
        if rmsnorm_vmem_bytes(br, d, itemsize) > budget:
            rejected += 1
            continue
        if br != default_r:
            out.append({"block_rows": br})
    # the kernel pads rows, so the default only needs to fit the budget
    default_ok = rmsnorm_vmem_bytes(default_r, d, itemsize) <= budget
    if default_ok:
        out.insert(0, {"block_rows": default_r})
    return out, rejected, ({"block_rows": default_r} if default_ok
                           else None)


def paged_candidates(n_pages: int, ps: int, hkv: int, g: int, D: int,
                     itemsize: int, vmem_budget: Optional[int] = None
                     ) -> Tuple[List[Dict[str, int]], int,
                                Optional[Dict[str, int]]]:
    budget = _budget(vmem_budget)
    default_p = min(tuning.DEFAULTS["paged_attention_fwd"]
                    ["pages_per_block"], n_pages)
    out, rejected = [], 0
    for ppb in _PAGED_PPB:
        if ppb > n_pages:
            continue
        if paged_vmem_bytes(ppb, ps, hkv, g, D, itemsize) > budget:
            rejected += 1
            continue
        if ppb != default_p:
            out.append({"pages_per_block": ppb})
    default_ok = paged_vmem_bytes(default_p, ps, hkv, g, D,
                                  itemsize) <= budget
    if default_ok:
        out.insert(0, {"pages_per_block": default_p})
    return out, rejected, ({"pages_per_block": default_p} if default_ok
                           else None)


# ----------------------------------------------------------------- sweep
@dataclass
class TuneResult:
    """Winner of one (kernel, shape-signature) sweep."""

    kernel: str
    signature: str
    config: Dict[str, int]
    us: float                      # winner's measured time
    default_us: float              # default config's time
    # False when the default config was invalid for the shape or rejected
    # by the VMEM budget — default_us then equals us (neutral speedup 1.0)
    default_timed: bool = True
    n_candidates: int = 0
    rejected_vmem: int = 0
    timings: Dict[str, float] = field(default_factory=dict)

    @property
    def speedup(self) -> float:
        return self.default_us / self.us if self.us else 1.0

    def entry(self) -> Tuple[str, Dict[str, Any]]:
        return tuning.entry_key(self.kernel, self.signature), {
            "config": self.config, "us": float(self.us),
            "default_us": float(self.default_us)}


def _cfg_label(cfg: Dict[str, int]) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(cfg.items()))


def _sweep(kernel: str, sig: str, candidates: Sequence[Dict[str, int]],
           rejected: int, default_cfg: Optional[Dict[str, int]],
           make_fn: Callable[..., Callable], args: tuple,
           timer: Callable, iters: int, warmup: int) -> TuneResult:
    """Time every candidate; earliest-fastest wins (default is first)."""
    if not candidates:
        raise ValueError(f"no valid tile candidates for {kernel} ({sig})")
    timings: Dict[str, float] = {}
    best_cfg: Optional[Dict[str, int]] = None
    best_us: Any = float("inf")
    for cfg in candidates:
        fn = make_fn(**cfg)
        # keep the timer's raw value: a TimingStats mean carries per-iter
        # percentiles that ride into the winner's BenchRecord
        us = timer(fn, *args, iters=iters, warmup=warmup)
        timings[_cfg_label(cfg)] = float(us)
        if us < best_us:
            best_cfg, best_us = cfg, us
    if default_cfg is not None:
        default_us, default_timed = timings[_cfg_label(default_cfg)], True
    else:
        # no usable default for this shape: report a neutral baseline
        default_us, default_timed = float(best_us), False
    return TuneResult(kernel=kernel, signature=sig, config=dict(best_cfg),
                      us=best_us, default_us=default_us,
                      default_timed=default_timed,
                      n_candidates=len(candidates), rejected_vmem=rejected,
                      timings=timings)


def tune_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                         timer: Callable = timeit_us, iters: int = 2,
                         warmup: int = 1,
                         vmem_budget: Optional[int] = None) -> TuneResult:
    from repro.kernels import ops

    _, Sq, _, D = q.shape
    Sk = k.shape[1]
    sig = tuning.attention_signature(q.shape, k.shape, q.dtype,
                                     causal=causal, window=window)
    cands, rej, dflt = attention_candidates(Sq, Sk, D, q.dtype.itemsize,
                                            vmem_budget)

    def make_fn(block_q: int, block_k: int):
        return functools.partial(ops.flash_attention, causal=causal,
                                 window=window, block_q=block_q,
                                 block_k=block_k)

    return _sweep("flash_attention_fwd", sig, cands, rej, dflt, make_fn,
                  (q, k, v), timer, iters, warmup)


@functools.lru_cache(maxsize=None)
def _bwd_jitted(causal, window, block_q, block_k):
    import jax

    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention_bwd

    return jax.jit(functools.partial(
        flash_attention_bwd, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=ops.interpret_mode()))


def _bwd_call(q, k, v, o, lse, do, *, causal, window, block_q, block_k):
    """Jit-per-config bwd entry; kwargs stay visible to fake timers."""
    return _bwd_jitted(causal, window, block_q, block_k)(q, k, v, o, lse,
                                                         do)


def tune_flash_attention_bwd(q, k, v, *, causal: bool = True,
                             window: int = 0, timer: Callable = timeit_us,
                             iters: int = 2, warmup: int = 1,
                             vmem_budget: Optional[int] = None
                             ) -> TuneResult:
    """Tunes dq/dkv block shapes against a fixed forward residual set."""
    import jax

    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention_fwd

    _, Sq, _, D = q.shape
    Sk = k.shape[1]
    sig = tuning.attention_signature(q.shape, k.shape, q.dtype,
                                     causal=causal, window=window)
    cands, rej, dflt = attention_candidates(Sq, Sk, D, q.dtype.itemsize,
                                            vmem_budget)
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 interpret=ops.interpret_mode(), return_lse=True)
    do = jax.numpy.ones_like(o)

    def make_fn(block_q: int, block_k: int):
        return functools.partial(_bwd_call, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k)

    return _sweep("flash_attention_bwd", sig, cands, rej, dflt, make_fn,
                  (q, k, v, o, lse, do), timer, iters, warmup)


def tune_wkv6(q, k, v, ld, u=None, *, timer: Callable = timeit_us,
              iters: int = 2, warmup: int = 1,
              vmem_budget: Optional[int] = None) -> TuneResult:
    from repro.kernels import ops

    _, T, _, K = q.shape
    V = v.shape[-1]
    sig = tuning.wkv6_signature(q.shape, V, q.dtype, use_u=u is not None)
    cands, rej, dflt = wkv6_candidates(T, K, V, q.dtype.itemsize,
                                       vmem_budget)

    def make_fn(chunk: int):
        return functools.partial(ops.wkv6, chunk=chunk)

    return _sweep("wkv6_fwd", sig, cands, rej, dflt, make_fn,
                  (q, k, v, ld, u), timer, iters, warmup)


def tune_rmsnorm(x, scale, *, timer: Callable = timeit_us, iters: int = 3,
                 warmup: int = 1,
                 vmem_budget: Optional[int] = None) -> TuneResult:
    from repro.kernels import ops

    d = x.shape[-1]
    rows = int(np.prod(x.shape[:-1]))
    sig = tuning.rmsnorm_signature(rows, d, x.dtype)
    cands, rej, dflt = rmsnorm_candidates(rows, d, x.dtype.itemsize,
                                          vmem_budget)

    def make_fn(block_rows: int):
        return functools.partial(ops.rmsnorm, block_rows=block_rows)

    return _sweep("rmsnorm_fwd", sig, cands, rej, dflt, make_fn,
                  (x, scale), timer, iters, warmup)


def tune_paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                         timer: Callable = timeit_us, iters: int = 2,
                         warmup: int = 1,
                         vmem_budget: Optional[int] = None) -> TuneResult:
    """Sweep the paged decode-attention ``pages_per_block``."""
    from repro.kernels import ops

    B, _, Hq, D = q.shape
    _, ps, Hkv, _ = k_pages.shape
    npag = block_tables.shape[1]
    sig = tuning.paged_attention_signature(q.shape, k_pages.shape, npag,
                                           q.dtype)
    cands, rej, dflt = paged_candidates(npag, ps, Hkv, Hq // Hkv, D,
                                        q.dtype.itemsize, vmem_budget)

    def make_fn(pages_per_block: int):
        return functools.partial(ops.paged_decode_attention,
                                 pages_per_block=pages_per_block)

    return _sweep("paged_attention_fwd", sig, cands, rej, dflt, make_fn,
                  (q, k_pages, v_pages, block_tables, lengths), timer,
                  iters, warmup)


def save(results: Sequence[TuneResult],
         backend: Optional[str] = None):
    """Persist winners to the tuned-config cache; returns the path."""
    entries = dict(r.entry() for r in results)
    return tuning.save_entries(entries, backend)
