"""Tuned tile-config lookup for the Pallas kernels.

Every kernel entry point (``flash_attention_fwd``/``_bwd``, ``wkv6_fwd``,
``rmsnorm_fwd``) and its jitted ``ops`` wrapper accepts ``None`` for its
tile parameters ("auto"). Resolution order:

1. an explicit value passed by the caller always wins;
2. otherwise the tuned-config cache is consulted under the kernel's
   (shape-signature, dtype, backend) key — winners persisted by the
   autotuner (:mod:`repro.bench.tune`) to ``results/tuned/<backend>.json``;
3. otherwise the historical constants in :data:`DEFAULTS` apply, so a
   cache-less checkout behaves exactly like the pre-tuning code.

Cache entries carry the environment fingerprint of the machine that
produced them; a load on a different backend / jax version / machine
ignores the file (stale tile choices are worse than defaults). Set
``REPRO_TUNED_DIR`` to relocate the cache (tests, CI sandboxes).

The parsed cache is held in memory per process; call :func:`clear_cache`
after writing new winners (the tuner's ``save`` does this) so same-process
lookups see them. Jitted wrappers resolve *before* tracing, so a new
winner means new static block args and a clean retrace.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, Mapping, Optional, Tuple

import numpy as np

REPO = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO / "results" / "tuned"
ENV_VAR = "REPRO_TUNED_DIR"
CACHE_VERSION = 1

# The pre-tuning constants every kernel falls back to on a cache miss.
DEFAULTS: Dict[str, Dict[str, int]] = {
    "flash_attention_fwd": {"block_q": 128, "block_k": 128},
    "flash_attention_bwd": {"block_q": 128, "block_k": 128},
    "wkv6_fwd": {"chunk": 64},
    "rmsnorm_fwd": {"block_rows": 256},
    "paged_attention_fwd": {"pages_per_block": 8},
}

# env-fingerprint keys that must match for a cache file to be trusted
_ENV_MATCH_KEYS = ("backend", "jax", "machine")

_CACHE: Dict[str, Dict[str, Any]] = {}   # backend -> parsed entries


# ----------------------------------------------------------- capabilities
@dataclass(frozen=True)
class BackendCaps:
    """Per-backend hardware capability contract the kernel layer tiles
    against — the multi-backend seam (ROADMAP item 4): new targets slot
    in as entries here, and both the tile resolvers and the static
    kernel contract checker (:mod:`repro.analysis.kernel_lint`) read
    their legality limits from this table instead of hard-coding TPU
    constants.

    ``min_tile`` maps an operand dtype to the minimum legal
    (sublane, lane) tile of that backend's vector memory layout; block
    dimensions smaller than the minimum are padded (wasting VMEM, which
    the footprint model charges), while larger dimensions must be whole
    multiples to be MXU-friendly.
    """

    name: str
    mxu: Tuple[int, int] = (128, 128)            # systolic matmul tile
    vpu: Tuple[int, int] = (8, 128)              # vector unit shape
    vmem_bytes: int = 16 * 1024 * 1024           # per-core fast memory
    # minimum legal (sublane, lane) tile per operand dtype
    min_tile: Mapping[str, Tuple[int, int]] = field(
        default_factory=lambda: {
            "float32": (8, 128),
            "bfloat16": (16, 128),
            "float16": (16, 128),
            "int8": (32, 128),
            "uint8": (32, 128),
            "int32": (8, 128),
        })
    # double-buffered operand pipelining: in/out blocks are resident
    # twice while the grid streams (the footprint model's multiplier)
    pipeline_buffers: int = 2

    @property
    def lane(self) -> int:
        return self.mxu[1]

    def supports(self, dtype: Any) -> bool:
        return _dtype_name(dtype) in self.min_tile

    def sublane(self, dtype: Any) -> int:
        """Minimum second-minor block extent for ``dtype`` (f32 fallback
        for dtypes outside the table, so footprint stays computable)."""
        return self.min_tile.get(_dtype_name(dtype),
                                 self.min_tile["float32"])[0]

    def padded_bytes(self, shape: Tuple[int, ...], dtype: Any) -> int:
        """VMEM bytes one block/scratch buffer of ``shape`` occupies once
        tiled: the last dim pads to the lane width, the second-minor to
        the dtype's sublane minimum (1-D buffers pad to one sublane)."""
        if not shape:
            return int(np.dtype(dtype).itemsize)
        dims = list(int(d) for d in shape)
        dims[-1] = -(-dims[-1] // self.lane) * self.lane
        sub = self.sublane(dtype)
        if len(dims) >= 2:
            dims[-2] = -(-dims[-2] // sub) * sub
        n = 1
        for d in dims:
            n *= d
        return n * int(np.dtype(dtype).itemsize)


BACKEND_CAPS: Dict[str, BackendCaps] = {
    # real TPU cores and interpret mode (cpu) share one contract: the
    # Pallas kernels are written against TPU tiling either way, and a
    # tile that is illegal on hardware should fail the lint even when
    # the test host happens to interpret it
    "tpu": BackendCaps(name="tpu"),
    "cpu": BackendCaps(name="cpu"),
    # placeholder Mosaic-GPU entry: tensor-core MMA tile with a shared
    # memory budget standing in for VMEM until GPU kernel variants land
    "gpu": BackendCaps(name="gpu", mxu=(64, 64), vpu=(1, 32),
                       vmem_bytes=228 * 1024,
                       min_tile={"float32": (8, 32), "bfloat16": (8, 32),
                                 "float16": (8, 32), "int8": (16, 32),
                                 "int32": (8, 32)}),
}


def capabilities(backend: Optional[str] = None) -> BackendCaps:
    """Capability entry for ``backend`` (default: the executing jax
    backend). Unknown backends get the TPU contract — the conservative
    choice, since every kernel here is authored against TPU tiling."""
    be = backend or backend_name()
    return BACKEND_CAPS.get(be, BACKEND_CAPS["tpu"])


# ------------------------------------------------------------ environment
def _env_fingerprint() -> Dict[str, Any]:
    from repro.bench.record import env_fingerprint

    return env_fingerprint()


def backend_name() -> str:
    """Key the cache by the executing jax backend (cpu = interpret mode)."""
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


def cache_dir() -> Path:
    return Path(os.environ.get(ENV_VAR, str(DEFAULT_CACHE_DIR)))


def cache_path(backend: Optional[str] = None) -> Path:
    return cache_dir() / f"{backend or backend_name()}.json"


# ------------------------------------------------------------- signatures
def _dtype_name(dtype: Any) -> str:
    return np.dtype(dtype).name


def signature(**dims: Any) -> str:
    """Canonical shape-signature string: sorted ``k=v`` pairs."""
    return ",".join(f"{k}={v}" for k, v in sorted(dims.items()))


def attention_signature(q_shape, k_shape, dtype, *, causal: bool,
                        window: int) -> str:
    B, Sq, Hq, D = q_shape
    _, Sk, Hkv, _ = k_shape
    return signature(B=B, Sq=Sq, Sk=Sk, Hq=Hq, Hkv=Hkv, D=D,
                     dtype=_dtype_name(dtype), causal=int(bool(causal)),
                     window=int(window))


def wkv6_signature(q_shape, v_head: int, dtype, *, use_u: bool) -> str:
    B, T, H, K = q_shape
    return signature(B=B, T=T, H=H, K=K, V=int(v_head),
                     dtype=_dtype_name(dtype), u=int(bool(use_u)))


def rmsnorm_signature(rows: int, d: int, dtype) -> str:
    return signature(rows=int(rows), d=int(d), dtype=_dtype_name(dtype))


def paged_attention_signature(q_shape, pages_shape, n_pages: int,
                              dtype) -> str:
    B, _, Hq, D = q_shape
    P, ps, Hkv, _ = pages_shape
    return signature(B=B, Hq=Hq, Hkv=Hkv, D=D, P=P, ps=ps,
                     npag=int(n_pages), dtype=_dtype_name(dtype))


# ------------------------------------------------------------------ cache
def clear_cache() -> None:
    _CACHE.clear()


def _load(path: Path) -> Dict[str, Any]:
    try:
        data = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return {}
    if data.get("version") != CACHE_VERSION:
        return {}
    stored = data.get("env", {})
    try:
        current = _env_fingerprint()
    except Exception:
        current = {}
    for key in _ENV_MATCH_KEYS:
        if key in stored or key in current:
            if stored.get(key) != current.get(key):
                return {}   # fingerprint mismatch: tuned elsewhere, ignore
    return dict(data.get("entries", {}))


def _entries(backend: Optional[str] = None) -> Dict[str, Any]:
    be = backend or backend_name()
    if be not in _CACHE:
        _CACHE[be] = _load(cache_path(be))
    return _CACHE[be]


def entry_key(kernel: str, sig: str) -> str:
    return f"{kernel}|{sig}"


def lookup(kernel: str, sig: str,
           backend: Optional[str] = None) -> Optional[Dict[str, int]]:
    """Tuned config for (kernel, signature), or None on a cache miss."""
    entry = _entries(backend).get(entry_key(kernel, sig))
    if not entry:
        return None
    return dict(entry.get("config", {})) or None


def resolve(kernel: str, sig: str, **overrides: Optional[int]
            ) -> Dict[str, int]:
    """DEFAULTS <- tuned cache <- explicit (non-None) caller overrides."""
    cfg = dict(DEFAULTS[kernel])
    tuned = lookup(kernel, sig)
    if tuned:
        cfg.update({k: int(v) for k, v in tuned.items() if k in cfg})
    cfg.update({k: int(v) for k, v in overrides.items() if v is not None})
    return cfg


def save_entries(entries: Dict[str, Dict[str, Any]],
                 backend: Optional[str] = None) -> Path:
    """Merge winners into the per-backend cache file (atomic replace)."""
    be = backend or backend_name()
    path = cache_path(be)
    path.parent.mkdir(parents=True, exist_ok=True)
    merged = _load(path)     # keep prior entries only if env still matches
    merged.update(entries)
    try:
        env = _env_fingerprint()
    except Exception:
        env = {}
    tmp = path.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(
        {"version": CACHE_VERSION, "env": env, "entries": merged},
        indent=1, sort_keys=True))
    os.replace(tmp, path)
    clear_cache()
    return path


# -------------------------------------------------- per-kernel resolvers
def resolve_attention_blocks(block_q: Optional[int], block_k: Optional[int],
                             *, q_shape, k_shape, dtype, causal: bool,
                             window: int,
                             kernel: str = "flash_attention_fwd"
                             ) -> Tuple[int, int]:
    if block_q is not None and block_k is not None:
        return int(block_q), int(block_k)
    sig = attention_signature(q_shape, k_shape, dtype, causal=causal,
                              window=window)
    cfg = resolve(kernel, sig, block_q=block_q, block_k=block_k)
    return cfg["block_q"], cfg["block_k"]


def resolve_wkv_chunk(chunk: Optional[int], *, q_shape, v_head: int, dtype,
                      use_u: bool) -> int:
    if chunk is not None:
        return int(chunk)
    sig = wkv6_signature(q_shape, v_head, dtype, use_u=use_u)
    return resolve("wkv6_fwd", sig)["chunk"]


def clamp_rmsnorm_rows(block_rows: int, *, d: int, dtype,
                       backend: Optional[str] = None) -> int:
    """Shrink ``block_rows`` (halving) until the fused footprint — in and
    out blocks double-buffered plus the f32 working copy — fits the
    backend's VMEM budget. The historical 256-row default overflows at
    d=4096/f32 (~21 MB vs 16 MB); the auto path clamps so wide models
    get the largest block that actually fits, the same way the paged
    resolver clamps pages_per_block to the block table."""
    caps = capabilities(backend)
    br = max(int(block_rows), 1)

    def fits(r: int) -> bool:
        blocks = 2 * caps.pipeline_buffers * caps.padded_bytes((r, d), dtype)
        work = caps.padded_bytes((r, d), "float32")
        return blocks + work <= caps.vmem_bytes

    floor = caps.sublane(dtype)
    while br > floor and not fits(br):
        br //= 2
    return max(br, 1)


def resolve_rmsnorm_rows(block_rows: Optional[int], *, rows: int, d: int,
                         dtype) -> int:
    if block_rows is not None:
        return int(block_rows)   # explicit caller value always wins
    sig = rmsnorm_signature(rows, d, dtype)
    return clamp_rmsnorm_rows(resolve("rmsnorm_fwd", sig)["block_rows"],
                              d=d, dtype=dtype)


def resolve_paged_pages_per_block(pages_per_block: Optional[int], *,
                                  q_shape, pages_shape, n_pages: int,
                                  dtype) -> int:
    """Explicit > tuned > default, clamped to [1, n_pages] so any source
    (caller, stale cache entry) yields a tiling the block table can
    satisfy."""
    if pages_per_block is None:
        sig = paged_attention_signature(q_shape, pages_shape, n_pages,
                                        dtype)
        pages_per_block = resolve("paged_attention_fwd",
                                  sig)["pages_per_block"]
    return max(1, min(int(pages_per_block), int(n_pages)))
