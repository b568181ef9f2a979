"""Static contract checker for the Pallas kernel tile configs.

Each kernel entry point (``flash_attention``, ``rwkv6``, ``rmsnorm``,
``paged_attention``) tiles its operands with BlockSpecs whose legality
depends on the target backend — MXU alignment, VMEM capacity, dtype
support. A bad tile config fails late (Mosaic lowering error on
hardware) or worse, silently (interpret mode happily runs tiles a real
core cannot hold), which invalidates every downstream benchmark number.
This module re-derives each kernel's tiling *plan* — grid, block shapes,
index maps, scratch — from a (dims, config) pair without tracing any
jax, and checks it against the backend capability table
(:func:`repro.kernels.tuning.capabilities`):

* **RK001** every operand dim must be an exact multiple of its block dim
  (after the wrapper's own clamping/padding, which is modeled here);
* **RK002** pipelined blocks (× ``pipeline_buffers``) + scratch (+ the
  kernel's known implicit intermediates) must fit ``vmem_bytes``;
* **RK003** block dims larger than the dtype's minimum (sublane, lane)
  tile must be whole multiples of it;
* **RK004** every index_map must stay in bounds over the full grid
  (sampled exhaustively on small grids, corners + midpoints on large);
* **RK005** operand dtypes must appear in the backend's tile table.

Checked configs: :data:`repro.kernels.tuning.DEFAULTS` against canonical
model shapes, plus every entry in the tuned cache for the backend
(signatures are parsed back into concrete dims). ``check_config`` is the
single-config entry point the tests use to plant illegal tiles.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.kernels import tuning

from .findings import Finding

Dims = Dict[str, Any]


@dataclass
class Block:
    """One BlockSpec use: operand array, block shape, and index map."""

    name: str
    array_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]
    dtype: str = "float32"
    pipelined: bool = True  # charged x pipeline_buffers in VMEM


@dataclass
class Plan:
    """A statically re-derived pallas_call: what the checker validates."""

    kernel: str
    path: str  # display path for findings
    grid: Tuple[int, ...]
    blocks: List[Block] = field(default_factory=list)
    scratch: List[Tuple[str, Tuple[int, ...], str]] = field(default_factory=list)
    # known in-kernel intermediates that live in VMEM but are not
    # declared scratch (e.g. rwkv6's pairwise-decay fallback tensor)
    implicit: List[Tuple[str, Tuple[int, ...], str]] = field(default_factory=list)
    notes: str = ""


# ------------------------------------------------------------ plan builders
def _grid_error(kernel: str, path: str, message: str) -> Plan:
    plan = Plan(kernel=kernel, path=path, grid=())
    plan.notes = message
    return plan


def plan_flash_attention(dims: Dims, config: Dict[str, int]) -> List[Plan]:
    """Forward + both backward pallas_calls for one tile config."""
    B, Sq, Sk = int(dims["B"]), int(dims["Sq"]), int(dims["Sk"])
    Hq, Hkv, D = int(dims["Hq"]), int(dims["Hkv"]), int(dims["D"])
    dt = str(dims.get("dtype", "float32"))
    path = "src/repro/kernels/flash_attention.py"
    if Hkv <= 0 or Hq % Hkv:
        return [
            _grid_error(
                "flash_attention", path, f"Hq={Hq} not divisible by Hkv={Hkv}"
            )
        ]
    g = Hq // Hkv
    bq = min(int(config["block_q"]), Sq)
    bk = min(int(config["block_k"]), Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)

    def qmap(b, h, i, j):
        return (b, h, i, 0)

    def kvmap(b, h, i, j):
        return (b, h // g, j, 0)

    def rowmap(b, h, i, j):
        return (b, h, i, 0)

    def kvmap_t(b, h, j, i):
        return (b, h // g, j, 0)

    def qmap_t(b, h, j, i):
        return (b, h, i, 0)

    def rowmap_t(b, h, j, i):
        return (b, h, i, 0)

    def outk_t(b, h, j, i):
        return (b, h, j, 0)

    q_arr, kv_arr = (B, Hq, Sq, D), (B, Hkv, Sk, D)
    row_arr = (B, Hq, Sq, 1)
    fwd = Plan(
        kernel="flash_attention_fwd",
        path=path,
        grid=(B, Hq, nq, nk),
        blocks=[
            Block("q", q_arr, (1, 1, bq, D), qmap, dt),
            Block("k", kv_arr, (1, 1, bk, D), kvmap, dt),
            Block("v", kv_arr, (1, 1, bk, D), kvmap, dt),
            Block("o", q_arr, (1, 1, bq, D), qmap, dt),
            Block("lse", row_arr, (1, 1, bq, 1), rowmap, "float32"),
        ],
        scratch=[
            ("m", (bq, 1), "float32"),
            ("l", (bq, 1), "float32"),
            ("acc", (bq, D), "float32"),
        ],
        implicit=[
            ("s", (bq, bk), "float32"),
            ("p", (bq, bk), "float32"),
            ("mask", (bq, bk), "float32"),
        ],
    )
    dq = Plan(
        kernel="flash_attention_bwd_dq",
        path=path,
        grid=(B, Hq, nq, nk),
        blocks=[
            Block("q", q_arr, (1, 1, bq, D), qmap, dt),
            Block("k", kv_arr, (1, 1, bk, D), kvmap, dt),
            Block("v", kv_arr, (1, 1, bk, D), kvmap, dt),
            Block("do", q_arr, (1, 1, bq, D), qmap, dt),
            Block("lse", row_arr, (1, 1, bq, 1), rowmap, "float32"),
            Block("delta", row_arr, (1, 1, bq, 1), rowmap, "float32"),
            Block("dq", q_arr, (1, 1, bq, D), qmap, dt),
        ],
        scratch=[("acc", (bq, D), "float32")],
        implicit=[
            ("s", (bq, bk), "float32"),
            ("p", (bq, bk), "float32"),
            ("ds", (bq, bk), "float32"),
        ],
    )
    dkv_arr = (B, Hq, Sk, D)  # per-q-head partials, summed outside
    dkv = Plan(
        kernel="flash_attention_bwd_dkv",
        path=path,
        grid=(B, Hq, nk, nq),
        blocks=[
            Block("q", q_arr, (1, 1, bq, D), qmap_t, dt),
            Block("k", kv_arr, (1, 1, bk, D), kvmap_t, dt),
            Block("v", kv_arr, (1, 1, bk, D), kvmap_t, dt),
            Block("do", q_arr, (1, 1, bq, D), qmap_t, dt),
            Block("lse", row_arr, (1, 1, bq, 1), rowmap_t, "float32"),
            Block("delta", row_arr, (1, 1, bq, 1), rowmap_t, "float32"),
            Block("dk", dkv_arr, (1, 1, bk, D), outk_t, "float32"),
            Block("dv", dkv_arr, (1, 1, bk, D), outk_t, "float32"),
        ],
        scratch=[
            ("dk_acc", (bk, D), "float32"),
            ("dv_acc", (bk, D), "float32"),
        ],
        implicit=[
            ("s", (bq, bk), "float32"),
            ("p", (bq, bk), "float32"),
            ("ds", (bq, bk), "float32"),
        ],
    )
    return [fwd, dq, dkv]


def plan_rwkv6(dims: Dims, config: Dict[str, int]) -> List[Plan]:
    B, T, H = int(dims["B"]), int(dims["T"]), int(dims["H"])
    K, V = int(dims["K"]), int(dims["V"])
    dt = str(dims.get("dtype", "float32"))
    path = "src/repro/kernels/rwkv6.py"
    c = min(int(config["chunk"]), T)
    n = -(-T // c)

    def seqmap(b, h, i):
        return (b, h, i, 0)

    def umap(b, h, i):
        return (h, 0, 0)

    def statemap(b, h, i):
        return (b, h, 0, 0)

    return [
        Plan(
            kernel="wkv6_fwd",
            path=path,
            grid=(B, H, n),
            blocks=[
                Block("q", (B, H, T, K), (1, 1, c, K), seqmap, dt),
                Block("k", (B, H, T, K), (1, 1, c, K), seqmap, dt),
                Block("v", (B, H, T, V), (1, 1, c, V), seqmap, dt),
                Block("ld", (B, H, T, K), (1, 1, c, K), seqmap, dt),
                Block("u", (H, 1, K), (1, 1, K), umap, "float32"),
                Block("o", (B, H, T, V), (1, 1, c, V), seqmap, dt),
                Block("state", (B, H, K, V), (1, 1, K, V), statemap, "float32"),
            ],
            scratch=[("S", (K, V), "float32")],
            # the masked pairwise-decay fallback path materializes (c, c, K)
            # twice (diff and its exp) plus the (c, c) attention matrix
            implicit=[
                ("a", (c, c), "float32"),
                ("diff", (c, c, K), "float32"),
                ("exp_diff", (c, c, K), "float32"),
            ],
        )
    ]


def plan_rmsnorm(dims: Dims, config: Dict[str, int]) -> List[Plan]:
    rows, d = int(dims["rows"]), int(dims["d"])
    dt = str(dims.get("dtype", "float32"))
    path = "src/repro/kernels/rmsnorm.py"
    br = min(int(config["block_rows"]), rows)
    rows_p = -(-rows // br) * br  # the wrapper zero-pads rows
    n = rows_p // br

    def rowmap(i):
        return (i, 0)

    def scalemap(i):
        return (0,)

    return [
        Plan(
            kernel="rmsnorm_fwd",
            path=path,
            grid=(n,),
            blocks=[
                Block("x", (rows_p, d), (br, d), rowmap, dt),
                Block("scale", (d,), (d,), scalemap, dt),
                Block("o", (rows_p, d), (br, d), rowmap, dt),
            ],
            implicit=[
                ("ms", (br, 1), "float32"),
                ("xf32", (br, d), "float32"),
            ],
        )
    ]


def plan_paged_attention(dims: Dims, config: Dict[str, int]) -> List[Plan]:
    B, Hq, Hkv = int(dims["B"]), int(dims["Hq"]), int(dims["Hkv"])
    D, P, ps = int(dims["D"]), int(dims["P"]), int(dims["ps"])
    npag = int(dims["npag"])
    L = int(dims.get("L", 1))  # layers in the stacked pool the kernel reads
    dt = str(dims.get("dtype", "float32"))
    path = "src/repro/kernels/paged_attention.py"
    if Hkv <= 0 or Hq % Hkv:
        return [
            _grid_error(
                "paged_attention", path, f"Hq={Hq} not divisible by Hkv={Hkv}"
            )
        ]
    # the resolver clamps to [1, npag]; model the same so the checker
    # judges the tiling that would actually run
    ppb = max(1, min(int(config["pages_per_block"]), npag))
    nb = -(-npag // ppb)
    # worst-case synthetic scalar-prefetch operands: every live table
    # entry points at the highest physical page, padding at the null
    # page, and the layer index is the last layer — the same bounds the
    # index_map sees at runtime
    btab = np.zeros((B, nb * ppb), dtype=np.int64)
    btab[:, :npag] = P - 1
    layer = L - 1

    def qmap(b, j):
        return (b, 0, 0)

    def kvmap(p):
        def index_map(b, j, p=p):
            return (layer, int(btab[b, j * ppb + p]), 0, 0, 0)

        return index_map

    # the stacked pool; the layer dim is squeezed from the block
    pages_arr = (L, P, ps, Hkv, D)
    blocks = [Block("q", (B, Hq, D), (1, Hq, D), qmap, dt)]
    for side in ("k", "v"):
        for p in range(ppb):
            blocks.append(
                Block(
                    f"{side}_pages[{p}]", pages_arr, (1, 1, ps, Hkv, D), kvmap(p), dt
                )
            )
    blocks.append(Block("o", (B, Hq, D), (1, Hq, D), qmap, dt))
    # every query head scores against the whole flattened page
    cols = ps * Hkv
    return [
        Plan(
            kernel="paged_attention_fwd",
            path=path,
            grid=(B, nb),
            blocks=blocks,
            scratch=[
                ("m", (Hq, 1), "float32"),
                ("l", (Hq, 1), "float32"),
                ("acc", (Hq, D), "float32"),
            ],
            implicit=[
                ("k", (cols, D), "float32"),
                ("v", (cols, D), "float32"),
                ("s", (Hq, cols), "float32"),
                ("pe", (Hq, cols), "float32"),
            ],
        )
    ]


PLANNERS: Dict[str, Callable[[Dims, Dict[str, int]], List[Plan]]] = {
    "flash_attention_fwd": plan_flash_attention,
    "flash_attention_bwd": plan_flash_attention,
    "wkv6_fwd": plan_rwkv6,
    "rmsnorm_fwd": plan_rmsnorm,
    "paged_attention_fwd": plan_paged_attention,
}

# representative full-model shapes the DEFAULTS must be legal for
CANONICAL_DIMS: Dict[str, List[Dims]] = {
    "flash_attention_fwd": [
        dict(
            B=1,
            Sq=2048,
            Sk=2048,
            Hq=32,
            Hkv=8,
            D=128,
            dtype="float32",
            causal=1,
            window=0,
        ),
        dict(
            B=1,
            Sq=2048,
            Sk=2048,
            Hq=32,
            Hkv=8,
            D=128,
            dtype="bfloat16",
            causal=1,
            window=0,
        ),
    ],
    "wkv6_fwd": [dict(B=1, T=2048, H=32, K=64, V=64, dtype="float32", u=1)],
    "rmsnorm_fwd": [
        dict(rows=8192, d=4096, dtype="float32"),
        dict(rows=8192, d=4096, dtype="bfloat16"),
    ],
    "paged_attention_fwd": [
        dict(B=8, Hq=32, Hkv=8, D=128, P=512, ps=16, npag=128, dtype="float32"),
        dict(
            B=64, Hq=32, Hkv=8, D=128, L=10, P=7800, ps=16, npag=256, dtype="bfloat16"
        ),
    ],
}


# --------------------------------------------------------------- the checks
def _ctx(plan: Plan, sig: str) -> str:
    return f"[{plan.kernel} {sig}]" if sig else f"[{plan.kernel}]"


def _check_plan(
    plan: Plan, caps: "tuning.BackendCaps", sig: str = ""
) -> List[Finding]:
    out: List[Finding] = []
    ctx = _ctx(plan, sig)
    if plan.notes and not plan.grid:
        out.append(
            Finding("RK001", plan.path, 0, f"{ctx} unplannable config: {plan.notes}")
        )
        return out

    # RK005: dtype support
    for blk in plan.blocks:
        if not caps.supports(blk.dtype):
            out.append(
                Finding(
                    "RK005",
                    plan.path,
                    0,
                    f"{ctx} operand {blk.name} dtype {blk.dtype} not in "
                    f"backend '{caps.name}' tile table",
                )
            )

    # RK001: block shapes must tile the operand exactly
    for blk in plan.blocks:
        if len(blk.block_shape) != len(blk.array_shape):
            out.append(
                Finding(
                    "RK001",
                    plan.path,
                    0,
                    f"{ctx} {blk.name} block rank {len(blk.block_shape)} != "
                    f"operand rank {len(blk.array_shape)}",
                )
            )
            continue
        for ax, (adim, bdim) in enumerate(zip(blk.array_shape, blk.block_shape)):
            if bdim <= 0 or adim % bdim:
                out.append(
                    Finding(
                        "RK001",
                        plan.path,
                        0,
                        f"{ctx} {blk.name} axis {ax}: block {bdim} does not "
                        f"tile operand dim {adim}",
                    )
                )

    # RK003: MXU/min-tile alignment on the last two block dims
    for blk in plan.blocks:
        shape = blk.block_shape
        if not shape:
            continue
        lane = caps.lane
        last = shape[-1]
        if last > lane and last % lane:
            out.append(
                Finding(
                    "RK003",
                    plan.path,
                    0,
                    f"{ctx} {blk.name} lane dim {last} exceeds {lane} without "
                    f"being a multiple (backend '{caps.name}')",
                )
            )
        if len(shape) >= 2:
            sub = caps.sublane(blk.dtype)
            second = shape[-2]
            if second > sub and second % sub:
                out.append(
                    Finding(
                        "RK003",
                        plan.path,
                        0,
                        f"{ctx} {blk.name} sublane dim {second} not a multiple "
                        f"of {sub} for {blk.dtype} (backend '{caps.name}')",
                    )
                )

    # RK002: VMEM footprint
    total = 0
    for blk in plan.blocks:
        nbytes = caps.padded_bytes(blk.block_shape, blk.dtype)
        total += nbytes * (caps.pipeline_buffers if blk.pipelined else 1)
    for _, shape, dt in plan.scratch:
        total += caps.padded_bytes(shape, dt)
    for _, shape, dt in plan.implicit:
        total += caps.padded_bytes(shape, dt)
    if total > caps.vmem_bytes:
        out.append(
            Finding(
                "RK002",
                plan.path,
                0,
                f"{ctx} VMEM footprint {total} B exceeds backend "
                f"'{caps.name}' budget {caps.vmem_bytes} B "
                f"({total / caps.vmem_bytes:.1f}x)",
            )
        )

    # RK004: index maps in bounds over the (sampled) grid
    out.extend(_check_index_maps(plan, ctx))
    return out


def _grid_samples(grid: Sequence[int]) -> List[Tuple[int, ...]]:
    """Cartesian product of per-dim samples: exhaustive for small dims,
    {0, 1, mid, last} corners for large ones."""
    axes = []
    for size in grid:
        size = int(size)
        if size <= 0:
            return []
        if size <= 16:
            axes.append(range(size))
        else:
            axes.append(sorted({0, 1, size // 2, size - 1}))
    return list(itertools.product(*axes))


def _check_index_maps(plan: Plan, ctx: str) -> List[Finding]:
    out: List[Finding] = []
    samples = _grid_samples(plan.grid)
    for blk in plan.blocks:
        if len(blk.block_shape) != len(blk.array_shape):
            continue  # already an RK001
        # max legal block index per axis (ceil handles non-covering
        # blocks, already flagged by RK001)
        limits = [
            -(-adim // bdim) if bdim else 0
            for adim, bdim in zip(blk.array_shape, blk.block_shape)
        ]
        for point in samples:
            try:
                idx = blk.index_map(*point)
            except Exception as e:
                out.append(
                    Finding(
                        "RK004",
                        plan.path,
                        0,
                        f"{ctx} {blk.name} index_map raised at grid {point}: "
                        f"{type(e).__name__}: {e}",
                    )
                )
                break
            if len(idx) != len(limits):
                out.append(
                    Finding(
                        "RK004",
                        plan.path,
                        0,
                        f"{ctx} {blk.name} index_map rank {len(idx)} != "
                        f"operand rank {len(limits)}",
                    )
                )
                break
            bad = [
                ax
                for ax, (i, lim) in enumerate(zip(idx, limits))
                if not 0 <= int(i) < max(lim, 1)
            ]
            if bad:
                out.append(
                    Finding(
                        "RK004",
                        plan.path,
                        0,
                        f"{ctx} {blk.name} index_map out of bounds at grid "
                        f"{point}: block index {tuple(int(i) for i in idx)} "
                        f"vs limits {tuple(limits)} (axes {bad})",
                    )
                )
                break
    return out


# ---------------------------------------------------------------- frontends
def check_config(
    kernel: str,
    dims: Dims,
    config: Dict[str, int],
    backend: Optional[str] = None,
    sig: str = "",
) -> List[Finding]:
    """Check one (kernel, dims, tile-config) triple against a backend."""
    caps = tuning.capabilities(backend)
    planner = PLANNERS.get(kernel)
    if planner is None:
        return [Finding("RK001", "src/repro/kernels", 0, f"unknown kernel '{kernel}'")]
    findings: List[Finding] = []
    for plan in planner(dims, config):
        findings.extend(_check_plan(plan, caps, sig or tuning.signature(**dims)))
    return findings


def _sig_dims(kernel: str, sig: str) -> Optional[Dims]:
    """Parse a tuned-cache signature string back into planner dims."""
    dims: Dims = {}
    try:
        for part in sig.split(","):
            key, val = part.split("=", 1)
            dims[key] = val if key == "dtype" else int(val)
    except ValueError:
        return None
    needed = {
        "flash_attention_fwd": {"B", "Sq", "Sk", "Hq", "Hkv", "D"},
        "flash_attention_bwd": {"B", "Sq", "Sk", "Hq", "Hkv", "D"},
        "wkv6_fwd": {"B", "T", "H", "K", "V"},
        "rmsnorm_fwd": {"rows", "d"},
        "paged_attention_fwd": {"B", "Hq", "Hkv", "D", "P", "ps", "npag"},
    }.get(kernel, set())
    return dims if needed <= set(dims) else None


def _auto_config(
    kernel: str, dims: Dims, config: Dict[str, int], backend: Optional[str]
) -> Dict[str, int]:
    """The config the *auto* resolution path would actually run: model
    the resolver-side clamps (rmsnorm's VMEM clamp; the paged ppb clamp
    is already inside the planner) so defaults and tuned entries are
    judged as applied, while explicit configs stay raw."""
    cfg = dict(config)
    if kernel == "rmsnorm_fwd" and "block_rows" in cfg:
        cfg["block_rows"] = tuning.clamp_rmsnorm_rows(
            cfg["block_rows"],
            d=int(dims["d"]),
            dtype=str(dims.get("dtype", "float32")),
            backend=backend,
        )
    return cfg


def check_defaults(backend: Optional[str] = None) -> List[Finding]:
    """Every DEFAULTS entry must be legal for the canonical shapes."""
    findings: List[Finding] = []
    for kernel, shapes in CANONICAL_DIMS.items():
        config = tuning.DEFAULTS[kernel]
        for dims in shapes:
            findings.extend(
                check_config(
                    kernel, dims, _auto_config(kernel, dims, config, backend), backend
                )
            )
    return findings


def check_tuned_cache(backend: Optional[str] = None) -> List[Finding]:
    """Every tuned-cache entry must be legal for its own signature."""
    be = backend or tuning.backend_name()
    path = tuning.cache_path(be)
    display = f"results/tuned/{be}.json"
    findings: List[Finding] = []
    try:
        import json

        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return findings  # no cache for this backend: nothing to do
    for key, entry in (data.get("entries") or {}).items():
        kernel, _, sig = key.partition("|")
        config = dict(tuning.DEFAULTS.get(kernel, {}))
        config.update({k: int(v) for k, v in (entry.get("config") or {}).items()})
        if not config:
            continue
        dims = _sig_dims(kernel, sig)
        if dims is None:
            findings.append(
                Finding(
                    "RK001", display, 0, f"unparseable tuned-cache signature '{key}'"
                )
            )
            continue
        config = _auto_config(kernel, dims, config, be)
        for f in check_config(kernel, dims, config, be, sig=sig):
            findings.append(Finding(f.rule, display, 0, f.message))
    return findings


def check_all(backend: Optional[str] = None) -> List[Finding]:
    return check_defaults(backend) + check_tuned_cache(backend)
