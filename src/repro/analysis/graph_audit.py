"""Jitted hot-path auditor: purity checks on the step-loop graphs.

The serving and training hot loops are only as fast as their jitted
graphs are clean: a stray host callback serializes the device queue, an
f64 leak doubles every bandwidth-bound op, a python-scalar argument
recompiles the step per distinct value, and a collective in a
single-device graph means the partitioner was misconfigured. This
module traces the real step functions — the train step from
:func:`repro.runtime.steps.build_train_step`, the serve-side decode
callable, and the paged engine's jitted helpers (admit / evict / fused
pool step) — and audits them at three levels:

* **jaxpr walk** (:func:`audit_function`) — flags host-callback
  primitives (RG001) and f64/c128 values (RG002), recursing into every
  sub-jaxpr (scan/while/cond bodies, nested pjit calls);
* **steady-state compile counts** (:func:`audit_engine_steady_state`) —
  runs an identical tiny workload through a paged engine twice and
  requires every jitted helper's compile-cache size to stay flat on the
  second pass (RG003: shape/weak-type churn recompiles);
* **optimized-HLO accounting** (:func:`audit_hlo`) — lowers + compiles a
  step and feeds ``compiled.as_text()`` to
  :func:`repro.core.hlo_analysis.analyze_hlo`, flagging collectives on
  single-device graphs (RG004) and infeed/outfeed host transfers
  (RG005).

All audits run on tiny reduced models (the tier-1 test cell) so the
whole pass is seconds, not minutes.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

from .findings import Finding

# primitive names that host-sync a jitted graph when hit in the step loop
_CALLBACK_PRIMS = (
    "debug_callback",
    "debug_print",
    "pure_callback",
    "io_callback",
    "callback",
    "host_callback",
)
_BAD_DTYPES = ("float64", "complex128")


# ------------------------------------------------------------- jaxpr audit
def _iter_eqns(jaxpr):
    """All equations of a jaxpr, recursing into sub-jaxprs in params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _sub_jaxprs(val):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    vals = val if isinstance(val, (tuple, list)) else (val,)
    for v in vals:
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v


def audit_jaxpr(name: str, closed_jaxpr, path: str = "<jaxpr>") -> List[Finding]:
    """RG001 (host callbacks) + RG002 (f64/c128) over one traced jaxpr."""
    findings: List[Finding] = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    seen_cb: set = set()
    seen_dt: set = set()
    for eqn in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if any(marker in prim for marker in _CALLBACK_PRIMS):
            if prim not in seen_cb:
                seen_cb.add(prim)
                findings.append(
                    Finding(
                        "RG001",
                        path,
                        0,
                        f"{name}: host callback primitive `{prim}` inside the "
                        "jitted hot path (serializes the device queue)",
                    )
                )
        for var in tuple(eqn.outvars) + tuple(eqn.invars):
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _BAD_DTYPES and (prim, dt) not in seen_dt:
                seen_dt.add((prim, dt))
                findings.append(
                    Finding(
                        "RG002",
                        path,
                        0,
                        f"{name}: {dt} value flows through `{prim}` — double "
                        "the bytes of every op it touches",
                    )
                )
    return findings


def audit_function(
    name: str, fn: Callable, *args, path: str = "<traced>", **kwargs
) -> List[Finding]:
    """Trace ``fn(*args, **kwargs)`` with make_jaxpr and audit it."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return audit_jaxpr(name, closed, path=path)


# --------------------------------------------------------------- HLO audit
def audit_hlo_text(
    name: str, text: str, *, expect_single_device: bool = True, path: str = "<hlo>"
) -> List[Finding]:
    """RG004/RG005 over one optimized-HLO dump, using the shared parser
    from :mod:`repro.core.hlo_analysis` for the collective accounting."""
    from repro.core.hlo_analysis import analyze_hlo

    findings: List[Finding] = []
    report = analyze_hlo(text)
    if expect_single_device and report.collectives:
        kinds = sorted({c.opcode for c in report.collectives})
        findings.append(
            Finding(
                "RG004",
                path,
                0,
                f"{name}: single-device step graph emits collectives "
                f"{kinds} ({report.collective_bytes} B) — partitioning is "
                "misconfigured",
            )
        )
    lowered = text.lower()
    for marker in ("infeed", "outfeed"):
        if marker in lowered:
            findings.append(
                Finding(
                    "RG005",
                    path,
                    0,
                    f"{name}: `{marker}` in optimized HLO — host transfer "
                    "inside the compiled step",
                )
            )
    return findings


def audit_hlo(
    name: str,
    fn: Callable,
    *args,
    expect_single_device: bool = True,
    path: str = "<hlo>",
    **kwargs,
) -> List[Finding]:
    """Lower + compile ``fn`` and audit the optimized HLO."""
    import jax

    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    text = compiled.as_text()
    return audit_hlo_text(
        name, text, expect_single_device=expect_single_device, path=path
    )


# ------------------------------------------------------ steady-state audit
def _cache_size(jitted) -> Optional[int]:
    try:
        return int(jitted._cache_size())
    except Exception:
        return None


def _tiny_engine(scheduler: str = "paged"):
    from repro.launch.serve import build_engine
    from repro.serving.request import SimClock

    return build_engine(
        "granite-3-8b",
        batch=2,
        prompt_len=16,
        max_new_tokens=8,
        scheduler=scheduler,
        reduce_kw=dict(layers=2, d_model=64, vocab=128, d_ff=128),
        clock=SimClock(),
        page_size=8,
        num_pages=32,
    )


def _tiny_requests(cfg, n: int = 3, prompt_len: int = 16, new_tokens: int = 6):
    import numpy as np

    from repro.serving.request import Request

    rng = np.random.default_rng(0)
    return [
        Request(
            rid=i,
            arrival_s=0.0,
            prompt=rng.integers(1, 100, size=prompt_len, dtype=np.int32),
            max_new_tokens=new_tokens,
        )
        for i in range(n)
    ]


def audit_engine_steady_state(
    path: str = "src/repro/serving/paged.py",
) -> List[Finding]:
    """RG003: run the same workload twice through one paged engine; every
    jitted helper's compile cache must stay flat on the second pass."""
    engine, cfg = _tiny_engine()
    engine.run(_tiny_requests(cfg))
    helpers = {
        "_pool_step": getattr(engine, "_pool_step", None),
        "_admit": getattr(engine, "_admit", None),
        "_jit_evict": getattr(engine, "_jit_evict", None),
        "_jit_chunk": getattr(engine, "_jit_chunk", None),
    }
    first = {k: _cache_size(v) for k, v in helpers.items() if v is not None}
    engine.run(_tiny_requests(cfg))
    findings: List[Finding] = []
    for k, v in helpers.items():
        if v is None or first.get(k) is None:
            continue
        second = _cache_size(v)
        if second is not None and second > first[k]:
            findings.append(
                Finding(
                    "RG003",
                    path,
                    0,
                    f"PagedEngine.{k}: compile cache grew {first[k]} -> "
                    f"{second} on an identical second run — python-scalar or "
                    "weak-type churn in the call signature",
                )
            )
    return findings


def check_cache_growth(
    name: str, jitted, calls: Sequence[tuple], path: str = "<jit>"
) -> List[Finding]:
    """Generic RG003 probe: after the first call compiles, every further
    same-shape call must hit the cache. ``calls`` is a list of argument
    tuples considered shape-identical by the caller."""
    findings: List[Finding] = []
    if not calls:
        return findings
    jitted(*calls[0])
    base = _cache_size(jitted)
    for args in calls[1:]:
        jitted(*args)
    final = _cache_size(jitted)
    if base is not None and final is not None and final > base:
        findings.append(
            Finding(
                "RG003",
                path,
                0,
                f"{name}: compile cache grew {base} -> {final} across "
                "shape-identical calls (recompilation hazard)",
            )
        )
    return findings


# ------------------------------------------------------------ repo targets
def audit_train_step() -> List[Finding]:
    """Trace the tier-1 tiny train step and audit jaxpr + optimized HLO."""
    import jax

    from repro.configs import ARCHS, MeshConfig, RunConfig, ShapeConfig, reduced
    from repro.data.pipeline import SyntheticLM
    from repro.runtime.steps import build_train_step

    cfg = reduced(ARCHS["granite-3-8b"], layers=2, d_model=64, vocab=256, d_ff=128)
    rcfg = RunConfig(
        model=cfg,
        shape=ShapeConfig("t", "train", 32, 2),
        mesh=MeshConfig(shape=(1, 1), axes=("data", "model")),
        param_dtype="float32",
        attention_backend="dense",
        learning_rate=1e-3,
        warmup_steps=2,
    )
    step_fn, model, opt = build_train_step(rcfg, total_steps=8)
    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    batch = SyntheticLM(rcfg.model, rcfg.shape.global_batch, rcfg.shape.seq_len)
    batch = batch.batch_at(0)
    path = "src/repro/runtime/steps.py"
    findings = audit_function(
        "train_step", step_fn, params, opt_state, batch, path=path
    )
    single = jax.device_count() == 1
    findings += audit_hlo(
        "train_step",
        step_fn,
        params,
        opt_state,
        batch,
        expect_single_device=single,
        path=path,
    )
    return findings


def audit_decode_step() -> List[Finding]:
    """Trace the raw serve-side decode callable on the tiny model."""
    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, MeshConfig, RunConfig, ShapeConfig, reduced
    from repro.runtime.steps import build_serve_steps

    cfg = reduced(ARCHS["granite-3-8b"], layers=2, d_model=64, vocab=128, d_ff=128)
    rcfg = RunConfig(
        model=cfg,
        shape=ShapeConfig("serve", "decode", 32, 2),
        mesh=MeshConfig(shape=(1, 1), axes=("data", "model")),
        param_dtype="float32",
        attention_backend="dense",
        decode_attention="simple",
    )
    prefill_fn, decode_fn, model = build_serve_steps(rcfg)
    params = model.init_params(jax.random.PRNGKey(0))
    caches = model.cache_init(2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    path = "src/repro/runtime/steps.py"
    findings = audit_function(
        "decode_step", decode_fn, params, caches, tok, pos, path=path
    )
    single = jax.device_count() == 1
    findings += audit_hlo(
        "decode_step",
        decode_fn,
        params,
        caches,
        tok,
        pos,
        expect_single_device=single,
        path=path,
    )
    return findings


def audit_all(include_steady_state: bool = True) -> List[Finding]:
    findings = audit_train_step() + audit_decode_step()
    if include_steady_state:
        findings += audit_engine_steady_state()
    return findings
