"""Serving cells: traffic through the program's paged engine.

The traffic file gives the arrivals, the set of prompt lengths with
their weights, and the distribution of output lengths. Every seed gets
the same multiset of prompt lengths, output lengths and gaps between
arrivals, in an order and with token ids drawn from the seed, so that
seeds change which request is where and not how much work there is.

Arrivals are an open loop: Poisson gaps at ``rate_per_s``, drawn as the
quantiles of the exponential distribution, so that every seed has the
same gaps in another order.

The window is one ``PagedEngine.run`` over the requests that arrive in
``--seconds``, built by ``serving.make_engine("paged", ...)`` on the
model's paged prefill and decode steps, with the Pallas paged decode
kernel. Once it has closed, a sample of the finished requests drawn from
the seed, the longest among them, is run through the plain reference,
and the widest gap by which a served token's reference logit lies below
the reference's best decides ``correct``.
"""
from __future__ import annotations

import bisect
import gc
import time
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W
from program import program_config

LOGIT_GAP = "logit_gap"


# ------------------------------------------------------------------ traffic
def _counts(weights, n):
    """Largest-remainder split of ``n`` items over ``weights``."""
    w = np.asarray(weights, float)
    raw = w / w.sum() * n
    base = np.floor(raw).astype(int)
    order = np.argsort(-(raw - base), kind="stable")
    base[order[:n - base.sum()]] += 1
    return base


def generate(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run: dicts with ``arrival_s``, ``prompt`` (int32
    ids) and ``max_new_tokens``, sorted by arrival."""
    rng = np.random.default_rng(seed)
    n = max(1, round(traffic["rate_per_s"] * seconds))
    lengths = np.repeat(traffic["prompt_lengths"],
                        _counts(traffic["prompt_weights"], n))
    out = traffic["output"]
    q = (np.arange(n) + 0.5) / n
    if out["dist"] == "uniform":
        outs = out["min"] + q * (out["max"] - out["min"])
    else:
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        outs = out["median"] * np.exp(out["sigma"] * z)
    outs = np.clip(np.round(outs), out["min"], out["max"]).astype(int)
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    gaps = -np.log1p(-q) / traffic["rate_per_s"]
    lengths, outs, gaps = (rng.permutation(a) for a in (lengths, outs, gaps))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return [{"arrival_s": float(a),
             "prompt": rng.integers(0, vocab, int(p), dtype=np.int32),
             "max_new_tokens": int(o)}
            for a, p, o in zip(arrivals, lengths, outs)]


# -------------------------------------------------------------------- clock
class SpanClock:
    """The engines' clock seam (``now``, ``charge``, ``wait_until``) on the
    host's wall clock, recording each charged dispatch as (kind, start,
    end): the time since the previous charge or wait, up to the charge,
    which the engines make once the dispatch's result is on the host.
    ``t0`` is the first reading after :meth:`arm`, which is when
    ``PagedEngine.run`` starts its clock."""

    def __init__(self) -> None:
        self.events: list = []
        self.t0 = None
        self._mark = time.perf_counter()

    def arm(self) -> None:
        self.events, self.t0 = [], None

    def now(self) -> float:
        t = time.perf_counter()
        if self.t0 is None:
            self.t0 = self._mark = t
        return t

    def charge(self, kind: str, n: int = 1) -> None:
        t = time.perf_counter()
        self.events.append((kind, self._mark, t))
        self._mark = t

    def wait_until(self, t: float) -> None:
        with jax.profiler.TraceAnnotation("serve.wait_for_arrival"):
            d = t - time.perf_counter()
            if d > 0:
                time.sleep(d)
        self._mark = time.perf_counter()

    def ends(self, kind: str) -> list:
        """End times, relative to ``t0``, of the dispatches of ``kind``."""
        return [e - self.t0 for k, _, e in self.events if k == kind]

    def durations(self, kind: str) -> list:
        return [e - s for k, s, e in self.events if k == kind]


def token_times(m, decode_ends) -> list:
    """When each of a request's tokens was on the host, relative to the
    run's start: the first after its prefill, each further one at the end
    of a decode step, since every active lane takes part in every step.
    The engine reads its clock for the first token after the prefill and
    for the finish after the last step, each before the next decode step
    ends, so the request's steps are those that end between the two."""
    k = bisect.bisect_right(decode_ends, m.first_token_s)
    j = bisect.bisect_right(decode_ends, m.finish_s)
    rest = decode_ends[k:j]
    if len(rest) != m.new_tokens - 1:
        raise RuntimeError(
            f"request {m.rid}: {m.new_tokens} tokens, but {len(rest)} decode "
            f"steps ended between its first token and its finish")
    return [m.first_token_s, *rest]


# ------------------------------------------------------------- the program
def program_model(cfg: dict, slots: int, span: int):
    """The program's serving model for this configuration."""
    from repro.configs.base import MeshConfig, RunConfig, ShapeConfig
    from repro.launch.mesh import make_mesh, set_mesh
    from repro.runtime.steps import build_serve_steps

    mc = program_config(cfg)
    rcfg = RunConfig(model=mc, shape=ShapeConfig("serve", "decode", span,
                                                 slots),
                     mesh=MeshConfig((1, 1), ("data", "model")),
                     attention_backend=cfg["attention_backend"],
                     param_dtype=cfg["dtype"], compute_dtype=cfg["dtype"],
                     decode_attention="simple")
    with set_mesh(make_mesh(rcfg.mesh, jax.devices()[:1])):
        _, _, model = build_serve_steps(rcfg)
    return model


def _requests(reqs):
    from repro.serving.request import Request

    return [Request(rid=i, prompt=r["prompt"],
                    max_new_tokens=r["max_new_tokens"],
                    arrival_s=r["arrival_s"]) for i, r in enumerate(reqs)]


def _warm_slices(engine, lo: int, hi: int) -> None:
    """The engine reads each retiring lane's tokens with an eager slice
    ``tokbuf[s, :n]``, one program per output length ``n``: compile them
    all before the window."""
    tokbuf = jnp.zeros((engine.slots, engine.cache_span), jnp.int32)
    for n in range(lo, hi + 1):
        np.asarray(tokbuf[0, :n])


# ---------------------------------------------------------------- the cell
def build(cfg: dict, traffic: dict, seed: int, h=None):
    """The engine of a serving cell over weights made from ``seed``, with
    every shape the traffic uses compiled: each prefill (chunk, live
    pages) shape that the length set makes, the decode step, admission,
    and the per-length token reads. ``h``, the harness's run object where
    given, notes when each phase ended. Returns (engine, clock)."""
    from repro.serving import make_engine

    slots = cfg["slots"]
    span = max(traffic["prompt_lengths"]) + traffic["output"]["max"]
    model = program_model(cfg, slots, span)
    W.check_layout(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                   cfg, cfg["dtype"])
    clock = SpanClock()
    engine = make_engine(
        "paged", model.prefill_chunk, model.decode_step_paged,
        W.make_params(cfg, seed, cfg["dtype"]), model.paged_cache_init,
        page_size=cfg["page_size"], num_pages=cfg["num_pages"],
        prefill_chunk_tokens=cfg["prefill_chunk"], slots=slots,
        cache_span=span, greedy=True, seed=seed, clock=clock)
    if h is not None:
        h.note(f"engine built at {time.perf_counter() - h.t_start:.1f} s")
    warm = [{"arrival_s": 0.0, "prompt": np.ones(p, np.int32),
             "max_new_tokens": 2} for p in traffic["prompt_lengths"]]
    engine.run(_requests(warm))
    _warm_slices(engine, traffic["output"]["min"], traffic["output"]["max"])
    if h is not None:
        h.note(f"shapes warmed at {time.perf_counter() - h.t_start:.1f} s")
    return engine, clock


def summarize(reqs, report, clock) -> dict:
    """What the metric readers take from one engine run."""
    metrics = sorted(report.metrics, key=lambda x: x.rid)
    decode_ends = clock.ends("decode")
    done = [x for x in metrics if x.outcome == "completed"]
    times = {x.rid: token_times(x, decode_ends) for x in done}
    inf = float("inf")
    return dict(
        ttft_s=[x.ttft_s if x.outcome == "completed" else inf
                for x in metrics],
        queue_s=[x.queue_s if x.outcome == "completed" else inf
                 for x in metrics],
        itl_s=[b - a for t in times.values() for a, b in zip(t, t[1:])],
        decode_s=clock.durations("decode"),
        prefill_s=clock.durations("prefill"),
        makespan_s=report.makespan_s,
        last_arrival_s=max(r["arrival_s"] for r in reqs),
        requests=len(reqs), completed=len(done),
        tokens_completed=sum(x.prompt_len + x.new_tokens for x in done),
        decode_rows=[(x.prompt_len, x.new_tokens) for x in done],
        decode_steps=report.decode_steps, prefill_chunks=report.prefills,
        served={x.rid: np.asarray(x.tokens, np.int32) for x in done})


def run(h) -> None:
    """One run of a serving cell; ``h`` is the harness's run object."""
    cfg, traffic = h.cfg, h.traffic
    engine, clock = build(cfg, traffic, h.seed, h)
    reqs = generate(traffic, h.seed, h.seconds, cfg["vocab_size"])
    clock.arm()
    with h.window():
        report = engine.run(_requests(reqs))
    h.read_memory_peak()
    work = summarize(reqs, report, clock)
    served = work.pop("served")
    h.work.update(work)
    h.attempted, h.failed = len(reqs), len(reqs) - work["completed"]
    h.note(f"requests {len(reqs)} completed {work['completed']} decode "
           f"steps {work['decode_steps']} prefill chunks "
           f"{work['prefill_chunks']} window {h.window_s:.3f} s")

    # the comparison, once the program's state is gone
    del engine, report
    gc.collect()
    t0 = time.perf_counter()
    sample = choose_sample(reqs, served, h.seed, traffic["check_tokens"])
    gap = max_gap(cfg, h.seed, reqs, served, sample, "f32")
    h.check(LOGIT_GAP, gap, cfg["check"][LOGIT_GAP])
    h.note(f"compared {sum(len(served[i]) for i in sample)} served tokens "
           f"of {len(sample)} requests in {time.perf_counter() - t0:.1f} s")


def choose_sample(reqs, served, seed: int, tokens: int) -> list:
    """Request ids to compare: the longest finished request, then others
    drawn from the seed, until ``tokens`` served tokens are covered."""
    ids = sorted(served, key=lambda i: (-(len(reqs[i]["prompt"])
                                          + len(served[i])), i))
    if not ids:
        return []
    rng = np.random.default_rng([seed, 1])
    pick = [ids[0]]
    rest = list(rng.permutation(ids[1:]))
    while rest and sum(len(served[i]) for i in pick) < tokens:
        pick.append(int(rest.pop()))
    return pick


def max_gap(cfg, seed, reqs, served, sample, precision: str) -> float:
    """The widest gap, over the sampled requests' served tokens, between
    the reference's best logit and its logit of the served token. With
    ``precision`` other than ``"f32"``, the served tokens are replaced by
    the tokens that the reference at that precision puts first."""
    worst = 0.0
    for i in sample:
        prompt, toks = reqs[i]["prompt"], served[i]
        ids = np.concatenate([prompt, toks[:-1]])[None]
        lo = len(prompt) - 1
        ref = R.sequence_logits(cfg, seed, cfg["dtype"], ids)[:, lo:]
        if precision != "f32":
            low = R.sequence_logits(cfg, seed, cfg["dtype"], ids,
                                    precision=precision)[:, lo:]
            toks = np.asarray(jnp.argmax(low, -1))[0]
        gaps = R.served_gaps(ref, jnp.asarray(toks)[None])
        worst = max(worst, float(gaps.max()))
    return worst
