"""Committed, locally runnable CI assertion checks.

Each subcommand replays one of the structural checks the CI workflow
gates on, straight from the benchmark JSONL — so a red CI step reproduces
locally with one command instead of digging a heredoc out of the
workflow file:

    PYTHONPATH=src python tools/ci_checks.py serving-goodput
    PYTHONPATH=src python tools/ci_checks.py tuned-cache
    PYTHONPATH=src python tools/ci_checks.py scaling-efficiency
    PYTHONPATH=src python tools/ci_checks.py paged-parity
    PYTHONPATH=src python tools/ci_checks.py prefix-parity
    PYTHONPATH=src python tools/ci_checks.py chaos-parity
    PYTHONPATH=src python tools/ci_checks.py pd-parity
    PYTHONPATH=src python tools/ci_checks.py trace-replay-error
    PYTHONPATH=src python tools/ci_checks.py doc-refs
    PYTHONPATH=src python tools/ci_checks.py inject-slowdown --factor 2
    PYTHONPATH=src python tools/ci_checks.py regression-gate

``inject-slowdown`` rewrites the JSONL with every timing multiplied by
the factor; ``regression-gate`` is the whole CI gate loop in one
command (compare vs restored baselines, re-bless, then self-test that a
scratch-copy slowdown makes the compare exit exactly 3).
``paged-parity`` and ``prefix-parity`` are standalone (no JSONL):
``paged-parity`` builds a tiny monolithic and paged engine pair at
equal KV memory budget and asserts greedy token parity plus
strictly-more concurrent admissions on the paged side; ``prefix-parity``
does the same for the prefix-sharing radix cache (cache on vs off at
equal page budget: token parity on a shared-prompt burst and a
multi-turn replay, strictly-more admissions, warm TTFT < cold TTFT);
``chaos-parity`` runs a deadline/priority burst under the default
seeded fault plan and asserts every survivor is token-identical to the
fault-free run with zero leaked pages, then self-tests its own leak
detector by no-op'ing the engine's page-release seam;
``pd-parity`` runs the same tiny model through the interleaved paged
engine and the disaggregated P/D engine and asserts greedy token parity
on a mixed burst, one page handoff per request reaching decode, and a
strictly lower decode-step p95 stall under a chunked-prefill-heavy
staggered workload (doctored self-tests for both gates).

``trace-replay-error`` gates the trace→DAG→replay cost model: every
captured scaling-matrix cell's identity replay must land within
``--max-rel-err`` (default 25%) of the measurement it decomposed, and a
doctored prediction must make the gate trip (self-test).
``doc-refs`` is the documentation lint: ``FILE.md §N``-style references
must resolve to an existing file with that section heading, and CLI
flags named in README/EXPERIMENTS/DESIGN prose must be defined by some
``launch/*``/``benchmarks/run``/``tools``/``chip_smoke.py`` argparse;
a planted dangling reference must fire (self-test).

Every check takes ``--jsonl`` (default ``results/bench/latest.jsonl``)
and exits 0/1; assertion messages name the offending record.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))  # for `import benchmarks.run` (gate)

DEFAULT_JSONL = REPO / "results" / "bench" / "latest.jsonl"
DEFAULT_BASELINES = REPO / "results" / "baselines"


def _records(jsonl: str):
    from repro.bench import read_jsonl

    path = Path(jsonl)
    if not path.exists():
        raise SystemExit(f"no bench records at {path}; run benchmarks.run")
    return read_jsonl(path)


def check_serving_goodput(args: argparse.Namespace) -> int:
    """Continuous batching must beat the static scheduler on the shared
    mixed-budget burst, and every serving record needs sane latencies."""
    recs = {r.name: r for r in _records(args.jsonl) if r.group == "serving"}
    for need in ("serving/sched_static", "serving/sched_continuous"):
        assert need in recs, f"missing record {need}"
    for r in recs.values():
        assert r.ttft_us > 0, f"{r.name}: missing ttft_us"
        assert r.p95_us >= r.p50_us > 0, f"{r.name}: bad percentiles"
    st = recs["serving/sched_static"].derived["goodput_rps"]
    ct = recs["serving/sched_continuous"].derived["goodput_rps"]
    assert ct > st, f"continuous goodput {ct} <= static {st}"
    print(f"serving-goodput: continuous {ct} > static {st} OK")
    return 0


def check_tuned_cache(args: argparse.Namespace) -> int:
    """The autotuner sweep must have persisted a winner that the kernel
    tuning lookup layer resolves for the swept rmsnorm shape."""
    import numpy as np

    from repro.kernels import tuning

    sig = tuning.rmsnorm_signature(args.rows, args.d, np.float32)
    cfg = tuning.lookup("rmsnorm_fwd", sig)
    assert cfg and "block_rows" in cfg, f"no tuned entry for {sig}"
    rows = tuning.resolve_rmsnorm_rows(
        None,
        rows=args.rows,
        d=args.d,
        dtype=np.float32,
    )
    assert rows == cfg["block_rows"], (rows, cfg)
    print(f"tuned-cache: {sig} -> {cfg} OK")
    return 0


def check_scaling_efficiency(args: argparse.Namespace) -> int:
    """Structural claims of the measured multi-device scaling matrix:

    * DP/TP/mixed records exist for the full device sweep with in-range
      efficiency/collective/balance metrics;
    * PP throughput follows the most-loaded-stage model within tolerance
      and ordering (Fig. 11c).
    """
    recs = {
        r.name: r
        for r in _records(args.jsonl)
        if r.group == "scaling_matrix" and r.status == "ok"
    }
    for n in (1, 2, 4, 8):
        assert f"scaling_matrix/dp{n}" in recs, f"missing dp{n} record"
    for n in (2, 4, 8):
        assert f"scaling_matrix/tp{n}" in recs, f"missing tp{n} record"
    for name, r in recs.items():
        d = r.derived
        if "efficiency" in d:
            assert 0 < d["efficiency"] <= args.max_efficiency, (
                f"{name}: efficiency {d['efficiency']} out of range"
            )
            assert 0 <= d["collective_frac"] < 1, name
            assert 0 <= d["shard_balance"] <= 1, name
    pp = sorted(
        (r for name, r in recs.items() if "/pp_" in name),
        key=lambda r: r.derived["max_stage"],
    )
    assert len(pp) >= 3, f"expected >=3 PP splits, got {len(pp)}"
    for r in pp:
        d = r.derived
        assert d["model_ok"], (
            f"{r.name}: measured/model ratio {d['model_ratio']} escapes "
            f"the most-loaded-stage tolerance band"
        )
    # most-loaded stage governs: a more loaded split must not beat a less
    # loaded one (10% slack absorbs wall-clock noise on shared runners;
    # the model_ratio band above is the primary gate)
    for a, b in zip(pp, pp[1:]):
        if a.derived["max_stage"] < b.derived["max_stage"]:
            assert a.derived["tok_s"] > 0.9 * b.derived["tok_s"], (
                f"{b.name} (max_stage {b.derived['max_stage']}) should be "
                f"slower than {a.name} ({a.derived['max_stage']})"
            )
    ratios = " ".join(
        f"pp[{r.derived['max_stage']}]={r.derived['model_ratio']}"
        for r in pp
    )
    print("scaling-efficiency:", ratios, "OK")
    return 0


def check_paged_parity(args: argparse.Namespace) -> int:
    """The paged-KV correctness gate, self-contained on a tiny model:

    * greedy outputs of the paged engine are token-identical to the
      monolithic continuous engine for every request — across mixed
      decode budgets AND mixed prompt lengths (chunked prefill included);
    * at equal KV memory budget (slots x span tokens on both sides) the
      paged engine admits strictly more concurrent requests on the
      mixed-budget burst.
    """
    from repro.data.pipeline import synth_requests
    from repro.launch.serve import build_engine
    from repro.serving import SimClock

    reduce_kw = dict(layers=2, d_model=64, vocab=128, d_ff=128)
    prompt, budget_max, slots, ps = 8, 24, 4, args.page_size
    span = prompt + budget_max
    cont, cfg = build_engine(
        "granite-3-8b",
        batch=slots,
        prompt_len=prompt,
        max_new_tokens=budget_max,
        scheduler="continuous",
        reduce_kw=reduce_kw,
        clock=SimClock(),
    )
    paged, _ = build_engine(
        "granite-3-8b",
        batch=2 * slots,
        prompt_len=prompt,
        max_new_tokens=budget_max,
        scheduler="paged",
        page_size=ps,
        num_pages=slots * span // ps,
        prefill_chunk_tokens=prompt // 2,
        reduce_kw=reduce_kw,
        clock=SimClock(),
    )
    # mixed budgets (burst) + a second wave with a shorter prompt, so
    # parity also covers chunked prefill ending on a partial chunk
    reqs = synth_requests(cfg, 8, prompt, max_new_tokens=(2, budget_max))
    short = synth_requests(cfg, 4, prompt - 3, max_new_tokens=5, seed=1)
    for r in short:
        r.rid += 100
    reqs = reqs + short
    rc = cont.run(reqs)
    rp = paged.run(reqs)
    toks_c = {m.rid: [int(t) for t in m.tokens] for m in rc.metrics}
    toks_p = {m.rid: [int(t) for t in m.tokens] for m in rp.metrics}
    assert rc.completed == rp.completed == len(reqs), (
        f"incomplete runs: continuous {rc.completed}, paged {rp.completed}"
    )
    for rid, want in toks_c.items():
        assert toks_p[rid] == want, (
            f"request {rid}: paged tokens {toks_p[rid]} != monolithic {want}"
        )
    assert rp.peak_concurrency > rc.peak_concurrency, (
        f"paged peak_concurrency {rp.peak_concurrency} <= monolithic "
        f"{rc.peak_concurrency} at equal KV budget ({slots * span} tokens)"
    )
    print(
        f"paged-parity: {len(reqs)} requests token-identical; "
        f"concurrency {rp.peak_concurrency} > {rc.peak_concurrency} "
        f"at {slots * span}-token budget OK"
    )
    return 0


def check_prefix_parity(args: argparse.Namespace) -> int:
    """The prefix-sharing correctness gate, standalone on a tiny model:

    * greedy outputs of the prefix-cached paged engine are
      token-identical to the cache-free paged engine on a
      shared-system-prompt burst plus a multi-turn session replay
      (covers read-only page attach, warm-suffix chunked prefill, AND
      the copy-on-write path when a whole prompt is cached);
    * at equal page budget the cached engine admits strictly more
      concurrent requests on the shared burst and reports
      prefill_tokens_saved > 0.
    """
    import numpy as np

    from repro.data.pipeline import synth_sessions
    from repro.launch.serve import build_engine
    from repro.serving import Request, SimClock

    reduce_kw = dict(layers=2, d_model=64, vocab=128, d_ff=128)
    ps, budget, lanes = args.page_size, 8, 8
    system_len, suffix_len, turns = 16, 8, 3
    span = 32 + turns * 16 + budget      # covers the longest replay turn
    engines = {}
    for pc in (False, True):
        engines[pc], cfg = build_engine(
            "granite-3-8b",
            batch=lanes,
            prompt_len=span - budget,
            max_new_tokens=budget,
            scheduler="paged",
            page_size=ps,
            num_pages=args.num_pages,
            prefill_chunk_tokens=2 * ps,
            prefix_cache=pc,
            reduce_kw=reduce_kw,
            clock=SimClock(),
        )
    # shared-system-prompt burst: one system prefix, distinct suffixes,
    # duplicated prompts included so the whole-prompt CoW path runs
    rng = np.random.default_rng(7)
    system = rng.integers(1, cfg.vocab_size, system_len).astype(np.int32)
    burst = []
    for i in range(8):
        sfx = rng.integers(1, cfg.vocab_size, suffix_len).astype(np.int32)
        burst.append(Request(rid=i, prompt=np.concatenate([system, sfx]),
                             max_new_tokens=budget))
    burst.append(Request(rid=8, prompt=burst[0].prompt.copy(),
                         max_new_tokens=budget, arrival_s=1.0))
    replay = synth_sessions(cfg, 2, turns, max_new_tokens=budget,
                            think_s=200.0, stagger_s=60.0, seed=3)
    for r in replay:
        r.rid += 1000
    reports = {}
    for label, reqs in (("burst", burst), ("replay", replay)):
        for pc in (False, True):
            rep = reports[label, pc] = engines[pc].run(list(reqs))
            assert rep.completed == len(reqs), (
                f"{label} cache={pc}: {rep.completed}/{len(reqs)} finished"
            )
        toks_off = {m.rid: [int(t) for t in m.tokens]
                    for m in reports[label, False].metrics}
        toks_on = {m.rid: [int(t) for t in m.tokens]
                   for m in reports[label, True].metrics}
        for rid, want in toks_off.items():
            assert toks_on[rid] == want, (
                f"{label} request {rid}: cached tokens {toks_on[rid]} "
                f"!= uncached {want}"
            )
    off, on = reports["burst", False], reports["burst", True]
    assert on.peak_concurrency > off.peak_concurrency, (
        f"cached peak_concurrency {on.peak_concurrency} <= uncached "
        f"{off.peak_concurrency} at equal {args.num_pages}-page budget"
    )
    assert on.prefill_tokens_saved > 0, "cache on but no prefill saved"
    warm = reports["replay", True].ttft_warm_samples_s()
    cold = reports["replay", True].ttft_cold_samples_s()
    assert warm and cold and max(warm) < min(cold), (
        f"replay warm TTFT {warm} not strictly below cold {cold}"
    )
    print(
        f"prefix-parity: {len(burst) + len(replay)} requests "
        f"token-identical; burst concurrency {on.peak_concurrency} > "
        f"{off.peak_concurrency} at {args.num_pages}-page budget, "
        f"saved {on.prefill_tokens_saved} prefill tokens; replay warm "
        f"TTFT {max(warm)}s < cold {min(cold)}s OK"
    )
    return 0


def check_chaos_parity(args: argparse.Namespace) -> int:
    """The fault-injection correctness gate, standalone on a tiny model:

    * a deadline/priority burst through the paged engine under the
      default seeded :class:`FaultPlan` must inject every scheduled
      fault, recover all of them, and leak zero pages;
    * every request that still completes under chaos is token-identical
      to the fault-free run (faults perturb scheduling and timing, never
      numerics — the chaos-parity contract);
    * self-test: with ``PagedEngine._release_pages`` no-op'd the leak
      detector MUST report leaked pages — proving the gate can actually
      trip, not just that this workload happens to be clean.
    """
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.serving import FaultPlan, PagedEngine, Request, SimClock

    reduce_kw = dict(layers=2, d_model=64, vocab=128, d_ff=128)

    def make(num_pages):
        return build_engine(
            "granite-3-8b",
            batch=2,
            prompt_len=18,
            max_new_tokens=6,
            scheduler="paged",
            page_size=4,
            num_pages=num_pages,
            prefill_chunk_tokens=4,
            reduce_kw=reduce_kw,
            clock=SimClock(),
        )

    def workload(cfg, mixed_priority=True):
        rng = np.random.default_rng(11)
        return [
            Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 6 + 2 * (i % 3)
                                        ).astype(np.int32),
                    max_new_tokens=5 + (i % 2), arrival_s=0.5 * i,
                    deadline_s=500.0,
                    priority=2 if mixed_priority and i == 3 else 0)
            for i in range(5)
        ]

    eng, cfg = make(13)
    base = eng.run(workload(cfg))
    assert base.completed == len(base.metrics), (
        f"fault-free run incomplete: {base.completed}/{len(base.metrics)}"
    )
    want = {m.rid: [int(t) for t in m.tokens] for m in base.metrics}

    eng.fault_plan = FaultPlan.default(args.seed)
    chaos = eng.run(workload(cfg))
    s = chaos.summary()
    assert s["faults_injected"] > 0, "fault plan injected nothing"
    assert s["fault_recoveries"] == s["faults_injected"], (
        f"unrecovered faults: {s['fault_recoveries']}/{s['faults_injected']}"
    )
    survivors = [m for m in chaos.metrics if m.outcome == "completed"]
    assert survivors, "no request survived the default fault plan"
    for m in survivors:
        got = [int(t) for t in m.tokens]
        assert got == want[m.rid], (
            f"request {m.rid}: tokens under chaos {got} != fault-free "
            f"{want[m.rid]}"
        )
    assert s["pages_leaked"] == 0, (
        f"{s['pages_leaked']} pages leaked after the chaos run"
    )

    # self-test: break the one page-release seam; uniform priorities and
    # no fault plan so nothing requeues (a requeue would re-allocate a
    # never-freed rid and crash instead of leaking), and a pool sized so
    # the leaky run still completes — the leak metric is REQUIRED to trip
    leaky_eng, cfg2 = make(64)
    orig = PagedEngine._release_pages
    PagedEngine._release_pages = lambda self, alloc, rid: None
    try:
        leaky = leaky_eng.run(workload(cfg2, mixed_priority=False))
        leaked = leaky.pages_leaked
    finally:
        PagedEngine._release_pages = orig
    assert leaked > 0, (
        "self-test: page release no-op'd but the leak detector reported "
        "0 leaked pages — the gate cannot trip"
    )
    print(
        f"chaos-parity: {s['faults_injected']} faults injected+recovered, "
        f"{len(survivors)}/{len(want)} survivors token-identical, 0 pages "
        f"leaked; self-test leaked {leaked} pages when release was "
        f"disabled OK"
    )
    return 0


def _assert_pd_token_parity(toks_paged: dict, toks_disagg: dict) -> None:
    """Per-request greedy token parity between the interleaved and
    disaggregated runs; raises AssertionError naming the first
    divergence (extracted so the doctored self-test can call it)."""
    assert set(toks_paged) == set(toks_disagg), (
        f"rid sets differ: {sorted(toks_paged)} vs {sorted(toks_disagg)}"
    )
    for rid, want in sorted(toks_paged.items()):
        assert toks_disagg[rid] == want, (
            f"request {rid}: disaggregated tokens {toks_disagg[rid]} != "
            f"interleaved {want}"
        )


def _assert_stall_improvement(p95_disagg: float,
                              p95_interleaved: float) -> None:
    """Disaggregation must strictly reduce the decode-step p95 stall —
    the whole point of splitting the roles (extracted for the
    self-test)."""
    assert p95_disagg < p95_interleaved, (
        f"disaggregated decode-step p95 stall {p95_disagg}s is not "
        f"strictly below interleaved {p95_interleaved}s"
    )


def check_pd_parity(args: argparse.Namespace) -> int:
    """The P/D-disaggregation gate, standalone on a tiny model:

    * greedy outputs of the disaggregated engine (separate prefill and
      decode worker pools over one shared page pool) are token-identical
      to the interleaved paged engine for every request on a mixed
      burst — across mixed decode budgets AND mixed prompt lengths
      (chunked prefill included);
    * every request that reaches decode does so through exactly one
      PageHandoff transfer;
    * under a chunked-prefill-heavy staggered workload the decode-step
      p95 stall (time a decode lane with live requests spends waiting on
      the loop's prefill dispatches) is strictly lower disaggregated
      than interleaved — prefill interference actually left the decode
      path;
    * self-test: a doctored token stream MUST trip the parity check, and
      the interleaved stalls compared against themselves MUST trip the
      strict-improvement check — proving both gates can fire.
    """
    import numpy as np

    from repro.data.pipeline import synth_requests
    from repro.launch.serve import build_engine
    from repro.serving import Request, SimClock

    reduce_kw = dict(layers=2, d_model=64, vocab=128, d_ff=128)

    # -- token parity on the mixed burst ------------------------------
    prompt, budget_max, slots, ps = 8, 24, 4, args.page_size

    def make(scheduler, **kw):
        return build_engine(
            "granite-3-8b",
            batch=slots,
            prompt_len=prompt,
            max_new_tokens=budget_max,
            scheduler=scheduler,
            page_size=ps,
            prefill_chunk_tokens=prompt // 2,
            reduce_kw=reduce_kw,
            clock=SimClock(),
            **kw,
        )

    paged, cfg = make("paged")
    disagg, _ = make("disaggregated", prefill_workers=2, decode_workers=2)
    reqs = synth_requests(cfg, 8, prompt, max_new_tokens=(2, budget_max))
    short = synth_requests(cfg, 4, prompt - 3, max_new_tokens=5, seed=1)
    for r in short:
        r.rid += 100
    reqs = reqs + short
    rp = paged.run(reqs)
    rd = disagg.run(reqs)
    assert rp.completed == rd.completed == len(reqs), (
        f"incomplete runs: interleaved {rp.completed}, "
        f"disaggregated {rd.completed}"
    )
    toks_p = {m.rid: [int(t) for t in m.tokens] for m in rp.metrics}
    toks_d = {m.rid: [int(t) for t in m.tokens] for m in rd.metrics}
    _assert_pd_token_parity(toks_p, toks_d)
    assert rd.handoffs == len(reqs), (
        f"{rd.handoffs} handoffs for {len(reqs)} requests reaching "
        "decode — pages did not change roles exactly once per request"
    )

    # -- decode interference under a chunked-prefill-heavy stagger ----
    pl, budget, chunk = 16, 12, 4

    def make_hot(scheduler, **kw):
        return build_engine(
            "granite-3-8b",
            batch=2,
            prompt_len=pl,
            max_new_tokens=budget,
            scheduler=scheduler,
            page_size=4,
            prefill_chunk_tokens=chunk,
            reduce_kw=reduce_kw,
            clock=SimClock(),
            **kw,
        )

    inter, cfg2 = make_hot("paged")
    dis2, _ = make_hot("disaggregated")
    rng = np.random.default_rng(5)
    stagger = [
        Request(rid=i,
                prompt=rng.integers(1, cfg2.vocab_size, pl).astype(np.int32),
                max_new_tokens=budget, arrival_s=45.0 * i)
        for i in range(8)
    ]
    si = inter.run(list(stagger)).summary()
    sd = dis2.run(list(stagger)).summary()
    assert si.get("decode_stall_p95_s", 0.0) > 0, (
        "interleaved run recorded no positive decode-step stalls — the "
        "workload does not exercise prefill interference"
    )
    p95_i = si["decode_stall_p95_s"]
    p95_d = sd.get("decode_stall_p95_s", 0.0)
    _assert_stall_improvement(p95_d, p95_i)

    # -- self-tests: both gates must be able to trip ------------------
    doctored = {rid: list(t) for rid, t in toks_d.items()}
    victim = sorted(doctored)[0]
    doctored[victim][-1] ^= 1
    try:
        _assert_pd_token_parity(toks_p, doctored)
    except AssertionError:
        pass
    else:
        raise AssertionError(
            "self-test: a flipped token passed the parity check — "
            "pd-parity cannot trip"
        )
    try:
        _assert_stall_improvement(p95_i, p95_i)
    except AssertionError:
        pass
    else:
        raise AssertionError(
            "self-test: equal stall p95s passed the strict-improvement "
            "check — pd-parity cannot trip"
        )
    print(
        f"pd-parity: {len(reqs)} requests token-identical with "
        f"{rd.handoffs} handoffs; decode-step p95 stall "
        f"{p95_d:.1f}s (disaggregated) < {p95_i:.1f}s (interleaved); "
        "self-tests tripped OK"
    )
    return 0


def check_static_analysis(args: argparse.Namespace) -> int:
    """The static-analysis gate, self-testing like chaos-parity:

    * the repo itself must be clean under every ``repro.analysis`` layer
      (seam AST lint, kernel tile contracts, traced hot-path audit);
    * self-test 1: the planted-violation fixtures under
      ``tests/fixtures/analysis/`` MUST trip every RS rule — proving the
      lint can fire, not just that the tree happens to be clean;
    * self-test 2: one deliberately illegal tile config per kernel MUST
      be rejected by the contract checker (VMEM overflow on flash/rwkv/
      rmsnorm/paged), while the shipped DEFAULTS stay accepted.
    """
    from repro.analysis import __main__ as analysis_cli
    from repro.analysis import kernel_lint, seams

    layers = (
        ("seams", "kernels")
        if args.skip_graphs
        else ("seams", "kernels", "graphs")
    )
    findings = analysis_cli.run_layers(layers)
    assert not findings, "repo not clean:\n" + "\n".join(
        str(f) for f in findings
    )

    fixtures = REPO / "tests" / "fixtures" / "analysis"
    tripped = {f.rule for f in seams.scan_tree(fixtures)}
    expected = {"RS101", "RS102", "RS103", "RS104", "RS105"}
    missing = expected - tripped
    assert not missing, (
        f"self-test: planted fixtures under {fixtures} did not trip "
        f"{sorted(missing)} — the lint cannot fire"
    )

    illegal = [
        (
            "flash_attention_fwd",
            dict(B=1, Sq=2048, Sk=2048, Hq=32, Hkv=8, D=128, dtype="float32"),
            {"block_q": 2048, "block_k": 2048},
        ),
        (
            "wkv6_fwd",
            dict(B=1, T=2048, H=32, K=64, V=64, dtype="float32"),
            {"chunk": 1024},
        ),
        (
            "rmsnorm_fwd",
            dict(rows=65536, d=512, dtype="float32"),
            {"block_rows": 65536},
        ),
        (
            "paged_attention_fwd",
            dict(B=8, Hq=32, Hkv=8, D=128, P=512, ps=16, npag=512, dtype="float32"),
            {"pages_per_block": 512},
        ),
    ]
    for kernel, dims, cfg in illegal:
        bad = kernel_lint.check_config(kernel, dims, cfg, "tpu")
        assert bad, (
            f"self-test: illegal tile config {cfg} for {kernel} was "
            "accepted — the contract checker cannot trip"
        )
    defaults_bad = kernel_lint.check_defaults("tpu")
    assert not defaults_bad, "shipped DEFAULTS rejected: " + "; ".join(
        str(f) for f in defaults_bad
    )
    print(
        f"static-analysis: repo clean across {','.join(layers)}; "
        f"self-test tripped {sorted(tripped & expected)} on fixtures and "
        f"rejected {len(illegal)} illegal tile configs OK"
    )
    return 0


_TRACE_CELLS = (
    "trace_replay/dp1", "trace_replay/dp2", "trace_replay/dp4",
    "trace_replay/dp8", "trace_replay/tp2", "trace_replay/tp4",
    "trace_replay/tp8", "trace_replay/mix_4x2", "trace_replay/mix_2x4",
)


def _trace_cell_errors(recs, max_rel_err: float) -> dict:
    """name -> recomputed rel_err for every gated trace-replay record;
    raises AssertionError on a missing cell or an out-of-bound error.
    Recomputes from predicted_us/measured_us so a doctored prediction
    cannot hide behind a stale stored rel_err."""
    by_name = {r.name: r for r in recs if r.group == "trace_replay"}
    out = {}
    for name in _TRACE_CELLS + ("trace_replay/serve_paged",):
        assert name in by_name, f"missing record {name}"
        d = by_name[name].derived
        measured = float(d.get("measured_us", d.get("busy_us", 0.0)))
        predicted = float(d["predicted_us"])
        assert measured > 0, f"{name}: non-positive measured_us {measured}"
        rel = abs(predicted - measured) / measured
        assert rel <= max_rel_err, (
            f"{name}: replay predicted {predicted:.1f}us vs measured "
            f"{measured:.1f}us — rel_err {rel:.4f} > {max_rel_err}"
        )
        out[name] = rel
    return out


def check_trace_replay(args: argparse.Namespace) -> int:
    """The trace→DAG→replay prediction gate (DESIGN.md §3):

    * every captured scaling-matrix cell (dp1..8, tp2..8, 4x2, 2x4) and
      the serving dispatch trace must be present in the JSONL with an
      identity-replay prediction within ``--max-rel-err`` of the
      measurement the DAG was decomposed from — the bound on how much
      the lane decomposition is allowed to drift from what was measured;
    * cross-split what-if records (``trace_replay/whatif_*``) must exist
      but are REPORTED, not gated (simulated-host contention, see
      DESIGN.md §4) — the gate only insists they carry both numbers;
    * self-test: doctoring one cell's predicted_us by 2x the bound MUST
      trip the checker — proving the gate can fire.
    """
    import copy

    recs = _records(args.jsonl)
    errors = _trace_cell_errors(recs, args.max_rel_err)
    whatif = [r for r in recs if r.name.startswith("trace_replay/whatif_")]
    assert whatif, "no trace_replay/whatif_* records (cross-split report)"
    for r in whatif:
        assert "predicted_us" in r.derived and "measured_us" in r.derived, (
            f"{r.name}: what-if record lacks predicted/measured pair"
        )

    doctored = copy.deepcopy(recs)
    victim = next(r for r in doctored if r.name == _TRACE_CELLS[0])
    victim.derived["predicted_us"] = (
        float(victim.derived["measured_us"]) * (1.0 + 2.0 * args.max_rel_err)
    )
    try:
        _trace_cell_errors(doctored, args.max_rel_err)
    except AssertionError:
        pass
    else:
        raise AssertionError(
            "self-test: a doctored prediction passed the gate — "
            "trace-replay-error cannot trip"
        )
    worst = max(errors, key=lambda k: errors[k])
    print(
        f"trace-replay-error: {len(errors)} cells within "
        f"{args.max_rel_err:.0%} (worst {worst} at {errors[worst]:.4f}), "
        f"{len(whatif)} what-if rows reported; self-test tripped OK"
    )
    return 0


# ------------------------------------------------------------- doc-refs
_MD_EXCLUDE = {"ISSUE.md", "PAPER.md", "PAPERS.md", "SNIPPETS.md",
               "CHANGES.md"}
# files whose prose names CLI flags that must exist in some argparse
_FLAG_CHECKED = {"README.md", "EXPERIMENTS.md", "DESIGN.md", "findings.md"}
# flags documented but owned by other programs (XLA, pytest, pip, git)
_FLAG_ALLOW_PREFIXES = ("--xla",)
_FLAG_ALLOW = {"--check"}  # `ruff format --check` in the pre-push recipe
_SECTION_REF_RE = None  # compiled lazily (module import stays cheap)


def _doc_ref_findings(root: Path) -> list:
    """All dangling ``FILE.md §N`` references and undefined CLI flags
    under ``root``. Pure function of the tree so the self-test can run
    it over a planted fixture directory."""
    import re

    ref_re = re.compile(r"([A-Za-z0-9_\-./]+\.md)\s*§\s*(\d+)")
    flag_re = re.compile(r"(--[a-z][a-z0-9][a-z0-9-]*)")
    heading_re_tmpl = r"(?m)^#{{1,6}}[^\n]*§\s*{n}\b"

    md_files = [
        p for p in sorted(root.rglob("*.md"))
        if p.name not in _MD_EXCLUDE
        and not any(part.startswith(".") for part in p.relative_to(root).parts)
    ]

    # argparse-defined flags across every CLI the docs may reference
    defined = set()
    cli_sources = [
        *sorted((root / "src" / "repro" / "launch").glob("*.py")),
        *sorted((root / "src" / "repro" / "analysis").glob("__main__.py")),
        root / "benchmarks" / "run.py",
        root / "tools" / "ci_checks.py",
        root / "chip_smoke.py",
    ]
    arg_re = re.compile(r"add_argument\(\s*[\"'](--[A-Za-z0-9][A-Za-z0-9-]*)")
    for src in cli_sources:
        if not src.exists():
            continue
        text = src.read_text()
        for m in arg_re.finditer(text):
            defined.add(m.group(1))
            # BooleanOptionalAction also registers the --no- negation
            if "BooleanOptionalAction" in text[m.start():m.start() + 300]:
                defined.add("--no-" + m.group(1)[2:])

    findings = []
    for md in md_files:
        rel = md.relative_to(root)
        text = md.read_text()
        for m in ref_re.finditer(text):
            fname, sec = m.group(1), m.group(2)
            target = root / fname
            if not target.exists():
                target = md.parent / fname
            if not target.exists():
                findings.append(
                    f"{rel}: reference '{m.group(0)}' -> missing file "
                    f"{fname}"
                )
                continue
            if not re.search(heading_re_tmpl.format(n=sec),
                             target.read_text()):
                findings.append(
                    f"{rel}: reference '{m.group(0)}' -> {fname} has no "
                    f"'§{sec}' heading"
                )
        if md.name in _FLAG_CHECKED:
            for m in flag_re.finditer(text):
                flag = m.group(1)
                if flag in defined or flag in _FLAG_ALLOW:
                    continue
                if flag.startswith(_FLAG_ALLOW_PREFIXES):
                    continue
                findings.append(
                    f"{rel}: CLI flag '{flag}' is not defined by any "
                    "launch/*, benchmarks/run, repro.analysis, ci_checks "
                    "or chip_smoke argparse"
                )
    return findings


def check_doc_refs(args: argparse.Namespace) -> int:
    """The documentation-reference lint:

    * every ``FILE.md §N`` citation in tracked markdown must point at an
      existing file containing a ``§N`` heading (the DESIGN.md contract:
      EXPERIMENTS.md cites §2/§4 by number, so the numbers are API);
    * every ``--flag`` named in README/EXPERIMENTS/DESIGN/findings prose
      must be defined by an ``add_argument`` in ``launch/*``,
      ``benchmarks/run``, ``repro.analysis``, ``tools/ci_checks`` or
      ``chip_smoke.py``;
    * self-test: a planted fixture tree with a dangling §-reference and
      an undefined flag MUST produce findings — proving the lint fires.
    """
    import tempfile

    root = Path(args.root).resolve()
    findings = _doc_ref_findings(root)
    assert not findings, "dangling doc references:\n" + "\n".join(
        f"  {f}" for f in findings
    )

    with tempfile.TemporaryDirectory() as td:
        planted = Path(td)
        (planted / "DESIGN.md").write_text("## §1 Real section\n")
        (planted / "README.md").write_text(
            "See DESIGN.md §1, DESIGN.md §99, GHOST.md §2, and pass "
            "--definitely-not-a-flag to the CLI.\n"
        )
        tripped = _doc_ref_findings(planted)
    assert len(tripped) == 3, (
        f"self-test: planted fixtures produced {len(tripped)} findings "
        f"(wanted 3: missing section, missing file, undefined flag): "
        f"{tripped}"
    )
    n_md = len([p for p in root.rglob('*.md')
                if p.name not in _MD_EXCLUDE])
    print(
        f"doc-refs: {n_md} markdown files clean; self-test tripped "
        f"{len(tripped)} planted findings OK"
    )
    return 0


def _inject(jsonl: str, factor: float) -> int:
    from repro.bench import write_jsonl

    recs = _records(jsonl)
    for r in recs:
        r.us_per_call *= factor
        r.p50_us *= factor
        r.p95_us *= factor
        r.ttft_us *= factor
        r.samples_us = [s * factor for s in r.samples_us]
    write_jsonl(recs, Path(jsonl))
    return len(recs)


def inject_slowdown(args: argparse.Namespace) -> int:
    """Multiply every timing in the JSONL by --factor (default 2x) —
    the regression-gate self-test injects this to prove --compare trips."""
    n = _inject(args.jsonl, args.factor)
    print(f"inject-slowdown: {n} records slowed {args.factor}x")
    return 0


def regression_gate(args: argparse.Namespace) -> int:
    """The whole CI gate loop in one command: compare fresh records
    against the (restored) baselines, re-bless them, then inject a
    --factor slowdown into a SCRATCH copy and require the compare to
    exit with exactly 3 (run.py's reserved regression code — 1/2 would
    mean the gate itself is broken, not that it tripped)."""
    import shutil
    import tempfile

    import benchmarks.run as bench_run

    base = ["--json", args.jsonl, "--baseline-dir", args.baseline_dir]
    with tempfile.TemporaryDirectory() as td:
        # only the cross-commit compare lands a real trajectory point;
        # the bless and the self-test write to scratch so one gate run
        # never double-counts a commit in the uploaded history
        scratch_traj = ["--trajectory", str(Path(td) / "trajectory.jsonl")]
        real_traj = (
            ["--trajectory", args.trajectory] if args.trajectory else []
        )
        rc = bench_run.main(["--compare-only", *base, *real_traj])
        if rc == 3:
            print(
                "regression-gate: PERFORMANCE REGRESSION vs the restored "
                "baselines (see report above)",
                file=sys.stderr,
            )
            return 3
        assert rc == 0, f"compare against restored baselines exited {rc}"
        rc = bench_run.main(
            ["--compare-only", "--bless", *base, *scratch_traj]
        )
        assert rc == 0, f"bless exited {rc}"
        scratch = str(Path(td) / "slowdown.jsonl")
        shutil.copy(args.jsonl, scratch)
        _inject(scratch, args.factor)
        rc = bench_run.main([
            "--compare-only",
            "--json",
            scratch,
            *scratch_traj,
            "--baseline-dir",
            args.baseline_dir,
        ])
    assert rc == 3, (
        f"expected regression exit 3 on a {args.factor}x slowdown, got {rc}"
    )
    print(f"regression-gate: pass -> bless -> {args.factor}x -> exit 3 OK")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser(
        "serving-goodput",
        help="continuous-batching goodput must beat the static scheduler",
    )
    p.set_defaults(fn=check_serving_goodput)

    p = sub.add_parser(
        "tuned-cache",
        help="autotuner winners resolve through the lookup",
    )
    p.add_argument("--rows", type=int, default=4096)
    p.add_argument("--d", type=int, default=512)
    p.set_defaults(fn=check_tuned_cache)

    p = sub.add_parser(
        "scaling-efficiency",
        help="scaling-matrix records obey the most-loaded-stage model",
    )
    p.add_argument("--max-efficiency", type=float, default=4.0)
    p.set_defaults(fn=check_scaling_efficiency)

    p = sub.add_parser(
        "paged-parity",
        help="paged engine: token parity + admits-more at equal KV budget",
    )
    p.add_argument("--page-size", type=int, default=8)
    p.set_defaults(fn=check_paged_parity)

    p = sub.add_parser(
        "prefix-parity",
        help="prefix cache: token parity + admits-more + warm TTFT wins",
    )
    p.add_argument("--page-size", type=int, default=8)
    p.add_argument("--num-pages", type=int, default=16)
    p.set_defaults(fn=check_prefix_parity)

    p = sub.add_parser(
        "chaos-parity",
        help="fault injection: survivors token-identical + zero page leaks",
    )
    p.add_argument("--seed", type=int, default=0)
    p.set_defaults(fn=check_chaos_parity)

    p = sub.add_parser(
        "pd-parity",
        help="P/D disaggregation: token parity + lower decode p95 stall",
    )
    p.add_argument("--page-size", type=int, default=8)
    p.set_defaults(fn=check_pd_parity)

    p = sub.add_parser(
        "static-analysis",
        help="repo clean under repro.analysis + planted violations trip",
    )
    p.add_argument(
        "--skip-graphs",
        action="store_true",
        help="skip the traced hot-path audit (the slow layer)",
    )
    p.set_defaults(fn=check_static_analysis)

    p = sub.add_parser(
        "trace-replay-error",
        help="trace DAG identity replay within tolerance per matrix cell",
    )
    p.add_argument("--max-rel-err", type=float, default=0.25)
    p.set_defaults(fn=check_trace_replay)

    p = sub.add_parser(
        "doc-refs",
        help="markdown §-references and CLI flags must resolve",
    )
    p.add_argument("--root", default=str(REPO))
    p.set_defaults(fn=check_doc_refs)

    p = sub.add_parser(
        "inject-slowdown",
        help="multiply every recorded timing by --factor",
    )
    p.add_argument("--factor", type=float, default=2.0)
    p.set_defaults(fn=inject_slowdown)

    p = sub.add_parser(
        "regression-gate",
        help="compare vs baselines, re-bless, self-test the gate trips",
    )
    p.add_argument("--factor", type=float, default=2.0)
    p.add_argument("--baseline-dir", default=str(DEFAULT_BASELINES))
    p.add_argument("--trajectory", default=None)
    p.set_defaults(fn=regression_gate)

    for sp in sub.choices.values():
        sp.add_argument(
            "--jsonl",
            default=str(DEFAULT_JSONL),
            help="bench JSONL path",
        )

    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except AssertionError as e:
        print(f"CHECK FAILED [{args.cmd}]: {e}", file=sys.stderr)
        return 1
    except SystemExit as e:  # _records: missing JSONL
        if isinstance(e.code, int):
            return e.code
        print(f"CHECK FAILED [{args.cmd}]: {e.code}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    raise SystemExit(main())
