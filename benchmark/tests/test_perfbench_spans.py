"""The program's own spans and named scopes, and their reduction.

On the CPU: the paged engine and the train loop emit the spans of
``repro/runtime/trace_names.py`` with their attributes and nesting (read
from the profiler's host plane); the scopes claim the ops of the compiled
train step, forward and backward; and they change nothing but metadata in
the compiled decode, prefill-chunk and train-step programs. On a trace
recorded on a TPU v5e (``data/spans.xplane.pb``, made by
``record_spans_trace.py``: a dozen requests through the serving cell's
engine, then four steps of the training cell, both at 2 layers of width
512): the reduction reads the spans, the idle time under them and the
device time by scope."""
from __future__ import annotations

import contextlib
import glob
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from tiny_cells import BENCH, cells, loop_module, make_root

import program_trace as P
import run
import scopes
import xplane
from repro.runtime import trace_names as N

FIXTURE = str(BENCH / "tests" / "data" / "spans.xplane.pb")
MEASURES = (P.decode_gap_p95_ms, P.lanes_per_step, P.idle_engine_share,
            P.decode_scan_copy_share, P.optimizer_share, P.head_loss_share)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("tiny"))
    serve, train = run.resolve(cells("serve")[0], root), \
        run.resolve(cells("train")[0], root)
    return serve, dict(train.traffic, first_steps=1), train.cfg


def _train(cfg, job, seed=5):
    """The training cell's jitted step with its state after one step."""
    mod = loop_module("train")
    bundle = mod.build(cfg, job, jax.devices()[:1])
    with jax.set_mesh(bundle[0]):
        _, params, opt_state, _ = mod.start(bundle, cfg, job, seed)
    return mod, bundle, params, opt_state


def _train_step_text(cfg, job) -> str:
    """The compiled text of the training cell's step, traced afresh."""
    _, _, optimizer, _, _, _, jit_step = loop_module("train").build(
        cfg, job, jax.devices()[:1])
    params = jax.eval_shape(
        loop_module("serve").program_model(cfg, 1, 16).init_params,
        jax.random.PRNGKey(0))
    rows = jax.ShapeDtypeStruct((job["batch"], job["seq"]), jnp.int32)
    return jit_step.lower(params, jax.eval_shape(optimizer.init, params),
                          {"tokens": rows, "labels": rows}
                          ).compile().as_text()


# ------------------------------------------------------------- host spans
@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """A dozen requests through the tiny serving cell's engine, arriving
    faster than it drains them but with a wait first, then three train
    steps, under the profiler on the CPU: the host spans and the
    engine's report."""
    from repro.runtime import train_loop

    c, job, tcfg = tiny
    serve = loop_module("serve")
    engine, _ = serve.build(c.cfg, c.traffic, 5)
    reqs = serve.generate(dict(c.traffic, rate_per_s=20.0), 5, 0.6,
                          c.cfg["vocab_size"])
    reqs[0]["arrival_s"] = 0.05            # the engine waits for the first
    mod, bundle, params, opt_state = _train(tcfg, job)
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        report = engine.run(serve._requests(reqs))
        with jax.set_mesh(bundle[0]):
            train_loop.run(bundle[6], params, opt_state,
                           mod.feed(job, 5, bundle[5], tcfg["vocab_size"]),
                           total_steps=3)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0])
    steps = []
    for plane in pd.planes:
        for line in plane.lines:
            steps += [(e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name == N.TRAIN_STEP_GROUP]
    return P.host_spans(pd), report, steps


def _inside(child, parents):
    return [p for p in parents
            if p.start <= child.start and child.end <= p.end]


def test_engine_emits_every_span_with_its_attributes(traced):
    spans, report, _ = traced
    count = Counter(s.name for s in spans)
    assert set(count) == {N.POOL_INIT, N.STEP, N.REAP, N.ADMIT,
                          N.PREFILL_CHUNK, N.DECODE, N.LANE_STATE_READ,
                          N.RETIRE, N.WAIT_FOR_ARRIVAL, N.TRAIN_BATCH,
                          N.TRAIN_STEP}
    assert count[N.POOL_INIT] == 1
    assert count[N.DECODE] == count[N.LANE_STATE_READ] \
        == report.decode_steps
    assert count[N.PREFILL_CHUNK] == report.prefills
    done = {m.rid for m in report.metrics if m.outcome == "completed"}
    assert len(done) == len(report.metrics) == count[N.ADMIT]
    by = {n: [s for s in spans if s.name == n] for n in count}
    assert [s.attrs["step"] for s in by[N.DECODE]] == \
        list(range(report.decode_steps))
    assert all(0 < s.attrs["lanes"] <= 4 for s in by[N.DECODE])
    assert [s.attrs["step"] for s in by[N.STEP]] == \
        list(range(count[N.STEP]))
    assert {s.attrs["rid"] for s in by[N.RETIRE]} == done
    # a retiring lane's tokens count its first, sampled at admission
    assert sum(s.attrs["tokens"] for s in by[N.RETIRE]) == \
        sum(m.new_tokens for m in report.metrics)
    for a in by[N.ADMIT]:
        chunks = [c for c in by[N.PREFILL_CHUNK] if _inside(c, [a])]
        assert {c.attrs["rid"] for c in chunks} == {a.attrs["rid"]}
        assert sum(c.attrs["tokens"] for c in chunks) == a.attrs["prompt_len"]
        assert chunks[0].attrs["start"] == a.attrs["cached"] == 0


def test_engine_spans_nest(traced):
    spans = traced[0]
    by = {n: [s for s in spans if s.name == n]
          for n in {s.name for s in spans}}
    for child, parent in ((N.REAP, N.STEP), (N.ADMIT, N.STEP),
                          (N.DECODE, N.STEP), (N.RETIRE, N.STEP),
                          (N.WAIT_FOR_ARRIVAL, N.STEP),
                          (N.LANE_STATE_READ, N.DECODE),
                          (N.PREFILL_CHUNK, N.ADMIT)):
        for s in by[child]:
            assert len(_inside(s, by[parent])) == 1, (child, s)
    # a retiring lane is read after its step's lane state, not inside it
    for s in by[N.RETIRE]:
        assert not _inside(s, by[N.DECODE])


def test_train_loop_emits_its_spans(traced):
    spans, _, steps = traced
    batches = [s for s in spans if s.name == N.TRAIN_BATCH]
    train = [s for s in spans if s.name == N.TRAIN_STEP]
    assert [s.attrs["step"] for s in batches] == [0, 1, 2]
    assert [s.attrs["step"] for s in train] == [0, 1, 2]
    assert [st["step_num"] for _, _, st in sorted(steps,
                                                  key=lambda x: x[0])] \
        == [0, 1, 2]
    for s in train:
        assert any(a <= s.start and s.end <= b for a, b, _ in steps)


# ---------------------------------------------------------------- scopes
@pytest.mark.parametrize("path,want", [
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/jvp(loss)/reduce_sum", "loss"),
    ("jit(step)/transpose(jvp(lm_head))/dot_general", "lm_head"),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "mlp"),
    ("jit(pool_step)/layers/while/body/dynamic_slice", "layers"),
    ("jit(pool_step)/layers/while/body/attention/jit(_paged_attention_jit)/"
     "pallas_call", "attention"),
    ("jit(step)/transpose(jvp(loss))/mul;jit(step)/transpose(jvp(mlp))/add",
     "loss"),
    ("jit(floss)/transpose(jvp(jit(_flash_attention)))/transpose", None),
    ("", None),
])
def test_scope_of_takes_the_innermost_scope(path, want):
    assert scopes.scope_of(path) == want


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', text)


def test_scopes_claim_the_train_step_forward_and_backward(tiny):
    _, job, tcfg = tiny
    found = {}
    for path in _op_names(_train_step_text(tcfg, job)):
        backward = "transpose(" in path
        found.setdefault((scopes.scope_of(path), backward), path)
    for scope in ("mlp", "attention", "norm", "lm_head", "loss", "embed"):
        assert (scope, False) in found, scope
    for scope in ("mlp", "attention", "lm_head", "loss"):
        assert (scope, True) in found, scope
    assert ("optimizer", False) in found
    assert "transpose(jvp(" in found[("mlp", True)]


def _instructions(text: str) -> list:
    """The compiled program's lines without metadata and without the
    tables of source files, functions and stack frames. Names numbered
    ``<name>.<n>`` are renamed in the order they first appear: the numbers
    count what the compiler made before, and may differ where the
    instructions do not."""
    out, table, names = [], False, {}

    def rename(m):
        return names.setdefault(m.group(0), f"n{len(names)}")

    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            line = re.sub(r", metadata=\{[^}]*\}", "", line)
            out.append(re.sub(r"\b[A-Za-z_][\w-]*\.\d+\b", rename, line))
    return out


def _programs(c, job, tcfg) -> dict:
    """The compiled text of the decode step, a prefill chunk and the train
    step, each traced afresh."""
    from repro.serving import make_engine
    from repro.serving.roles import DecodeWorker

    cfg, span = c.cfg, 64
    model = loop_module("serve").program_model(cfg, cfg["slots"], span)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    engine = make_engine(
        "paged", model.prefill_chunk, model.decode_step_paged, params,
        model.paged_cache_init, page_size=cfg["page_size"],
        num_pages=cfg["num_pages"], prefill_chunk_tokens=16,
        slots=cfg["slots"], cache_span=span, greedy=True, seed=5)
    caches = jax.eval_shape(
        lambda: engine.cache_init(engine.num_pages, engine.page_size))
    state = DecodeWorker(engine, engine.slots, npag_max=engine.npag_max).state
    return {
        "decode": engine._pool_step.lower(
            params, caches, state, jax.random.PRNGKey(0)).compile().as_text(),
        "prefill_chunk": engine._jit_chunk.lower(
            params, caches, jnp.ones((1, 16), jnp.int32),
            jnp.zeros((1, 2), jnp.int32), jnp.int32(0)).compile().as_text(),
        "train_step": _train_step_text(tcfg, job),
    }


def test_scopes_change_only_metadata(tiny, monkeypatch):
    scoped = _programs(*tiny)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _programs(*tiny)
    for name in scoped:
        assert _instructions(scoped[name]) == _instructions(plain[name]), \
            name
        assert any(scopes.scope_of(p) for p in _op_names(scoped[name]))
        assert not any(scopes.scope_of(p) for p in _op_names(plain[name]))


# ------------------------------------------------------------- measures
@pytest.mark.parametrize("measure", MEASURES, ids=lambda f: f.__name__)
def test_measures_are_silent_without_their_input(measure):
    assert measure(None) is None
    # a program without spans or scopes, as the parent of this change
    bare = P.ProgramTrace(window=(0, 10 ** 9), devices=1, idle=[[]],
                          op_s={("jit_pool_step(1)", "copy.1"): 0.5,
                                ("jit_train_step(2)", "fusion.3"): 0.5})
    assert measure(bare) is None


@pytest.fixture(scope="module")
def recorded():
    return P.reduce(FIXTURE, run.WINDOW_SPAN)


def test_recorded_trace_has_the_spans(recorded):
    t = recorded
    names = {s.name for s in t.spans}
    assert {N.STEP, N.DECODE, N.LANE_STATE_READ, N.ADMIT, N.PREFILL_CHUNK,
            N.RETIRE, N.WAIT_FOR_ARRIVAL, N.TRAIN_STEP,
            N.TRAIN_BATCH} <= names
    decode = t.named(N.DECODE)
    assert decode and all(s.attrs["lanes"] >= 0 for s in decode)
    assert 0 < P.lanes_per_step(t) <= 8
    assert P.decode_gap_p95_ms(t) > 0


def test_recorded_idle_is_covered_by_spans(recorded):
    t = recorded
    idle = t.idle_by_span()
    total = sum(idle.values())
    busy = xplane.reduce_trace(FIXTURE, run.WINDOW_SPAN).busy_s
    assert total + busy == pytest.approx(t.window_s, rel=1e-6)
    assert idle.get(N.WAIT_FOR_ARRIVAL, 0) > 0
    assert idle.get(None, 0) < total
    share = P.idle_engine_share(t)
    engine = sum(v for k, v in idle.items()
                 if k and k.startswith("engine.") and k != N.WAIT_FOR_ARRIVAL)
    assert share == pytest.approx(100 * engine / t.window_s, rel=1e-6)


def test_recorded_ops_by_scope(recorded):
    t = recorded
    decode, train = t.module_s("pool_step"), t.module_s("train_step")
    for scope in ("attention", "kv_write", "mlp", "norm", "lm_head",
                  "sample"):
        assert decode.get(scope, 0) > 0, scope
    for scope in ("attention", "mlp", "lm_head", "loss", "optimizer"):
        assert train.get(scope, 0) > 0, scope
    for measure in (P.decode_scan_copy_share, P.optimizer_share,
                    P.head_loss_share):
        assert 0 < measure(t) < 100
    unclaimed = sum(v for k, v in decode.items()
                    if k not in P.DECODE_WORK)
    assert P.decode_scan_copy_share(t) == pytest.approx(
        100 * unclaimed / sum(decode.values()))
    assert sum(s for _, s in t.top_unclaimed("pool_step", 10 ** 6)) \
        == pytest.approx(decode.get(None, 0))
    # the ops by module hold every op that xplane's reduction counts
    assert sum(t.op_s.values()) == pytest.approx(
        sum(xplane.reduce_trace(FIXTURE, run.WINDOW_SPAN).op_s.values()))
