"""Record the device trace that the span and scope tests read.

    python benchmark/tests/record_spans_trace.py OUT_DIR

Runs on one TPU, under the JAX profiler and inside a host span named as
the benchmark's window: the serving cell's paged engine over a dozen
requests whose arrivals leave it waiting, then four steps of the training
cell's train loop. Both cells' configurations are cut to 2 layers of
width 512 (4 query heads of 128), so that the trace stays small; the
programs are the cells' own, with their spans and named scopes. Copies
the ``.xplane.pb`` to ``OUT_DIR/spans.xplane.pb`` and prints what the
tests read from it.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

import jax

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

SMALL = dict(hidden_size=512, intermediate_size=1024, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=1024)
SERVE = dict(slots=8, num_pages=128, page_size=16, prefill_chunk=64)
TRAFFIC = dict(rate_per_s=20.0, prompt_lengths=[32, 96],
               prompt_weights=[0.5, 0.5],
               output={"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 16})
JOB = dict(batch=2, seq=512, first_steps=1)
SEED = 3000001700


def config(name: str, **extra) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(SMALL, **extra)
    return cfg


def without_plane(data: bytes, name: str) -> bytes:
    """The serialized XSpace ``data`` less its plane called ``name`` (the
    HLO of every program run, which no reduction reads)."""
    import program_trace as P

    b, out, i = memoryview(data), bytearray(), 0
    for num, v in P._fields(b, 0, len(b)):
        if not (num == 1 and name == next(
                (P._text(b, pv) for pn, pv in P._fields(b, *v) if pn == 2),
                "")):
            out += b[i:v[1]]
        i = v[1]
    return bytes(out)


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_spans_trace: needs a TPU", file=sys.stderr)
        return 1
    import program_trace
    import run
    from repro.runtime import train_loop

    serve = run.load_module(BENCH / "loops" / "serve.py")
    train = run.load_module(BENCH / "loops" / "train.py")
    scfg = config("granite-3-8b.serve", **SERVE)
    traffic = dict(json.loads((BENCH / "traffic" / "chat.json").read_text()),
                   **TRAFFIC)
    engine, _ = serve.build(scfg, traffic, SEED)
    reqs = serve._requests(serve.generate(traffic, SEED, 0.6,
                                          scfg["vocab_size"]))
    tcfg = config("granite-3-8b.train")
    bundle = train.build(tcfg, JOB, jax.devices()[:1])
    mesh, bshard, jit_step = bundle[0], bundle[5], bundle[6]
    with jax.set_mesh(mesh):
        _, params, opt_state, _ = train.start(bundle, tcfg, JOB, SEED)

    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(run.WINDOW_SPAN):
        report = engine.run(reqs)
        with jax.set_mesh(mesh):
            res = train_loop.run(
                jit_step, params, opt_state,
                train.feed(JOB, SEED, bshard, tcfg["vocab_size"], 1),
                total_steps=4)
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    out = Path(out_dir) / "spans.xplane.pb"
    out.write_bytes(without_plane(Path(path).read_bytes(), "/host:metadata"))
    shutil.rmtree(tmp)
    print("requests", report.completed, "decode steps", report.decode_steps,
          "train losses", res.losses)

    t = program_trace.reduce(str(out), run.WINDOW_SPAN)
    names = sorted({s.name for s in t.spans})
    print("spans", {n: len([s for s in t.spans if s.name == n])
                    for n in names})
    for s in t.spans[:12]:
        print("  ", s.name, s.start, s.end, s.attrs)
    print("modules", sorted({k[0] for k in t.op_s}))
    for module in ("pool_step", "train_step"):
        print(module, t.module_s(module))
        print("  unclaimed", t.top_unclaimed(module, 5))
    print("idle by span", t.idle_by_span())
    print("six", program_trace.decode_gap_p95_ms(t),
          program_trace.lanes_per_step(t), program_trace.idle_engine_share(t),
          program_trace.decode_scan_copy_share(t),
          program_trace.optimizer_share(t), program_trace.head_loss_share(t))
    print("size", out.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
