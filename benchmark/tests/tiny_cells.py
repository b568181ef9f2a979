"""The benchmark's cells at a size the CPU runs in seconds: the same cell
names and traffic shapes as ``BENCHMARK.json``, with the model and the
traffic shrunk, and the limits of the comparison set for that size.
Tests use them to drive whole runs on the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

MODEL = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=500,
             attention_multiplier=32 ** -0.5)
SERVE = dict(slots=4, num_pages=40, page_size=16, prefill_chunk=32)
TRAFFIC = {
    "serve": dict(rate_per_s=4.0, prompt_lengths=[16, 40, 64],
                  prompt_weights=[0.5, 0.3, 0.2], check_tokens=20),
    "train": dict(batch=2, seq=64),
}
OUTPUT = {"median": 6, "sigma": 0.5, "min": 3, "max": 12}
# Limits for the tiny model, set as the cells' own are at their real
# sizes: above what sound tiny runs read (logit gap at most 0.0025; loss,
# gradient and change gaps at most 6e-4, 1.2e-3 and 3.6e-4 over three
# seeds) and below the control's or a fault's readings (logit gap 0.08
# for the float8 control and 0.45 for an unwritten cache; gaps of 2e-2
# and more for half a batch, 1 for a state left unchanged).
CHECK = {"logit_gap": 0.02, "loss_gap": 5e-3, "grad_norm_gap": 5e-3,
         "update_norm_gap": 3e-3}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def make_root(tmp: Path) -> Path:
    """A checkout-like directory holding the tiny cells."""
    s = spec()
    (tmp / "benchmark" / "configs").mkdir(parents=True)
    (tmp / "benchmark" / "traffic").mkdir(parents=True)
    for conf in s["configs"]:
        cfg = json.loads((ROOT / conf["file"]).read_text())
        cfg.update(MODEL)
        if "slots" in cfg:
            cfg.update(SERVE)
        cfg["check"] = {k: CHECK[k] for k in cfg["check"]}
        (tmp / conf["file"]).write_text(json.dumps(cfg))
    for cell in s["workloads"]:
        name = cell["traffic"]
        traffic = json.loads(
            (BENCH / "traffic" / f"{name}.json").read_text())
        traffic.update(TRAFFIC[traffic["loop"]])
        if "output" in traffic:
            traffic["output"].update(OUTPUT)
        (tmp / "benchmark" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
    (tmp / "BENCHMARK.json").write_text(json.dumps(s))
    return tmp


def execute(root: Path, workload: str, seed: int = 7,
            seconds: float = 1.0) -> dict:
    """One run of a tiny cell on the CPU, past the harness's look for a
    chip."""
    import jax

    import run

    return run.execute(workload, seed, seconds, False,
                       devices=jax.devices()[:1], peaks=PEAKS, root=root)


def loop_module(kind: str):
    import run

    return run.load_module(BENCH / "loops" / f"{kind}.py")


def cells(kind: str) -> list:
    """Names of the cells whose traffic runs the ``kind`` loop."""
    out = []
    for cell in spec()["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                       .read_text())
        if t["loop"] == kind:
            out.append(cell["name"])
    return out
