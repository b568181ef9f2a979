"""Whole runs of the serving cells at a tiny size on the CPU, past the
harness's look for a chip: a sound run is correct, and a run whose timed
path is broken underneath is not. The control, the reference at float8
put in the program's place, reads far above the program and fails the
cell's limit."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import pytest

from tiny_cells import cells, execute, loop_module, make_root

CELLS = cells("serve")


def _token_altered(model):
    def decode(params, caches, token, pos, tables):
        logits, caches = model.decode_step_paged(params, caches, token, pos,
                                                 tables)
        return jnp.roll(logits, 1, axis=-1), caches
    return dataclasses.replace(model, decode_step_paged=decode)


def _cache_unwritten(model):
    def decode(params, caches, token, pos, tables):
        logits, _ = model.decode_step_paged(params, caches, token, pos,
                                            tables)
        return logits, caches
    return dataclasses.replace(model, decode_step_paged=decode)


def _half_lanes(model):
    """The decode step computes the second half of its lanes and hands the
    first half the second half's logits."""
    def decode(params, caches, token, pos, tables):
        logits, caches = model.decode_step_paged(params, caches, token, pos,
                                                 tables)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[half:]] * 2), caches
    return dataclasses.replace(model, decode_step_paged=decode)


FAULTS = {"sound": None, "token_altered": _token_altered,
          "state_unchanged": _cache_unwritten, "half_batch": _half_lanes}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", list(FAULTS))
def test_run_is_correct_only_when_sound(root, cell, fault, monkeypatch):
    import run

    serve = loop_module("serve")
    if FAULTS[fault] is not None:
        build = serve.program_model
        monkeypatch.setattr(serve, "program_model",
                            lambda *a: FAULTS[fault](build(*a)))
    result = execute(root, cell)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is (fault == "sound"), result["checks"]
    assert list(result)[-1] == "checks"
    want = {m["name"] for m in run.resolve(cell, root).end_to_end}
    assert set(result["metrics"]) == want and "setup_s" in want


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_far_above_the_program(root, cell, monkeypatch):
    import run

    c = run.resolve(cell, root)
    serve = loop_module("serve")
    engine, clock = serve.build(c.cfg, c.traffic, 11)
    reqs = serve.generate(c.traffic, 11, 1.0, c.cfg["vocab_size"])
    clock.arm()
    w = serve.summarize(reqs, engine.run(serve._requests(reqs)), clock)
    sample = serve.choose_sample(reqs, w["served"], 11, 10 ** 6)
    args = (c.cfg, 11, reqs, w["served"], sample)
    program = serve.max_gap(*args, "f32")
    control = serve.max_gap(*args, "fp8")
    assert control > 3 * program

    # the control put in the program's place fails the cell's own limit
    first = serve.max_gap
    monkeypatch.setattr(serve, "max_gap", lambda *a: first(*a[:-1], "fp8"))
    result = execute(root, cell, seed=11)
    assert result["correct"] is False, result["checks"]
