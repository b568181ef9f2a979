"""Whole runs of the training cells at a tiny size on the CPU, past the
harness's look for a chip: a sound run is correct, and a run whose timed
step is broken underneath is not. The control, the reference at float8
put in the program's place, reads far above the program and fails the
cell's limits."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from tiny_cells import cells, execute, loop_module, make_root

CELLS = cells("train")


def _unchanged(step):
    def bad(params, opt_state, batch):
        return (params, opt_state, step(params, opt_state, batch)[2])
    return bad


def _half_batch(step):
    def bad(params, opt_state, batch):
        half = {k: jnp.concatenate([v[:v.shape[0] // 2]] * 2)
                for k, v in batch.items()}
        return step(params, opt_state, half)
    return bad


def _labels_altered(step):
    def bad(params, opt_state, batch):
        return step(params, opt_state,
                    dict(batch, labels=jnp.roll(batch["labels"], 1, 1)))
    return bad


FAULTS = {"sound": None, "state_unchanged": _unchanged,
          "half_batch": _half_batch, "labels_altered": _labels_altered}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", list(FAULTS))
def test_run_is_correct_only_when_sound(root, cell, fault, monkeypatch):
    train = loop_module("train")
    if FAULTS[fault] is not None:
        make = train.program_step

        def broken(*a, **k):
            out = list(make(*a, **k))
            out[1] = FAULTS[fault](out[1])
            return tuple(out)
        monkeypatch.setattr(train, "program_step", broken)
    result = execute(root, cell, seconds=0.5)
    assert result["attempted"] >= 2
    assert result["correct"] is (fault == "sound"), result["checks"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_far_above_the_program(root, cell, monkeypatch):
    import jax

    import run

    c = run.resolve(cell, root)
    train = loop_module("train")
    bundle = train.build(c.cfg, c.traffic, jax.devices()[:1])
    with jax.set_mesh(bundle[0]):
        numbers = train.start(bundle, c.cfg, c.traffic, 11)[0]
    steps = c.traffic["first_steps"]
    ref = train.reference_steps(c.cfg, c.traffic, 11, steps, "f32")
    low = train.reference_steps(c.cfg, c.traffic, 11, steps, "fp8")
    program, control = train.compare(numbers, ref), train.compare(low, ref)
    assert any(control[k] > 3 * program[k] for k in program)

    # the control put in the program's place fails the cell's own limits
    first = train.start

    def control_start(bundle, cfg, job, seed):
        _, *rest = first(bundle, cfg, job, seed)
        return (train.reference_steps(cfg, job, seed, job["first_steps"],
                                      "fp8"), *rest)
    monkeypatch.setattr(train, "start", control_start)
    result = execute(root, cell, seed=11, seconds=0.5)
    assert result["correct"] is False, result["checks"]
