"""Training cells: the program's train step driven by its own train loop.

The traffic file of a training cell is the job: batch rows and their
length. Every row of every step is distinct, drawn from the seed and the
step. Set-up builds one object, the jitted step from
``runtime.steps.build_train_step`` with its parameters (made from the
seed, in the configuration's dtype) and AdamW state, and drives it
through ``runtime.train_loop.run`` for its first steps; the window then
hands that same step and state to ``train_loop.run`` again for as many
steps as fill ``--seconds`` at the warm step time.

After the window the plain reference takes the first steps from the same
weights and rows, in float32, and three numbers are compared: each step's
loss, the norm of each leaf's first gradient as the optimizer got it
(read from its first moment after one step), and the norm of each leaf's
change over the first steps (read from the float32 master weights).
"""
from __future__ import annotations

import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W
from program import program_config

LOSS, GRAD, CHANGE = "loss_gap", "grad_norm_gap", "update_norm_gap"


def batch_at(job: dict, seed: int, step: int, vocab: int) -> dict:
    """Rows of ``step``: uniform token ids, labels the ids one ahead."""
    rng = np.random.default_rng([seed, step])
    rows = rng.integers(0, vocab, (job["batch"], job["seq"] + 1),
                        dtype=np.int32)
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


def _flat(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def change_norms(cfg, seed, dtype, master) -> dict:
    """Per leaf, the norm of ``master`` minus the initial weights, the
    latter made again from the seed one leaf at a time."""
    out = {}
    for path, leaf in _flat(master).items():
        spec = {p: st for p, _, _, _, st in W.leaf_specs(cfg, dtype)}[path]
        if spec:
            parts = [_diff_norm(leaf[i], W.layer_leaf(cfg, seed, dtype,
                                                      path, i))
                     for i in range(leaf.shape[0])]
            out[path] = float(np.sqrt(sum(float(p) ** 2 for p in parts)))
        else:
            out[path] = float(_diff_norm(leaf, W.layer_leaf(
                cfg, seed, dtype, path, 0)))
    return out


class Recorder:
    """The jitted step as the train loop calls it, reading what the
    comparison needs from the first steps' outputs before the next step
    consumes them: the first moments after step 1 and the state after
    the last."""

    def __init__(self, step, b1: float):
        self.step, self.b1, self.calls = step, b1, 0
        self.grad_norms = None
        self.last = None

    def __call__(self, params, opt_state, batch):
        out = self.step(params, opt_state, batch)
        self.calls += 1
        if self.calls == 1:
            m = _flat(out[1].m)
            self.grad_norms = {p: float(n) / (1 - self.b1)
                               for p, n in _flat(_norms(m)).items()}
        self.last = out
        return out


def program_step(cfg, job, devices, total_steps: int):
    """The program's jitted train step, mesh and shardings for a cell."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import MeshConfig, RunConfig, ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamWState
    from repro.parallel import sharding as shd
    from repro.runtime.steps import build_train_step

    opt = cfg["optimizer"]
    rcfg = RunConfig(
        model=program_config(cfg),
        shape=ShapeConfig("train", "train", job["seq"], job["batch"]),
        mesh=MeshConfig(tuple(cfg["mesh"]), ("data", "model")),
        attention_backend=cfg["attention_backend"], remat=True,
        param_dtype=cfg["dtype"], compute_dtype=cfg["dtype"],
        learning_rate=opt["lr"], warmup_steps=opt["warmup_steps"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        opt_state_dtype="float32", opt_master=True)
    mesh = make_mesh(rcfg.mesh, devices)
    with jax.set_mesh(mesh):
        step_fn, model, optimizer = build_train_step(rcfg, total_steps)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    shapes = jax.eval_shape(model.init_params, key)
    W.check_layout(shapes, cfg, cfg["dtype"])
    pspecs = shd.param_pspecs(shapes, rcfg.model, rcfg)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    state_shard = AdamWState(step=NamedSharding(mesh, P()), master=shard,
                             m=shard, v=shard)
    return (mesh, step_fn, optimizer, shard, state_shard,
            NamedSharding(mesh, P(rcfg.mesh.axes[0], None)))


def start(bundle, cfg: dict, job: dict, seed: int):
    """Make the weights and AdamW state from ``seed`` and drive the jitted
    step through the train loop for the job's first steps. Returns what
    the comparison reads from them, the step's state after them, and the
    warm step times."""
    from repro.runtime import train_loop

    mesh, step_fn, optimizer, shard, state_shard, bshard, jit_step = bundle
    opt = cfg["optimizer"]
    params = W.make_params(cfg, seed, cfg["dtype"], shard)
    opt_state = jax.jit(optimizer.init, out_shardings=state_shard)(params)
    rec = Recorder(jit_step, opt["b1"])
    warm = train_loop.run(rec, params, opt_state,
                          feed(job, seed, bshard, cfg["vocab_size"]),
                          total_steps=job["first_steps"])
    params, opt_state, _ = rec.last
    numbers = {"losses": warm.losses, "grad_norms": rec.grad_norms,
               "changes": change_norms(cfg, seed, cfg["dtype"],
                                       opt_state.master)}
    return numbers, params, opt_state, warm.step_times


def feed(job, seed, sharding, vocab, offset: int = 0):
    """The train loop's ``data_iter_fn``: step -> the rows on the device."""
    def data(step):
        b = batch_at(job, seed, offset + step, vocab)
        return {k: jax.device_put(v, sharding) for k, v in b.items()}
    return data


def build(cfg, job, devices):
    """The cell's mesh, shardings and jitted step (donating its state)."""
    mesh, step_fn, optimizer, shard, state_shard, bshard = program_step(
        cfg, job, devices, cfg["optimizer"]["total_steps"])
    with jax.set_mesh(mesh):
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
    return mesh, step_fn, optimizer, shard, state_shard, bshard, jit_step


def run(h) -> None:
    """One run of a training cell; ``h`` is the harness's run object."""
    from repro.runtime import train_loop

    cfg, job = h.cfg, h.traffic
    bundle = build(cfg, job, h.devices)
    h.note(f"step built at {time.perf_counter() - h.t_start:.1f} s")
    mesh, bshard, jit_step = bundle[0], bundle[5], bundle[6]
    with jax.set_mesh(mesh):
        numbers, params, opt_state, times = start(bundle, cfg, job, h.seed)
        h.note(f"first steps read at {time.perf_counter() - h.t_start:.1f} s")
        steps = max(2, round(h.seconds / statistics.mean(times[1:])))
        with h.window():
            res = train_loop.run(jit_step, params, opt_state,
                                 feed(job, h.seed, bshard, cfg["vocab_size"],
                                      job["first_steps"]),
                                 total_steps=steps)
    h.read_memory_peak()
    tokens = job["batch"] * job["seq"]
    h.attempted, h.failed = steps, steps - len(res.losses)
    h.work.update(train_steps=len(res.losses),
                  train_tokens=tokens * len(res.losses),
                  seq=job["seq"], batch=job["batch"], chips=len(h.devices))
    h.note(f"first losses {numbers['losses']}, warm step times {times}; "
           f"window {steps} steps, retries {res.retries}, last loss "
           f"{res.losses[-1] if res.losses else None}")
    del res, params, opt_state, bundle, jit_step
    gc.collect()

    t0 = time.perf_counter()
    ref = reference_steps(cfg, job, h.seed, job["first_steps"], "f32")
    for name, value in compare(numbers, ref).items():
        h.check(name, value, cfg["check"][name])
    h.note(f"reference steps in {time.perf_counter() - t0:.1f} s")


def reference_steps(cfg, job, seed: int, steps: int, precision: str,
                    rows=batch_at) -> dict:
    """The reference's first ``steps`` steps from the same weights and
    rows (``rows`` makes a step's batch, as :func:`batch_at` does):
    losses, each leaf's first clipped gradient norm, and each leaf's
    change over the steps."""
    opt = cfg["optimizer"]
    vocab = cfg["vocab_size"]
    fz = R._frozen(cfg)
    params = jax.jit(lambda p: jax.tree.map(
        lambda x: x.astype(jnp.float32), p))(
        W.make_params(cfg, seed, cfg["dtype"]))
    state, losses = None, []
    grad_norms = raw_grads = None
    for s in range(steps):
        b = rows(job, seed, s, vocab)
        value, grads = R.loss_and_grad(params, jnp.asarray(b["tokens"]),
                                       jnp.asarray(b["labels"]), fz,
                                       precision)
        losses.append(float(value))
        params, state, clip = R.adamw(params, grads, state, s + 1, opt)
        if s == 0:
            raw_grads = {p: float(n) for p, n in
                         _flat(_norms(grads)).items()}
            grad_norms = {p: n * clip for p, n in raw_grads.items()}
        del grads
    changes = change_norms(cfg, seed, cfg["dtype"], params)
    return {"losses": losses, "grad_norms": grad_norms, "changes": changes,
            "raw_grads": raw_grads}


def _leaf_gap(got: dict, want: dict, floor_from: dict, skip=()) -> float:
    """Largest |got - want| over leaves, each against the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(floor_from.values())
    return max(abs(got[p] - want[p]) / max(want[p], med)
               for p in want if p not in skip)


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers compared. Leaves whose reference gradient is under
    a thousandth of the median leaf's are left out of the change: Adam
    moves them by round-off alone."""
    med = statistics.median(ref["raw_grads"].values())
    still = {p for p, n in ref["raw_grads"].items() if n < 1e-3 * med}
    return {
        LOSS: max(abs(a - b) for a, b in zip(prog["losses"],
                                             ref["losses"])),
        GRAD: _leaf_gap(prog["grad_norms"], ref["grad_norms"],
                        ref["grad_norms"]),
        CHANGE: _leaf_gap(prog["changes"], ref["changes"], ref["changes"],
                          still),
    }
