"""The rwkv6 training cell: the program's train step driven by its own
train loop, as :mod:`loops.train` drives granite's.

The job, the rows of every step, the recorder of what the comparison
reads, and the three numbers compared are :mod:`loops.train`'s. This
module brings what differs by model: the program's configuration from the
file (the ``rwkv6`` family, the wkv run as the chunked scan the file
names), the seeded weights of :mod:`weights_rwkv6` and the reference of
:mod:`reference_rwkv6`. With ``--trace 1`` it reads, after the window,
the train step's device seconds by named scope from the trace, which the
``wkv`` metrics take.
"""
from __future__ import annotations

import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import reference_rwkv6 as R6
import weights_rwkv6 as W6
from loops.train import (_diff_norm, _flat, _norms, Recorder, batch_at,
                         compare, feed)

# the host span run.py opens around the measured window
WINDOW_SPAN = "bench.window"
# the key of h.work holding the train step's device seconds by scope
SCOPE_S = "train_step_scope_s"


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file; refuses
    what the program cannot run as the file states it."""
    from repro.configs.base import ModelConfig, SSMConfig

    m = W6.dims(cfg)
    as_run = {"layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
              "attention_hidden_size": m["d"],
              "group_norm_eps": 1e-5 * cfg["head_size_divisor"] ** 2}
    for k, v in as_run.items():
        if cfg[k] != v:
            raise ValueError(f"the program runs {k}={v!r}; the configuration "
                             f"states {cfg[k]!r}")
    wkv = cfg["wkv"]
    return ModelConfig(
        name=cfg["name"], family="ssm", num_layers=m["L"], d_model=m["d"],
        num_heads=0, num_kv_heads=0, d_ff=m["f"], vocab_size=m["V"],
        norm="layernorm", activation="rwkv", rope="none",
        attention_kind="none", tie_embeddings=False,
        ssm=SSMConfig(kind="rwkv6", head_size=m["hs"], chunk_size=wkv["chunk"],
                      mix_rank=m["R"], decay_rank=m["Rd"],
                      group_norm_eps=cfg["group_norm_eps"]))


def program_step(cfg, job, devices, total_steps: int):
    """The program's jitted train step, mesh and shardings for the cell."""
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
        mesh=MeshConfig(tuple(cfg["mesh"]), ("data", "model")), remat=True,
        ssm_chunk=cfg["wkv"]["chunk"], ssm_factored=cfg["wkv"]["factored"],
        param_dtype=cfg["dtype"], compute_dtype=cfg["dtype"],
        learning_rate=opt["lr"], warmup_steps=opt["warmup_steps"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        opt_state_dtype="float32", opt_master=True)
    mesh = make_mesh(rcfg.mesh, devices)
    with jax.set_mesh(mesh):
        step_fn, model, optimizer = build_train_step(rcfg, total_steps)
    shapes = jax.eval_shape(model.init_params,
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    W6.check_layout(shapes, cfg, cfg["dtype"])
    pspecs = shd.param_pspecs(shapes, rcfg.model, rcfg)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    state_shard = AdamWState(step=NamedSharding(mesh, P()), master=shard,
                             m=shard, v=shard)
    return (mesh, step_fn, optimizer, shard, state_shard,
            NamedSharding(mesh, P(rcfg.mesh.axes[0], None)))


def build(cfg, job, devices):
    """The cell's mesh, shardings and jitted step (donating its state)."""
    mesh, step_fn, optimizer, shard, state_shard, bshard = program_step(
        cfg, job, devices, cfg["optimizer"]["total_steps"])
    with jax.set_mesh(mesh):
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
    return mesh, step_fn, optimizer, shard, state_shard, bshard, jit_step


def change_norms(cfg, seed, dtype, master) -> dict:
    """Per leaf, the norm of ``master`` minus the initial weights, the
    latter made again from the seed one layer's slice at a time."""
    stacked = {p: st for p, _, _, _, st in W6.leaf_specs(cfg, dtype)}
    out = {}
    for path, leaf in _flat(master).items():
        layers = range(leaf.shape[0]) if stacked[path] else [None]
        parts = [_diff_norm(leaf if i is None else leaf[i],
                            W6.layer_leaf(cfg, seed, dtype, path, i or 0))
                 for i in layers]
        out[path] = float(np.sqrt(sum(float(p) ** 2 for p in parts)))
    return out


def start(bundle, cfg: dict, job: dict, seed: int):
    """Make the weights and AdamW state from ``seed`` and drive the jitted
    step through the train loop for the job's first steps. Returns what
    the comparison reads from them, the step's state after them, and the
    warm step times."""
    from repro.runtime import train_loop

    mesh, step_fn, optimizer, shard, state_shard, bshard, jit_step = bundle
    params = W6.make_params(cfg, seed, cfg["dtype"], shard)
    opt_state = jax.jit(optimizer.init, out_shardings=state_shard)(params)
    rec = Recorder(jit_step, cfg["optimizer"]["b1"])
    warm = train_loop.run(rec, params, opt_state,
                          feed(job, seed, bshard, cfg["vocab_size"]),
                          total_steps=job["first_steps"])
    params, opt_state, _ = rec.last
    numbers = {"losses": warm.losses, "grad_norms": rec.grad_norms,
               "changes": change_norms(cfg, seed, cfg["dtype"],
                                       opt_state.master)}
    return numbers, params, opt_state, warm.step_times


def scope_seconds(trace_dir: str) -> dict:
    """The train step's device seconds in the traced window by the named
    scope that claims them (``None``: no scope)."""
    import program_trace
    import xplane

    return program_trace.reduce(xplane.find_xplane(trace_dir),
                                WINDOW_SPAN).module_s("train_step")


def run(h) -> None:
    """One run of the cell; ``h`` is the harness's run object."""
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
    if h.trace:
        t0 = time.perf_counter()
        h.work[SCOPE_S] = scope_seconds(h.trace_dir)
        h.note(f"trace reduced in {time.perf_counter() - t0:.1f} s: "
               f"{h.work[SCOPE_S]}")

    t0 = time.perf_counter()
    ref = reference_steps(cfg, job, h.seed, job["first_steps"], "f32")
    for name, value in compare(numbers, ref).items():
        h.check(name, value, cfg["check"][name])
    h.note(f"reference steps in {time.perf_counter() - t0:.1f} s")


def reference_steps(cfg, job, seed: int, steps: int, precision: str,
                    rows=batch_at) -> dict:
    """The reference's first ``steps`` steps from the same weights and
    rows (``rows`` makes a step's batch, as ``batch_at`` does): losses,
    each leaf's first clipped gradient norm, and each leaf's change over
    the steps."""
    opt = cfg["optimizer"]
    fz = R._frozen(cfg)
    params = R6.float32_params(cfg, seed)
    state, losses = None, []
    grad_norms = raw_grads = None
    for s in range(steps):
        b = rows(job, seed, s, cfg["vocab_size"])
        value, grads = R6.loss_and_grad(params, jnp.asarray(b["tokens"]),
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
