"""GPipe-style pipeline parallelism over a mesh axis (the paper's
Graphcore/IPU execution model, §III.C / Fig. 11c).

Stages map to slices of the stacked layer parameters (the leading L dim is
sharded over the pipe axis), microbatches flow stage-to-stage with
collective_permute, and uneven layer->stage assignments are first-class —
the Tier-2 benchmark reproduces the paper's finding that throughput is
governed by the most-loaded stage.

This is a correctness/benchmark-grade schedule (GPipe with output
collection on the last stage); production would add 1F1B and weight
sharding within stages, noted in DESIGN.md.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P


def stage_layout(num_layers: int, stage_layers: Sequence[int]):
    """Map layer index -> (stage, slot) with per-stage padding to max."""
    if sum(stage_layers) != num_layers:
        raise ValueError(
            f"stage_layers {tuple(stage_layers)} must sum to "
            f"num_layers={num_layers}")
    lmax = max(stage_layers)
    layer_of = []
    for s, n in enumerate(stage_layers):
        for j in range(n):
            layer_of.append((s, j))
    return lmax, layer_of


def stack_stages(stacked_params, stage_layers: Sequence[int]):
    """(L, ...) param leaves -> ((S, Lmax, ...), valid_mask (S, Lmax))."""
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    S = len(stage_layers)
    lmax, _ = stage_layout(L, stage_layers)
    bounds = np.cumsum([0] + list(stage_layers))
    mask = np.zeros((S, lmax), bool)
    for s, n in enumerate(stage_layers):
        mask[s, :n] = True

    def per_leaf(x):
        out = jnp.zeros((S, lmax) + x.shape[1:], x.dtype)
        for s in range(S):
            sl = x[bounds[s]:bounds[s + 1]]
            out = out.at[s, : stage_layers[s]].set(sl)
        return out

    return jax.tree.map(per_leaf, stacked_params), jnp.asarray(mask)


def pipeline_forward(staged_params, valid_mask, mbs, layer_fn,
                     *, axis: str = "model"):
    """GPipe forward. mbs: (M, mb, S_seq, d) microbatch activations
    (replicated over the pipe axis); staged_params leaves: (S, Lmax, ...)
    sharded P(axis, ...). Returns (M, mb, S_seq, d) final-stage outputs.

    layer_fn(x, p_layer) -> x.
    """
    M = mbs.shape[0]

    def local(mbs_l, params_l, mask_l):
        # params_l leaves: (1, Lmax, ...) local stage slice
        params_l = jax.tree.map(lambda x: x[0], params_l)
        mask_l = mask_l[0]
        s = jax.lax.axis_index(axis)
        S = axis_size(axis)

        def run_stage(x):
            def body(c, xs):
                p, valid = xs
                y = layer_fn(c, p)
                return jnp.where(valid, y, c), None
            y, _ = jax.lax.scan(body, x, (params_l, mask_l))
            return y

        zero = jnp.zeros_like(mbs_l[0])
        outs0 = jnp.zeros_like(mbs_l)

        def step(t, carry):
            act, outs = carry
            mb_idx = t - s
            active = (mb_idx >= 0) & (mb_idx < M)
            safe = jnp.clip(mb_idx, 0, M - 1)
            x_in = jnp.where(s == 0, mbs_l[safe], act)
            y = run_stage(x_in)
            y = jnp.where(active, y, zero)
            outs = jnp.where(
                active & (s == S - 1),
                outs.at[safe].set(y), outs)
            # hand activation to the next stage
            y_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(S - 1)])
            return (y_next, outs)

        S_static = valid_mask.shape[0]
        (_, outs) = jax.lax.fori_loop(
            0, M + S_static - 1, step, (zero, outs0))
        # only the last stage holds nonzero outputs; psum broadcasts them so
        # out_specs can be replicated over the pipe axis.
        return jax.lax.psum(outs, axis)

    return shard_map(
        local,
        in_specs=(P(None), jax.tree.map(lambda _: P(axis), staged_params),
                  P(axis)),
        out_specs=P(None),
        check_vma=False,
    )(mbs, staged_params, valid_mask)


def pipeline_step_time(stage_layers: Sequence[int], per_layer_s: float,
                       n_microbatches: int) -> float:
    """Analytic GPipe step time: (M + S - 1) x slowest stage."""
    return (n_microbatches + len(stage_layers) - 1) * \
        max(stage_layers) * per_layer_s


def stage_utilization(stage_layers: Sequence[int]) -> List[float]:
    """Useful-layer fraction per stage under this module's padded scan
    schedule: every stage executes Lmax layer slots and masks the invalid
    ones, so stage s does n_s/Lmax useful work — the per-stage Eq. 1
    allocation ratio of the pipeline."""
    if not stage_layers:
        return []
    lmax = max(stage_layers)
    return [n / lmax for n in stage_layers]


def pipeline_allocation(stage_layers: Sequence[int]) -> float:
    """Eq. 2 over pipeline stages. Every stage is busy for the same wall
    time under the padded schedule (runtime weights are equal), so the
    runtime-weighted allocation collapses to the mean per-stage useful
    fraction: mean(n_s) / Lmax. 1.0 = perfectly even split."""
    util = stage_utilization(stage_layers)
    return sum(util) / len(util) if util else 0.0
