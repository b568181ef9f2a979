"""Pure-JAX AdamW with f32 master weights, global-norm clipping and a
warmup-cosine schedule. Optimizer state shards exactly like the params
(ZeRO — see parallel/sharding.py), so the update is fully local.

Memory policy knobs (needed to fit the 400-480B MoE archs on v5e-256,
where f32 AdamW state alone is 22 GB/chip):
* ``state_dtype``  — 'float32' | 'bfloat16' | 'int8': m/v storage. int8 is
  blockwise-quantized (16-elem blocks along the last dim with f32 scales,
  ~1.25 B/elem; blocks never straddle a shard boundary), in the spirit of
  8-bit Adam [arXiv:2110.02861].
* ``use_master``   — keep an f32 master copy (True) or update the bf16
  params directly with f32 round-trip math (False).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.runtime import trace_names as N

_QBLOCK = 16  # along the last dim: small enough to stay inside any shard


class Q8(NamedTuple):
    """Blockwise-int8 tensor: q keeps the source shape (and sharding);
    scale has shape[:-1] + (last/_QBLOCK,)."""
    q: jnp.ndarray
    scale: jnp.ndarray


def quantizable(shape) -> bool:
    return len(shape) >= 2 and shape[-1] % _QBLOCK == 0


def _q8_encode(x: jnp.ndarray):
    if not quantizable(x.shape):
        return x.astype(jnp.float32)
    blocks = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, _QBLOCK)
    scale = jnp.maximum(jnp.abs(blocks).max(axis=-1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(blocks / scale[..., None]), -127, 127)
    return Q8(q=q.astype(jnp.int8).reshape(x.shape),
              scale=scale.astype(jnp.float32))


def _q8_decode(enc) -> jnp.ndarray:
    if not isinstance(enc, Q8):
        return enc.astype(jnp.float32)
    blocks = enc.q.astype(jnp.float32).reshape(
        *enc.q.shape[:-1], -1, _QBLOCK)
    return (blocks * enc.scale[..., None]).reshape(enc.q.shape)


def _is_q8(leaf) -> bool:
    return isinstance(leaf, Q8)


class AdamWState(NamedTuple):
    step: jnp.ndarray
    master: dict          # f32 master copy of params ({} if use_master=False)
    m: dict
    v: dict


def warmup_cosine(base_lr: float, warmup: int, total: int,
                  final_frac: float = 0.1) -> Callable:
    def lr(step):
        step = step.astype(jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, base_lr * cos)
    return lr


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


@dataclass(frozen=True)
class AdamW:
    lr_fn: Callable
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    state_dtype: str = "float32"   # float32 | bfloat16 | int8
    use_master: bool = True

    def _enc(self, x32: jnp.ndarray):
        if self.state_dtype == "int8":
            return _q8_encode(x32)
        return x32.astype(jnp.dtype(self.state_dtype))

    def _dec(self, enc) -> jnp.ndarray:
        if self.state_dtype == "int8":
            return _q8_decode(enc)
        return enc.astype(jnp.float32)

    def init(self, params) -> AdamWState:
        def zeros():   # fresh buffers each call: m/v/master must not alias
            return jax.tree.map(
                lambda x: self._enc(jnp.zeros(x.shape, jnp.float32)), params)
        # copy=True: an f32 param must not alias its master (both get donated)
        master = jax.tree.map(
            lambda x: jnp.array(x, dtype=jnp.float32, copy=True), params) \
            if self.use_master else {}
        return AdamWState(step=jnp.zeros((), jnp.int32), master=master,
                          m=zeros(), v=zeros())

    def update(self, grads, state: AdamWState, params):
        with jax.named_scope(N.OPTIMIZER):
            return self._update(grads, state, params)

    def _update(self, grads, state: AdamWState, params):
        step = state.step + 1
        lr = self.lr_fn(step)
        gnorm = global_norm(grads)
        clip = jnp.minimum(1.0, self.grad_clip / jnp.maximum(gnorm, 1e-8)) \
            if self.grad_clip else 1.0

        def upd(g, m_enc, v_enc, master):
            g = g.astype(jnp.float32) * clip
            m = self.b1 * self._dec(m_enc) + (1 - self.b1) * g
            v = self.b2 * self._dec(v_enc) + (1 - self.b2) * jnp.square(g)
            mhat = m / (1 - self.b1 ** step.astype(jnp.float32))
            vhat = v / (1 - self.b2 ** step.astype(jnp.float32))
            wd = self.weight_decay if master.ndim >= 2 else 0.0
            master = master - lr * (mhat / (jnp.sqrt(vhat) + self.eps)
                                    + wd * master)
            return self._enc(m), self._enc(v), master

        is_leaf = _is_q8
        flat_g, treedef = jax.tree.flatten(grads)
        flat_m = jax.tree.leaves(state.m, is_leaf=is_leaf)
        flat_v = jax.tree.leaves(state.v, is_leaf=is_leaf)
        if self.use_master:
            flat_ma = jax.tree.leaves(state.master)
        else:
            flat_ma = [p.astype(jnp.float32) for p in jax.tree.leaves(params)]
        outs = [upd(g, m, v, ma)
                for g, m, v, ma in zip(flat_g, flat_m, flat_v, flat_ma)]
        new_m = jax.tree.unflatten(treedef, [o[0] for o in outs])
        new_v = jax.tree.unflatten(treedef, [o[1] for o in outs])
        new_master_flat = [o[2] for o in outs]
        new_params = jax.tree.unflatten(treedef, [
            ma.astype(p.dtype)
            for ma, p in zip(new_master_flat, jax.tree.leaves(params))])
        new_state = AdamWState(
            step=step,
            master=(jax.tree.unflatten(treedef, new_master_flat)
                    if self.use_master else {}),
            m=new_m, v=new_v)
        metrics = {"grad_norm": gnorm, "lr": lr}
        return new_params, new_state, metrics
