"""The Pallas kernels compiled by the TPU compiler for a described v5e chip,
at the widths of the configurations that run them.

Interpret mode runs block shapes and VMEM footprints that Mosaic refuses;
this compile is the check that the chip would accept each kernel. No chip
is needed: the topology is described, not attached. It is described inside
a fixture, never while a module is imported, because only one process at a
time may load the TPU library.
"""
import dataclasses
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops, tuning
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.paged_attention import paged_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.rwkv6 import wkv6_fwd


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from repro.configs import MeshConfig
    from repro.launch.mesh import make_mesh
    return make_mesh(MeshConfig((2, 2), ("data", "model")), topo.devices)


@pytest.fixture(scope="module")
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one; keep the cache
    out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_shapes(dtype=jnp.bfloat16):
    cfg = get_arch("granite-3-8b")
    B, S, D = 1, 2048, cfg.resolved_head_dim
    q = (B, S, cfg.num_heads, D)
    kv = (B, S, cfg.num_kv_heads, D)
    return dict(q=(q, dtype), k=(kv, dtype), v=(kv, dtype),
                o=(q, dtype), do=(q, dtype),
                lse=((B, cfg.num_heads, S, 1), jnp.float32))


def _case(name):
    """(kernel function, {arg: (shape, dtype)}) at real widths."""
    granite = get_arch("granite-3-8b")
    if name == "flash_fwd_lse":
        sh = _flash_shapes()
        return (lambda q, k, v: flash_attention_fwd(
            q, k, v, causal=True, return_lse=True),
            {a: sh[a] for a in ("q", "k", "v")})
    if name == "flash_bwd":
        sh = _flash_shapes()
        return (lambda q, k, v, o, lse, do: flash_attention_bwd(
            q, k, v, o, lse, do, causal=True),
            {a: sh[a] for a in ("q", "k", "v", "o", "lse", "do")})
    if name == "paged_decode":
        B, ps, npag = 8, 16, 36
        P = B * npag + 1
        D = granite.resolved_head_dim
        pool = ((P, ps, granite.num_kv_heads, D), jnp.float32)
        return (lambda q, k, v, bt, ln: paged_attention_fwd(
            q, k, v, bt, ln),
            dict(q=((B, 1, granite.num_heads, D), jnp.float32), k=pool,
                 v=pool, bt=((B, npag), jnp.int32), ln=((B,), jnp.int32)))
    if name == "wkv6":
        rwkv = get_arch("rwkv6-3b")
        K = rwkv.ssm.head_size
        H = rwkv.d_model // K
        seq = ((1, 2048, H, K), jnp.bfloat16)
        return (lambda q, k, v, ld, u: wkv6_fwd(q, k, v, ld, u),
                dict(q=seq, k=seq, v=seq, ld=((1, 2048, H, K), jnp.float32),
                     u=((H, K), jnp.float32)))
    if name == "rmsnorm":
        d = granite.d_model
        return (lambda x, s: rmsnorm_fwd(x, s),
                dict(x=((4096, d), jnp.bfloat16), s=((d,), jnp.float32)))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["flash_fwd_lse", "flash_bwd",
                                  "paged_decode", "wkv6", "rmsnorm"])
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache,
                                 monkeypatch, tmp_path):
    # tiles from the kernels' defaults, not from a tuned-cache file
    monkeypatch.setenv(tuning.ENV_VAR, str(tmp_path))
    tuning.clear_cache()
    fn, specs = _case(name)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs.values()]
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        tuning.clear_cache()
    assert "tpu_custom_call" in compiled.as_text()


# the paged serving step at granite's widths in bf16: 64 lanes, 16-token
# pages, 7800 pages, 512-token prefill chunks, 256 live pages a row
PAGED = dict(slots=64, page_size=16, num_pages=7800, chunk=512, npag=256)
_POOL_OPS = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def _paged_step(name, one_chip, layers):
    """(compiled program, one layer's pool shape) of the paged decode step
    or one prefill chunk at reduced depth, the pools donated."""
    from repro.models.model import build
    from repro.models.transformer import Runtime

    cfg = dataclasses.replace(get_arch("granite-3-8b"), num_layers=layers)
    model = build(cfg, Runtime(attention_backend="pallas"),
                  param_dtype=jnp.bfloat16)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = shaped(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    pools = shaped(jax.eval_shape(lambda: model.paged_cache_init(
        PAGED["num_pages"], PAGED["page_size"])))
    B, npag = PAGED["slots"], PAGED["npag"]
    if name == "decode":
        fn, args = model.decode_step_paged, (i32(B, 1), i32(B), i32(B, npag))
    else:
        fn, args = model.prefill_chunk, (i32(1, PAGED["chunk"]),
                                         i32(1, npag), i32())
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, *args).compile()
    return compiled, pools["layers"]["k"].shape[1:]


@pytest.mark.parametrize("name", ["decode", "prefill_chunk"])
def test_paged_step_updates_the_pool_in_place(name, one_chip,
                                              no_compile_cache, monkeypatch,
                                              tmp_path):
    """The layer scan carries the stacked pools and each layer writes its
    new keys and values into them in place: the compiled step neither
    copies, slices out nor writes back a layer's pool or the whole pool,
    and needs less scratch memory than one layer's pool."""
    monkeypatch.setenv(tuning.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    tuning.clear_cache()
    layers = 2
    try:
        compiled, layer_pool = _paged_step(name, one_chip, layers)
    finally:
        tuning.clear_cache()
    text = compiled.as_text()
    if name == "decode":
        assert "tpu_custom_call" in text       # the paged kernel
    pool_dims = [",".join(map(str, d)) for d in
                 (layer_pool, (1,) + layer_pool, (layers,) + layer_pool)]
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([a-z-]+)\(", line)
        if m and m.group(2) in _POOL_OPS and any(
                f"[{d}]" in m.group(1) for d in pool_dims):
            moved.append(line.strip()[:160])
    assert moved == []
    pool_bytes = 2 * math.prod(layer_pool)                      # bf16
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_flash_attention_fwd_bwd_compiles_on_a_2x2_mesh(
        mesh_2x2, no_compile_cache, monkeypatch):
    """XLA cannot partition a Mosaic kernel: over a data x model mesh the
    model runs it per shard, and the whole forward and backward compile."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import ops
    from repro.launch.mesh import set_mesh
    from repro.models.transformer import Runtime, _attend

    # the process's backend is the CPU; these calls are compiled for the
    # described TPU, where the kernels never interpret
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)

    rt = Runtime(attention_backend="pallas", mesh_batch_axes=("data",))
    sh = _flash_shapes(jnp.float32)
    spec = NamedSharding(mesh_2x2, P("data"))
    B = 4
    args = [jax.ShapeDtypeStruct((B,) + sh[a][0][1:], jnp.float32,
                                 sharding=spec) for a in ("q", "k", "v")]

    def loss(q, k, v):
        return jnp.sum(_attend(q, k, v, rt, causal=True))

    with set_mesh(mesh_2x2):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
