"""Record the small device trace that the trace-reduction tests read.

    python benchmark/tests/record_trace.py OUT_DIR

Runs on one TPU: a jitted matmul, the Pallas paged decode kernel and the
Pallas flash-attention kernels forward and backward, each inside a host
span, under the JAX profiler. Copies the ``.xplane.pb`` to
``OUT_DIR/small.xplane.pb`` and prints every plane, line and the first
events of each line, so that a reader can see how the kernels are named.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2048, 2048)), jnp.bfloat16)
    mm = jax.jit(lambda a: (a @ a).astype(jnp.float32).sum())
    q = jnp.asarray(rng.standard_normal((8, 1, 32, 128)), jnp.bfloat16)
    pool = jnp.asarray(rng.standard_normal((65, 16, 8, 128)), jnp.bfloat16)
    tables = jnp.asarray(np.arange(1, 65).reshape(8, 8), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, 128, 8), jnp.int32)
    fq = jnp.asarray(rng.standard_normal((1, 1024, 32, 128)), jnp.bfloat16)
    fk = jnp.asarray(rng.standard_normal((1, 1024, 8, 128)), jnp.bfloat16)

    def floss(q, k, v):
        return ops.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    fgrad = jax.jit(jax.grad(floss, argnums=(0, 1, 2)))

    def work():
        with jax.profiler.TraceAnnotation("probe.matmul"):
            mm(x).block_until_ready()
        with jax.profiler.TraceAnnotation("probe.paged"):
            ops.paged_decode_attention(q, pool, pool, tables,
                                       lengths).block_until_ready()
        with jax.profiler.TraceAnnotation("probe.flash"):
            jax.block_until_ready(fgrad(fq, fk, fk))

    work()                                  # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(2):
        work()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(path, Path(out_dir) / "small.xplane.pb")
    shutil.rmtree(tmp)

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(Path(out_dir) / "small.xplane.pb"))
    for plane in pd.planes:
        print("PLANE", plane.name, dict(plane.stats))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            seen = set()
            for e in evs:
                if e.name in seen or len(seen) >= 12:
                    continue
                seen.add(e.name)
                print("    EV", repr(e.name), e.start_ns, e.duration_ns,
                      dict(e.stats))
    d = jax.devices()[0]
    print("device", d.device_kind, d.memory_stats())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
