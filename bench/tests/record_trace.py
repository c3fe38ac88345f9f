#!/usr/bin/env python3
"""Record the small trace the trace-reduction tests read.

    python3 bench/tests/record_trace.py bench/testdata

Runs on the chip: three tiny jitted programs named as the trainer's
(``collect_traj``, ``postprocess``, ``update``), each twice, and where JAX
finds more than one chip a ``pmap`` whose ``psum`` is a collective.  Writes
``small.xplane.pb`` into the given directory.
"""
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp


def collect_traj(x):
    for _ in range(3):
        x = jnp.sin(x) @ x
    return x


def postprocess(x):
    return jnp.cumsum(x, axis=0) * 0.5


def update(x):
    return x - 0.1 * jnp.tanh(x @ x.T)


def main(dest: str) -> int:
    x = jnp.ones((256, 256), jnp.float32) / 256
    progs = [jax.jit(f) for f in (collect_traj, postprocess, update)]
    n = jax.device_count()
    exchange = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")
    xs = jnp.ones((n, 1024), jnp.float32)
    for p in progs:
        p(x).block_until_ready()
    exchange(xs).block_until_ready()
    tmp = Path(tempfile.mkdtemp(prefix="record_trace_"))
    jax.profiler.start_trace(str(tmp))
    for _ in range(2):
        for p in progs:
            x = p(x)
        x.block_until_ready()
        if n > 1:
            exchange(xs).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(tmp.rglob("*.xplane.pb"))[-1]
    size = src.stat().st_size
    Path(dest).mkdir(parents=True, exist_ok=True)
    shutil.copy(src, Path(dest) / "small.xplane.pb")
    shutil.rmtree(tmp)
    print(f"recorded {size} bytes on {n} device(s) "
          f"{jax.devices()[0].device_kind!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
