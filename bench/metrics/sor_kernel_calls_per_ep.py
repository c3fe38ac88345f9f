"""Calls of the batched pressure-solve kernel (``poisson_rb_sor_batched``)
per complete episode, averaged over chips: one per dt when every solve of
the rollout runs in it (``bench/kernel_ops.py``)."""
from bench import kernel_ops

KERNEL = "poisson_rb_sor_batched"


def read(ctx):
    r = kernel_ops.runs(ctx["trace"], KERNEL)
    return None if r is None else r.calls / r.episodes
