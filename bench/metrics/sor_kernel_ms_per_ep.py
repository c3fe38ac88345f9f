"""Device time of the pressure-solve kernels (``poisson_rb_sor_batched``,
``poisson_rb_sor_packed``) per complete episode, averaged over chips
(``bench/kernel_ops.py``)."""
from bench import kernel_ops
from bench.metrics.sor_kernel_calls_per_ep import KERNELS


def read(ctx):
    r = kernel_ops.runs(ctx["trace"], KERNELS.__contains__)
    return None if r is None else 1e3 * r.seconds / r.episodes
