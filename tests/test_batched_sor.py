"""The batched packed-SOR kernel (``kernels/poisson`` ``rb_sor_batched``):
bit-for-bit agreement with the XLA loop it replaces on the TPU, the
``custom_vmap`` that hands it the whole env batch, and the dispatch rule.

The CPU's XLA contracts a product and a sum into one fused multiply-add
where its fusions allow, and two differently fused programs contract
differently, so on the CPU two programs agree bit for bit only without FMA
instructions.  The comparisons therefore run in one child process with
``--xla_cpu_max_isa=AVX``: every operation then rounds once, as on the
TPU's VPU, which is the arithmetic the kernel reproduces.  The kernel runs
in interpret mode there.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import poisson
from repro.cfd.grid import GridConfig
from repro.kernels.poisson import ops as poisson_ops

SRC = Path(__file__).resolve().parents[1] / "src"
CELL = GridConfig(res=8, dt=0.01, poisson_iters=50)      # 34 x 176
OMEGA = CELL.poisson_omega

# name: (batch, ny, nx, iters, polish)
CASES = {
    "B1_small": (1, 6, 16, 7, 2),
    "B3_cell": (3, CELL.ny, CELL.nx, 50, 10),
    "B60_cell_no_polish": (60, CELL.ny, CELL.nx, 6, 0),
    "B60_small": (60, 6, 16, 50, 10),
    "B3_small_polish_over_half": (3, 10, 24, 9, 20),
    "B3_odd_rows": (3, 7, 16, 9, 2),
    "B5_rows_past_a_loop_block": (5, 28, 16, 4, 1),
}

CHILD = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro.cfd import poisson
from repro.cfd.grid import GridConfig
from repro.kernels.poisson import ops, kernel

cases, dx, dy, omega = json.loads(sys.argv[1])
out = {}

def planes(batch, ny, w, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (batch, ny, w), jnp.float32) for k in keys]

def diff(a, b):
    return sum(int(np.sum(np.asarray(x).view(np.int32)
                          != np.asarray(y).view(np.int32)))
               for x, y in zip(a, b))

for name, (batch, ny, nx, iters, polish) in cases.items():
    p = planes(batch, ny, nx // 2, batch * ny)
    n_sor = iters - min(polish, iters // 2)
    loop = jax.jit(jax.vmap(lambda *q: poisson.packed_sor_loop(
        *q, omega, dx=dx, dy=dy, iters=iters, n_sor=n_sor)))(*p)
    kern = jax.jit(lambda *q: kernel.rb_sor_batched(
        *q, dx=dx, dy=dy, omega=omega, iters=iters, polish=polish,
        interpret=True))(*p)
    out[name] = diff(loop, kern)

# the whole solve as the rollout runs it: vmapped over 60 envs, through
# the dispatch on a TPU (the kernel) and elsewhere (the XLA loop)
ny, nx = 34, 176
rhs = jax.random.normal(jax.random.PRNGKey(1), (60, ny, nx), jnp.float32)
p0 = jax.random.normal(jax.random.PRNGKey(2), (60, ny, nx), jnp.float32)
solve = lambda: jax.jit(jax.vmap(lambda r, q: poisson.solve(
    r, dx, dy, iters=50, omega=omega, p0=q)))(rhs, p0)
xla = solve()
ops.kernel_platform = lambda: "tpu"
out["solve_vmap60_cell"] = diff([xla], [solve()])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def bit_diffs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=AVX",
               PYTHONPATH=os.pathsep.join(
                   [str(SRC)] + [p for p in [os.environ.get("PYTHONPATH")]
                                 if p]))
    arg = json.dumps([CASES, CELL.dx, CELL.dy, OMEGA])
    r = subprocess.run([sys.executable, "-c", CHILD, arg], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", list(CASES) + ["solve_vmap60_cell"])
def test_kernel_matches_xla_loop_bit_for_bit(bit_diffs, case):
    """Elements whose bits differ between the kernel and the XLA loop."""
    assert bit_diffs[case] == 0


# ---------------------------------------------------------------------------
# custom_vmap: every vmapped axis folds into the kernel's one block
# ---------------------------------------------------------------------------

def _kernel_calls(jaxpr):
    """The batched kernel's pallas_call equations anywhere in ``jaxpr``."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["name"] == "poisson_rb_sor_batched":
                out.append(eqn)
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _kernel_calls(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _kernel_calls(sub)
    return out


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as on a TPU; the kernel still runs interpreted here.  The
    choice is made at trace time, so no program traced meanwhile may stay
    in jit's caches for the tests that follow."""
    monkeypatch.setattr(poisson_ops, "kernel_platform", lambda: "tpu")
    yield
    jax.clear_caches()


def _solve_planes(red, black, rhs_r, rhs_b, iters=5, polish=2):
    return poisson_ops.rb_sor_solve(red, black, rhs_r, rhs_b, dx=CELL.dx,
                                    dy=CELL.dy, omega=OMEGA, iters=iters,
                                    polish=polish)


def _loop_planes(red, black, rhs_r, rhs_b, iters=5, polish=2):
    return poisson.packed_sor_loop(red, black, rhs_r, rhs_b, OMEGA,
                                   dx=CELL.dx, dy=CELL.dy, iters=iters,
                                   n_sor=iters - min(polish, iters // 2))


def _planes(shapes, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


# name: (transform, each operand's shape, envs in the block); a plane is
# (ny, w) = (6, 8) per env
VMAPS = {
    "vmap": (jax.vmap, [(5, 6, 8)] * 4, 5),
    "vmap_of_vmap": (lambda f: jax.vmap(jax.vmap(f)), [(2, 3, 6, 8)] * 4, 6),
    "partly_batched": (lambda f: jax.vmap(f, in_axes=(0, 0, None, None)),
                       [(4, 6, 8), (4, 6, 8), (6, 8), (6, 8)], 4),
    "vmap_over_axis_1": (lambda f: jax.vmap(f, in_axes=1, out_axes=1),
                         [(6, 3, 8)] * 4, 3),
}


@pytest.mark.parametrize("name", ["plain"] + list(VMAPS))
def test_custom_vmap_puts_whole_batch_in_one_block(on_tpu, name):
    """Every vmapped axis folds into the one kernel call's block; an
    unbatched call (one env) runs the XLA loop."""
    if name == "plain":
        planes = _planes([(6, 8)] * 4)
        assert not _kernel_calls(jax.make_jaxpr(_solve_planes)(*planes).jaxpr)
        for a, b in zip(_solve_planes(*planes), _loop_planes(*planes)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    transform, shapes, envs = VMAPS[name]
    planes = _planes(shapes)
    fn, ref_fn = transform(_solve_planes), transform(_loop_planes)
    calls = _kernel_calls(jax.make_jaxpr(fn)(*planes).jaxpr)
    assert len(calls) == 1
    # the block: rows, then every env, then packed columns
    assert calls[0].invars[0].aval.shape == (6, envs, 8)
    got, want = fn(*planes), ref_fn(*planes)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def test_solve_uses_kernel_only_where_the_rule_says(on_tpu, monkeypatch):
    """Through ``poisson.solve`` under vmap: the cell's grid at 60 envs
    takes the kernel; res 16 at 60 envs is over the VMEM fit and keeps the
    XLA loop, as one env does; an odd width and a traced omega never reach
    the kernel; on the CPU nothing does."""
    def n_calls(ny, nx, batch, omega=OMEGA):
        f = jax.vmap(lambda r: poisson.solve(r, 0.3, 0.2, iters=4,
                                             omega=omega))
        x = jnp.ones((batch, ny, nx), jnp.float32)
        return len(_kernel_calls(jax.make_jaxpr(f)(x).jaxpr))

    big = GridConfig(res=16)
    assert n_calls(CELL.ny, CELL.nx, 60) == 1
    assert n_calls(big.ny, big.nx, 2) == 1
    assert n_calls(big.ny, big.nx, 60) == 0
    assert n_calls(CELL.ny, CELL.nx, 1) == 0
    assert n_calls(CELL.ny, CELL.nx - 1, 60) == 0
    assert n_calls(CELL.ny, CELL.nx, 60, omega=jnp.float32(OMEGA)) == 0
    monkeypatch.setattr(poisson_ops, "kernel_platform", lambda: "cpu")
    jax.clear_caches()
    assert n_calls(CELL.ny, CELL.nx, 60) == 0


def test_default_platform_here_is_not_tpu():
    """The tier-1 suite's solver paths run the XLA loop, unchanged."""
    assert poisson_ops.kernel_platform() == jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# dispatch rule: a pure function of platform, grid shape and batch size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("platform,res,odd,batch,fits", [
    ("tpu", 8, False, 60, True),       # the cell: ~6.4 MiB of planes
    ("tpu", 8, False, 2, True),
    ("tpu", 8, False, 1, False),       # one env: the XLA loop
    ("tpu", 32, False, 60, False),     # ~13 MiB per plane
    ("tpu", 16, False, 60, False),
    ("tpu", 16, False, 16, True),
    ("tpu", 8, True, 60, False),       # odd nx: no checkerboard packing
    ("cpu", 8, False, 60, False),
    ("gpu", 8, False, 1, False),
])
def test_dispatch_rule(platform, res, odd, batch, fits):
    g = GridConfig(res=res)
    nx = g.nx - 1 if odd else g.nx
    assert poisson_ops.batched_kernel_fits(platform, g.ny, nx, batch) is fits


def test_dispatch_rule_monotone_in_batch():
    """A batch that fits fits with fewer envs, down to two: the solve asks
    at batch 2 whether any vmapped batch could take the kernel."""
    for res in (4, 8, 16, 32):
        g = GridConfig(res=res)
        fit = [poisson_ops.batched_kernel_fits("tpu", g.ny, g.nx, b)
               for b in range(2, 257)]
        assert fit == sorted(fit, reverse=True)
