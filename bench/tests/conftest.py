import copy
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tiny():
    """Cell 1 cut to a size the CPU runs in seconds, with its limits."""
    from bench import check, harness
    _, cfg, traffic = harness.load_cell("cyl_re100_jets.paper")
    cfg = dict(cfg, res=4, n_envs=4, warmup_time=0.5, poisson_iters=10)
    traffic = copy.deepcopy(traffic)
    traffic.update(steps_per_action=2, actions_per_episode=3)
    traffic["ppo"].update(epochs=2, minibatches=2)
    return cfg, traffic, check.load_limits(ROOT, "cyl_re100_jets.paper")
