import os
import sys
from pathlib import Path

# src layout without an editable install: bare ``python -m pytest`` must
# still find the ``repro`` package, with or without PYTHONPATH=src.
_SRC = str(Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# Tests must see 1 CPU device (the 512-device override is dryrun-only).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Tests keep JAX's persistent compilation cache off, in this process and in
# every child that inherits the environment (repro.launch.compile_cache).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _reset_warning_caches():
    """Warn-once caches are process-global; without this reset, any test
    asserting a once-per-shape warning depends on execution order."""
    from repro.core import backend as backend_mod
    from repro.testing import faults
    backend_mod.reset_warning_caches()
    faults.reset()
    yield
    faults.reset()      # a test that armed faults must not leak them
