"""Launch-layer tests: step builders, shardings, roofline math (1 device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, get_config, list_configs
from repro.launch import roofline, steps
from repro.launch.mesh import make_abstract_mesh, make_debug_mesh
from repro.models import model as M
from repro.models.sharding import param_specs


def test_all_configs_registered():
    assert len(list_configs()) == 10


def test_input_shapes_pool():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["long_500k"].seq_len == 524_288
    assert INPUT_SHAPES["long_500k"].global_batch == 1
    assert INPUT_SHAPES["decode_32k"].kind == "decode"


def test_param_specs_cover_big_dims():
    """Every >=1M-element parameter of every arch must be sharded on the
    production mesh shape (16,16) — nothing big may stay replicated."""
    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    for arch in list_configs():
        cfg = get_config(arch)
        shapes = steps.abstract_params(cfg)
        specs = param_specs(mesh, shapes)
        flat_sh = jax.tree_util.tree_flatten_with_path(shapes)[0]
        flat_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for (kp, leaf), spec in zip(flat_sh, flat_sp):
            n = int(np.prod(leaf.shape))
            if n >= 4_000_000:
                assert any(a is not None for a in spec), \
                    (arch, kp, leaf.shape, spec)


def test_opt_state_specs_mirror_params():
    mesh = make_debug_mesh(1, 1)
    cfg = get_config("phi4-mini-3.8b")
    pshape = steps.abstract_params(cfg)
    oshape = steps.abstract_opt_state(cfg, pshape)
    ospecs = steps.opt_state_specs(mesh, pshape, oshape)
    # structure must match the state tree exactly
    jax.tree.map(lambda s, sp: None, oshape, ospecs,
                 is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)))


def test_vocab_padding():
    assert get_config("seamless-m4t-large-v2").vocab_padded % 256 == 0
    assert get_config("hymba-1.5b").vocab_padded == 32256
    assert get_config("llama3-405b").vocab_padded == 128256  # already /256


def test_model_flops_sane():
    cfg = get_config("phi4-mini-3.8b")
    pshape = steps.abstract_params(cfg)
    n = roofline.param_count(cfg, pshape)
    assert 3.0e9 < n < 6.0e9, n
    fl = roofline.model_flops(cfg, INPUT_SHAPES["train_4k"], pshape)
    assert abs(fl - 6 * n * 256 * 4096) / fl < 1e-6


def test_model_flops_moe_active():
    cfg = get_config("deepseek-v3-671b")
    pshape = steps.abstract_params(cfg)
    n_total = roofline.param_count(cfg, pshape)
    n_active = roofline.active_param_count(cfg, pshape)
    assert 6.3e11 < n_total < 7.2e11, n_total      # ~671B
    assert 3.0e10 < n_active < 5.0e10, n_active     # ~37B active


def test_roofline_terms():
    hw = roofline.HARDWARE_PRESETS["tpu_v5e"]
    rl = roofline.Roofline(
        arch="x", shape="train_4k", mesh="m", n_devices=256,
        flops_per_dev=hw.peak_flops, bytes_per_dev=hw.hbm_bw,
        coll_bytes_per_dev=hw.ici_bw,
        model_flops=hw.peak_flops * 256, coll_by_kind={}, hw=hw)
    assert abs(rl.compute_s - 1.0) < 1e-9
    assert abs(rl.memory_s - 1.0) < 1e-9
    assert abs(rl.collective_s - 1.0) < 1e-9
    assert abs(rl.useful_ratio - 1.0) < 1e-9


def test_hardware_spec_presets_and_resolution(monkeypatch):
    # explicit preset name and passthrough of a spec object
    assert roofline.hardware_spec("tpu_v5e").peak_flops == 197e12
    custom = roofline.HardwareSpec("lab_gpu", 1e12, 1e11, 1e10)
    assert roofline.hardware_spec(custom) is custom
    # environment override beats device detection
    monkeypatch.setenv(roofline.HW_SPEC_ENV, "cpu_generic")
    assert roofline.hardware_spec().name == "cpu_generic"
    monkeypatch.delenv(roofline.HW_SPEC_ENV)
    # detection keys on the device_kind JAX reports, with a cited source
    v5e = roofline.HARDWARE_PRESETS[roofline.DEVICE_KINDS["TPU v5 lite"]]
    assert v5e.name == "tpu_v5e" and "819 GB/s" in v5e.source

    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(roofline.jax, "devices", lambda: [_Dev()])
    assert roofline.hardware_spec().name == "tpu_v5e"


def test_hardware_spec_unknown_is_actionable(monkeypatch):
    with pytest.raises(ValueError, match="cpu_generic.*tpu_v5e"):
        roofline.hardware_spec("tpu_v9000")
    # a bad env override fails the same way instead of silently defaulting
    monkeypatch.setenv(roofline.HW_SPEC_ENV, "nonsense")
    with pytest.raises(ValueError, match="unknown hardware spec"):
        roofline.hardware_spec()
    # this suite pins JAX_PLATFORMS=cpu: the host CPU is no known device
    # kind, so detection raises instead of pricing it as any preset
    monkeypatch.delenv(roofline.HW_SPEC_ENV)
    with pytest.raises(ValueError, match="no hardware preset for device_kind"):
        roofline.hardware_spec()


def test_roofline_prices_against_its_spec():
    cpu = roofline.HARDWARE_PRESETS["cpu_generic"]
    rl = roofline.build("x", "s", "m", 1,
                        {"flops": cpu.peak_flops, "bytes": cpu.hbm_bw / 2,
                         "coll_bytes": 0.0}, cpu.peak_flops, hw="cpu_generic")
    assert rl.hw.name == "cpu_generic"
    assert abs(rl.compute_s - 1.0) < 1e-9
    assert abs(rl.memory_s - 0.5) < 1e-9
    assert rl.dominant == "compute"
    assert rl.to_dict()["hw"]["name"] == "cpu_generic"


def test_train_step_on_debug_mesh():
    """make_train_step with a real (1,1) mesh: runs and decreases loss."""
    mesh = make_debug_mesh(1, 1)
    cfg = get_config("qwen2-vl-2b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = steps.make_opt(cfg)
    opt_state = opt.init(params)
    ts = jax.jit(steps.make_train_step(cfg, mesh))
    from repro.models import frontend as fe_mod
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1),
             "frontend_embeds": jnp.zeros(
                 (B, fe_mod.num_frontend_tokens(cfg, S),
                  fe_mod.frontend_dim(cfg)))}
    step = jnp.int32(0)
    losses = []
    for _ in range(3):
        params, opt_state, step, metrics = ts(params, opt_state, step, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_microbatch_clamp():
    """Microbatches clamp so B/mb divides the dp axes (multi-pod bug fix)."""
    import dataclasses
    mesh = make_debug_mesh(1, 1)
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b").reduced(),
                              train_microbatches=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = steps.make_opt(cfg)
    ts = jax.jit(steps.make_train_step(cfg, mesh))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)   # B=4 < 8 microbatches
    batch = {"tokens": tokens, "labels": tokens}
    params, _, _, metrics = ts(params, opt.init(params), jnp.int32(0), batch)
    assert not bool(jnp.isnan(metrics["loss"]))


def test_cache_specs_structure():
    mesh = make_debug_mesh(1, 1)
    for arch in ("llama3-405b", "deepseek-v3-671b", "rwkv6-3b",
                 "hymba-1.5b", "seamless-m4t-large-v2"):
        cfg = get_config(arch)
        specs, shapes = steps.cache_specs(cfg, mesh, 8, 1024)
        jax.tree.map(lambda s, sp: None, shapes, specs,
                     is_leaf=lambda x: isinstance(
                         x, (jax.ShapeDtypeStruct, P)))


def test_fp8_cache_dtype():
    cfg = get_config("llama3-405b")
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 64))
    assert cache["k"].dtype == jnp.float8_e4m3fn
    cfg2 = get_config("phi4-mini-3.8b")
    cache2 = jax.eval_shape(lambda: M.init_cache(cfg2, 2, 64))
    assert cache2["k"].dtype == jnp.bfloat16


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets no directory of its
    own; unset, the cache sits at the fixed <checkout>/.jax_cache."""
    from pathlib import Path

    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    checkout = Path(__file__).resolve().parent.parent
    try:
        if from_env:
            monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
            where = compile_cache.enable_compile_cache()
            assert where == str(checkout / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == where
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # the suite keeps the cache off (tests/conftest.py)
    assert not jax.config.jax_enable_compilation_cache
