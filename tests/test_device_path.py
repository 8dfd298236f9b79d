"""What replaced the device path's quiet fallbacks (ISSUE 21): one peak
table that raises for an unknown TPU, kernel gates that let a failing
device query propagate, interpret mode refused on a TPU, a compile cache
placed at one fixed path, a native loader that says when it failed, and
entry points that exit non-zero instead of carrying on."""
import os
import subprocess
import sys
import warnings

import jax
import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class _FakeTPU:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def _on(monkeypatch, kind):
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeTPU(kind)])


# ---------------------------------------------------------------- peak table
def test_peak_lookup_matches_v5_lite_and_raises_for_unknown_tpu(monkeypatch):
    from flexflow_tpu.obs.telemetry import detect_peak_flops

    assert detect_peak_flops() is None  # the CPU mesh has no peak
    _on(monkeypatch, "TPU v5 lite")
    assert detect_peak_flops() == 197e12
    _on(monkeypatch, "TPU v99")
    with pytest.raises(RuntimeError, match="unknown TPU device_kind"):
        detect_peak_flops()


def test_machine_model_detect_cpu_is_v5e_and_unknown_tpu_raises(monkeypatch):
    from flexflow_tpu.search.machine_model import TPUMachineModel

    assert TPUMachineModel.detect(8).generation == "v5e"
    _on(monkeypatch, "TPU v5 lite")
    assert TPUMachineModel.detect(4, num_hosts=1).peak_flops == 197e12
    _on(monkeypatch, "TPU v99")
    with pytest.raises(RuntimeError, match="unknown TPU device_kind"):
        TPUMachineModel.detect(4, num_hosts=1)


def test_flash_tuning_unmeasured_tpu_generation_raises(monkeypatch):
    from flexflow_tpu.ops import attention

    assert attention._flash_tuning() == attention.FLASH_TUNING["v5e"]
    _on(monkeypatch, "TPU v5 lite")
    assert attention._flash_tuning() == attention.FLASH_TUNING["v5e"]
    _on(monkeypatch, "TPU v6e")  # in the peak table, no measured tile row
    with pytest.raises(RuntimeError, match="no measured row"):
        attention._flash_tuning()


# ------------------------------------------------------------- kernel gates
def test_kernel_gates_propagate_a_failing_device_query(monkeypatch):
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_decode import use_flash_decode
    from flexflow_tpu.kernels.softmax import should_use_pallas_softmax
    from flexflow_tpu.kernels.topk import should_use_pallas_topk
    from flexflow_tpu.ops.attention import _should_use_flash

    def boom(*a, **k):
        raise RuntimeError("device query failed")

    monkeypatch.setattr(jax, "devices", boom)
    q = jnp.zeros((1, 2, 512, 64), jnp.bfloat16)
    x = jnp.zeros((8, 1024), jnp.bfloat16)
    for gate in (lambda: use_flash_decode(128, 16),
                 lambda: _should_use_flash("auto", q, q, False),
                 lambda: should_use_pallas_softmax(x, -1, opt_in=True),
                 lambda: should_use_pallas_topk(x, 2, opt_in=True)):
        with pytest.raises(RuntimeError, match="device query failed"):
            gate()


def test_interpret_defaults_on_cpu_and_is_refused_on_tpu(monkeypatch):
    from flexflow_tpu.kernels._common import resolve_interpret

    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    _on(monkeypatch, "TPU v5 lite")
    assert resolve_interpret(None) is False
    with pytest.raises(RuntimeError, match="interpret mode"):
        resolve_interpret(True)


# ------------------------------------------------------------ compile cache
def test_compile_cache_respects_env_else_fixed_checkout_path(monkeypatch):
    from flexflow_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.ensure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.ensure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.ensure_compile_cache() == want  # idempotent
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(compile_cache.__file__) as f:
        src = f.read()
    for moving_part in ("tempfile", "getpid", "time"):
        assert moving_part not in src


# ------------------------------------------------------------ native loader
def test_native_build_failure_is_said_once(monkeypatch, tmp_path):
    from flexflow_tpu import native

    assert native.implementation() == "native"  # g++ is in the image

    def fail(so):
        raise subprocess.CalledProcessError(1, ["g++"], stderr=b"no g++")

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    monkeypatch.setattr(native, "_so_path",
                        lambda: str(tmp_path / "libffnative-none.so"))
    monkeypatch.setattr(native, "_build", fail)
    with pytest.warns(UserWarning, match="pure-Python"):
        assert native.implementation() == "python"
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the second ask is silent
        assert native.get_lib() is None


def test_native_library_is_named_by_its_source():
    import hashlib

    from flexflow_tpu import native

    with open(native._SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    assert os.path.basename(native._so_path()) == f"libffnative-{tag}.so"


# -------------------------------------------------------------- entry points
def test_chip_smoke_refuses_the_cpu_within_seconds():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(_REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no result line without a chip


def test_config_lets_a_failing_device_query_propagate(monkeypatch):
    from flexflow_tpu import FFConfig

    def boom(*a, **k):
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        FFConfig()


# ------------------------------------------------ flash kernel on a mesh
@pytest.mark.parametrize("shape,names", [((8,), ("data",)),
                                         ((4, 2), ("data", "model")),
                                         ((2, 4), ("data", "model"))])
def test_flash_on_mesh_rides_shard_map_and_matches_einsum(shape, names):
    """Mosaic kernels cannot be auto-partitioned (on the chip a sharded
    operand fails to lower), so on a mesh the flash call is a shard_map
    over (batch, heads): same numbers as the einsum core, forward and
    gradients."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flexflow_tpu.ops.attention import _flash_on_mesh, mha_core

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(shape), names)
    rng = np.random.default_rng(0)
    q, k, v = (jax.device_put(
        jnp.asarray(rng.normal(size=(8, 4, 128, 64)), jnp.float32),
        NamedSharding(mesh, P("data"))) for _ in range(3))

    def through(core):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v) ** 2), argnums=(0, 1, 2)))

    jaxpr = str(jax.make_jaxpr(
        lambda q, k, v: _flash_on_mesh(q, k, v, True, 0.0, None, mesh)
    )(q, k, v))
    assert "shard_map" in jaxpr
    got = through(lambda q, k, v: _flash_on_mesh(q, k, v, True, 0.0, None,
                                                 mesh))(q, k, v)
    want = through(lambda q, k, v: mha_core(q, k, v, causal=True))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    # dropout: one sample repeated over the batch — the first sample of
    # each data shard has the same LOCAL (batch, head) index, so only the
    # mesh position folded into the seed keeps their masks apart
    same = jax.device_put(jnp.tile(q[:1], (8, 1, 1, 1)),
                          NamedSharding(mesh, P("data")))
    out = np.asarray(jax.jit(lambda x: _flash_on_mesh(
        x, x, x, False, 0.5, jnp.uint32(7), mesh))(same))
    assert np.all(np.isfinite(out))
    per_shard = 8 // shape[0]
    assert not np.allclose(out[0], out[per_shard])
