"""What the program decides from the platform it runs on: interpret or
compile the kernels, which blocks a compiled kernel may take, which
modeled chip the meters bill, where the compile cache lives, and which
platform a child process may touch."""
import os
import types

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.power import SPECS_BY_KIND, V5E, modeled_spec
from repro.kernels import ops, resolve_interpret
from repro.launch import compile_cache, dryrun


def test_interpret_is_decided_from_the_platform():
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


@pytest.mark.parametrize("n,target,align,want", [
    (96, 128, 8, 96),         # whole dimension
    (4096, 256, 128, 256),
    (4096, 512, 8, 512),
    (1000, 512, 128, 0),      # no lane-aligned divisor: no block
    (1000, 512, 8, 200),
    (1000, 512, 1, 500),      # interpret mode: any divisor
    (97, 64, 1, 1),
])
def test_blk_picks_aligned_divisors(n, target, align, want):
    b = ops._blk(n, target, align)
    assert b == want
    if b:
        assert n % b == 0 and (b == n or b % align == 0)


@pytest.mark.parametrize("pick,dims", [
    (ops.flash_blocks, (4096, 4096)),
    (ops.swiglu_blocks, (2048, 18944)),
    (ops.rglru_blocks, (4096, 4096)),
    (ops.mriq_blocks, (64 ** 3, 3072)),
    (ops.flash_blocks, (2048, 2048)),      # qwen2-7b.prompt_heavy's prefill
    (ops.flash_blocks, (16384, 16384)),    # granite long_prompt's prefill
])
def test_compiled_blocks_at_published_widths_tile(pick, dims):
    blocks = pick(*dims, compiled=True)
    assert all(b >= 8 and d % b == 0 for b, d in zip(blocks, dims))
    assert all(b % ops.SUBLANE == 0 or b == d for b, d in zip(blocks, dims))
    assert ops.ssd_chunk(4096, 256, compiled=True) == 256


def test_oracle_fallback_is_counted():
    reg = obs.set_metrics(obs.MetricsRegistry())
    try:
        q = jnp.ones((1, 4, 2, 8))
        out = ops.flash_attention(q, q, q)
        assert out.shape == q.shape
        assert reg.counter("kernel_fallback_flash_attention").value == 1
    finally:
        obs.set_metrics(None)


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_modeled_spec_by_device_kind():
    assert modeled_spec(_dev("tpu", "TPU v5 lite")) is V5E
    assert modeled_spec(_dev("cpu", "cpu")) is V5E     # modeled, not read
    assert SPECS_BY_KIND["TPU v5 lite"] is V5E
    with pytest.raises(ValueError, match="TPU v9"):
        modeled_spec(_dev("tpu", "TPU v9"))


def test_compile_cache_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    min_t = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before   # jax's own
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert path.endswith("artifacts/jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_t)


def _cache_key(scope: str) -> str:
    """The persistent cache's key of a program whose one op sits in the
    named scope `scope`."""
    import numpy as np
    from jax._src import cache_key, compiler, xla_bridge

    def f(x):
        with jax.named_scope(scope):
            return jnp.sin(x)
    ir = jax.jit(f).lower(jnp.ones(8)).compiler_ir("stablehlo")
    opts = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    return cache_key.get(ir, np.array([jax.devices()[0]]), opts,
                         xla_bridge.get_backend())


def test_compile_cache_keys_on_the_named_scopes(monkeypatch):
    """A program that differs from a cached one only in its scopes is not
    read back as that one: its device ops would carry the old names."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    min_t = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    try:
        jax.config.update(flag, False)
        assert len({_cache_key(s) for s in ("layers", "layer")}) == 1
        compile_cache.enable_compile_cache()
        assert getattr(jax.config, flag) is True
        keys = [_cache_key(s) for s in ("layers", "layers", "layer")]
        assert keys[0] == keys[1] != keys[2]
    finally:
        jax.config.update(flag, before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_t)


def test_dryrun_child_pins_cpu_and_keeps_xla_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/x "
                       "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    dryrun.setup_host_devices(512)
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["XLA_FLAGS"].split() == [
        "--xla_dump_to=/x", "--xla_force_host_platform_device_count=512"]
