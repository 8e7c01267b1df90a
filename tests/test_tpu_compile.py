"""Compile the render path's Pallas kernels for a TPU v5e chip, without one.

Mosaic (the TPU kernel compiler) is installed with JAX and compiles for a
described, unattached topology, so these tests catch what interpret mode
cannot: block shapes that break the TPU's (8, 128) tiling rule, ops Mosaic
has no lowering for, and kernels that overrun VMEM. Shapes are the hd1080
serving deployment's (`serving.workloads.hd1080_engine`): 1920×1088 →
T = 8160 tiles of P = 256 pixels, Mt = 16 mini-tiles per tile, a 512-slot
spill chunk, 512k Gaussians. Nothing runs; each test asserts that the
compiled program holds a Mosaic kernel (`tpu_custom_call`).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.kernels import prtu, render as krender

T, P, K, MT, N = 8160, 256, 512, 16, 524288
# The dense oracle's (mini-tile × Gaussian) mask at the full 512k would be
# 68 GB; it compiles here against a 16k-Gaussian slice of the scene.
N_DENSE = 16384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Compile kernels through Mosaic (this process's backend is the CPU,
    where they would be interpreted), with the persistent compilation cache
    off: a described-topology compile cannot be read back from it."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _blend_operands():
    return [((T, P, 2), jnp.float32), ((T, K, 8), jnp.float32),
            ((T, K, 3), jnp.float32), ((T, K), jnp.int8),
            ((T, K, MT), jnp.int8)]


def test_blend_tiles_compiles(mosaic, one_chip):
    text = _compile_text(krender.blend_tiles, one_chip, *_blend_operands())
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("carried", [False, True],
                         ids=["first_pass", "spill_pass"])
def test_blend_tiles_fused_compiles(mosaic, one_chip, carried):
    ops = _blend_operands()
    if not carried:
        def fn(*a):
            return krender.blend_tiles_fused(*a)
    else:
        ops += [((T, P), jnp.float32), ((T, P, 3), jnp.float32),
                ((T, P), jnp.float32), ((T, P), jnp.float32)]

        def fn(*a):
            return krender.blend_tiles_fused(*a[:5], init=tuple(a[5:]))
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *ops)


def test_prtu_cat_mask_compiles(mosaic, one_chip):
    def fn(p_top, p_bot, mu, conic, lhs, spiky):
        return prtu.prtu_cat_mask(p_top, p_bot, mu, conic, lhs, spiky)
    text = _compile_text(
        fn, one_chip, ((T * MT, 2), jnp.float32), ((T * MT, 2), jnp.float32),
        ((N_DENSE, 2), jnp.float32), ((N_DENSE, 3), jnp.float32),
        ((N_DENSE,), jnp.float32), ((N_DENSE,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_prtu_entry_cat_mask_compiles(mosaic, one_chip):
    def fn(p_top, p_bot, origins, feat):
        return prtu.prtu_entry_cat_mask(p_top, p_bot, origins, feat)
    text = _compile_text(
        fn, one_chip, ((MT, 2), jnp.float32), ((MT, 2), jnp.float32),
        ((T, 2), jnp.int32), ((T, 8, K), jnp.float32))
    assert "tpu_custom_call" in text
