"""Compile every Pallas entry point of ``kernels/ops.py`` for a TPU v5e chip
that is described, not attached: the TPU compiler refuses block specs and
VMEM budgets that the interpret-mode parity tests can never see.  Shapes are
the real widths of the models whose hot sites route to the kernels
(glm4-9b attention, rwkv6-3b wkv, a 151552-token vocabulary and a 10-class
exit head), plus the reduced smoke geometries the chip smoke run trains.

The topology is described inside a module fixture, never at import time,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_compile_cache):
    """``fn`` compiled for one v5e chip from argument shapes; asserts the
    Pallas kernel lowered natively (a Mosaic ``tpu_custom_call``)."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text
    return run


# (B, H, Hkv, T, D, dtype): glm4-9b at a 4096-token window; its smoke config
ATTN = {"glm4-9b": (1, 32, 2, 4096, 128, jnp.bfloat16),
        "glm4-9b-smoke": (4, 8, 2, 16, 32, jnp.float32)}
# (B, T, H, K, chunk, dtype): rwkv6-3b; its smoke config
WKV = {"rwkv6-3b": (1, 1024, 40, 64, 128, jnp.bfloat16),
       "rwkv6-3b-smoke": (4, 16, 4, 32, 8, jnp.float32)}


def _attn_shapes(geom):
    B, H, Hkv, T, D, dt = ATTN[geom]
    return (B, H, T, D), (B, Hkv, T, D), dt


@pytest.mark.parametrize("geom", sorted(ATTN))
def test_flash_attention_compiles(compile_for_chip, geom):
    q, kv, dt = _attn_shapes(geom)
    compile_for_chip(
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
        (q, dt), (kv, dt), (kv, dt))


@pytest.mark.parametrize("geom", sorted(ATTN))
def test_flash_attention_decode_compiles(compile_for_chip, geom):
    """One query row against the cache, with the traced valid prefix."""
    (B, H, T, D), kv, dt = _attn_shapes(geom)
    compile_for_chip(
        lambda q, k, v, n: ops.flash_attention(q, k, v, causal=False,
                                               kv_valid=n, interpret=False),
        ((B, H, 1, D), dt), (kv, dt), (kv, dt), ((), jnp.int32))


@pytest.mark.parametrize("geom", sorted(ATTN))
def test_flash_attention_fwd_compiles(compile_for_chip, geom):
    q, kv, dt = _attn_shapes(geom)
    compile_for_chip(
        lambda q, k, v: ops.flash_attention_fwd(q, k, v, interpret=False),
        (q, dt), (kv, dt), (kv, dt))


@pytest.mark.parametrize("geom", sorted(ATTN))
def test_flash_attention_bwd_compiles(compile_for_chip, geom):
    q, kv, dt = _attn_shapes(geom)
    compile_for_chip(
        lambda q, k, v, o, lse, do: ops.flash_attention_bwd(
            q, k, v, o, lse, do, interpret=False),
        (q, dt), (kv, dt), (kv, dt), (q, dt), (q[:3], jnp.float32), (q, dt))


def _wkv_shapes(geom):
    B, T, H, K, chunk, dt = WKV[geom]
    return (B, T, H, K), (H, K), chunk, dt


@pytest.mark.parametrize("geom", sorted(WKV))
def test_rwkv_wkv_compiles(compile_for_chip, geom):
    x, u, chunk, dt = _wkv_shapes(geom)
    compile_for_chip(
        lambda r, k, v, w, u: ops.rwkv_wkv(r, k, v, w, u, chunk=chunk,
                                           return_state=True,
                                           interpret=False),
        (x, dt), (x, dt), (x, dt), (x, jnp.float32), (u, jnp.float32))


@pytest.mark.parametrize("geom", sorted(WKV))
def test_rwkv_wkv_fwd_compiles(compile_for_chip, geom):
    x, u, chunk, dt = _wkv_shapes(geom)
    compile_for_chip(
        lambda r, k, v, w, u: ops.rwkv_wkv_fwd(r, k, v, w, u, chunk=chunk,
                                               interpret=False),
        (x, dt), (x, dt), (x, dt), (x, jnp.float32), (u, jnp.float32))


@pytest.mark.parametrize("geom", sorted(WKV))
def test_rwkv_wkv_bwd_compiles(compile_for_chip, geom):
    (B, T, H, K), u, chunk, dt = _wkv_shapes(geom)
    x = (B, T, H, K)
    s0 = (B * H, -(-T // chunk), K, K)
    compile_for_chip(
        lambda r, k, v, w, u, s0, dy, dsT: ops.rwkv_wkv_bwd(
            r, k, v, w, u, s0, dy, dsT, chunk=chunk, interpret=False),
        (x, dt), (x, dt), (x, dt), (x, jnp.float32), (u, jnp.float32),
        (s0, jnp.float32), (x, jnp.float32), ((B, H, K, K), jnp.float32))


@pytest.mark.parametrize("B,V,dt", [
    (16, 151552, jnp.bfloat16),     # glm4-9b vocabulary exit head
    (12, 10, jnp.float32),          # CIFAR-10 classifier exit
    (64, 512, jnp.float32),         # smoke-config vocabulary, B*T rows
])
def test_entropy_exit_compiles(compile_for_chip, B, V, dt):
    compile_for_chip(
        lambda x, tau: ops.entropy_exit(x, tau, interpret=False),
        ((B, V), dt), ((), jnp.float32))
