"""The Pallas kernels compile for a TPU v5e chip, at real widths.

Interpret mode (``tests/test_kernels.py``) checks what the kernels
compute; it cannot see what the chip's compiler refuses — block shapes
off the (8, 128) tiling, more fast memory than a kernel may use. These
cases compile each kernel for one chip of a DESCRIBED ``v5e:2x2``
topology (no chip attached) and check that the program carries the
kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and a
module that loaded it while being collected would give pytest-xdist
workers different test sets. The persistent compilation cache is off
around these compiles: an entry compiled for a described chip cannot be
read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.head_select.kernel import head_select_losses
from repro.kernels.rwkv6.kernel import wkv_kernel


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


CASES = {
    # q/k/v [B, H, S, D] bf16
    "flash_attention": (
        flash_attention,
        [((1, 16, 2048, 128), jnp.bfloat16)] * 3),
    # features [T, D], heads [K, D, V], labels [T]
    "head_select_losses": (
        head_select_losses,
        [((2048, 2048), jnp.float32), ((4, 2048, 32768), jnp.float32),
         ((2048,), jnp.int32)]),
    # r/k/v/w [B, S, H, hd] f32, u [H, hd]
    "wkv_kernel": (
        wkv_kernel,
        [((1, 2048, 32, 64), jnp.float32)] * 4 + [((32, 64), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = CASES[name]
    compiled = jax.jit(fn).lower(
        *(_sds(one_chip, s, d) for s, d in args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
