"""Compile every Pallas kernel for a described TPU v5e at real model widths.

Nothing runs.  The TPU compiler installed beside jax compiles for a chip
that is described, not attached, and refuses what the chip would refuse:
block layouts Mosaic cannot tile, primitives it cannot lower, too much
VMEM.  Interpret-mode tests (``test_kernels.py``) cannot see any of that.
Each test asserts the kernel reaches the program as a Mosaic custom call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rwkv6_wkv import wkv6_pallas
from repro.kernels.ssd_scan import ssd_pallas

BF16, F32 = jnp.bfloat16, jnp.float32


def _attn(b, s, hq, hkv, d):
    return [((b, s, hq, d), BF16), ((b, s, hkv, d), BF16), ((b, s, hkv, d), BF16)]


# (kernel, [(shape, dtype) per argument]) at each model's published width
CASES = {
    "flash_attention-qwen3-4b": (flash_attention_pallas, _attn(1, 2048, 32, 8, 128)),
    "flash_attention-smollm-360m": (flash_attention_pallas, _attn(1, 2048, 15, 5, 64)),
    "flash_attention-smollm-360m-s100": (flash_attention_pallas, _attn(1, 100, 15, 5, 64)),
    "wkv6-rwkv6-1.6b": (
        wkv6_pallas,
        [((1, 2048, 32, 64), BF16)] * 4 + [((32, 64), F32)],
    ),
    "ssd-zamba2-1.2b": (
        ssd_pallas,
        [
            ((1, 2048, 64, 64), BF16),   # x: d_inner 4096 = 64 heads of 64
            ((1, 2048, 64), F32),        # dt
            ((64,), F32),                # a
            ((1, 2048, 64), BF16),       # B (d_state 64)
            ((1, 2048, 64), BF16),       # C
        ],
    ),
}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off (such entries cannot be read back without a chip) and the
    TPU compiler's logs off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    kernel, args = CASES[case]
    sds = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip) for shape, dt in args]
    compiled = jax.jit(kernel).lower(*sds).compile()
    assert "tpu_custom_call" in compiled.as_text()
