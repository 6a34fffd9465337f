"""Every Pallas kernel compiles for a TPU v5e at real widths.

The TPU compiler is installed with JAX, so a described (not attached)
``v5e:2x2`` topology compiles what the chip would: VMEM overruns and
tiling the interpret-mode tests cannot see are refused here.  Each test
checks that the compiled program holds the kernel (``tpu_custom_call``),
so a silent fallback to interpret mode or the jnp reference fails.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.ssm_scan import ssm_scan


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


F32, BF16 = jnp.float32, jnp.bfloat16

#: kernel -> (callable, argument shapes/dtypes): the widths the chip smoke
#: runs (PolyBench/C 4.2 LARGE for atax and covariance, smollm-360m's 15
#: heads of 64 for attention, a falcon-mamba-sized channel axis for the scan)
CASES = {
    "axpy": (lambda x, y: ops.axpy(x, y, 2.5, impl="pallas",
                                   interpret=False),
             [((1 << 20,), F32), ((1 << 20,), F32)]),
    "matmul": (functools.partial(ops.matmul, impl="pallas", interpret=False),
               [((2048, 2048), BF16), ((2048, 2048), BF16)]),
    "atax": (functools.partial(ops.atax, impl="pallas", interpret=False),
             [((1900, 2100), F32), ((2100,), F32)]),
    "covariance": (functools.partial(ops.covariance, impl="pallas",
                                     interpret=False),
                   [((1200, 1400), F32)]),
    "flash_attention": (functools.partial(ops.attention, causal=True,
                                          impl="pallas", interpret=False),
                        [((1, 15, 2048, 64), BF16)] * 3),
    "ssm_scan": (functools.partial(ssm_scan, interpret=False),
                 [((1, 256, 8192, 16), F32), ((1, 256, 8192, 16), F32),
                  ((1, 256, 16), F32)]),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, shapes = CASES[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
