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
from jax.sharding import PartitionSpec, SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
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
    "decode_attention": (
        functools.partial(decode_attention, interpret=False),
        [((64, 15, 64), BF16), ((64, 5, 64), BF16), ((64, 5, 64), BF16),
         ((32, 64, 320, 2048), BF16), ((32, 64, 320, 2048), BF16),
         ((64,), jnp.int32), ((), jnp.int32)]),
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


def _compile_ragged_step(devices, b, s, monkeypatch, cfg=None):
    """HLO text of the ragged serve step at smollm-360m's widths (2 layers,
    a small vocabulary), or of ``cfg``, for ``b`` slots of an
    ``s``-position cache, compiled for the described chips ``devices`` as a
    (data, 1) mesh, the program taking its TPU path as on the chip.
    -> (text, cfg)"""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro import models as M
    from repro.serve.engine import build_ragged_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: len(devices))
    cfg = cfg or dataclasses.replace(M.get("smollm-360m"), n_layers=2,
                                     vocab_size=4096)
    mesh = Mesh(np.asarray(devices).reshape(-1, 1), ("data", "model"))
    step, cspecs, tok = build_ragged_step(cfg, mesh, b, s, 0.0)
    here = NamedSharding(mesh, PartitionSpec())
    put = lambda tree, sh=here: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=here)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, b, s))
    text = step.lower(
        put(jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))),
        jax.tree.map(lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
            cache, cspecs),
        jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=tok),
        i32(b), i32(b), put(jax.eval_shape(lambda: jax.random.key(0))), i32()
    ).compile().as_text()
    return text, cfg


def test_serve_decode_step_reads_the_cache_in_place(one_chip, monkeypatch):
    """The ragged serve step at smollm-360m's widths and its 64 x 2048 serve
    cache, compiled for a v5e: the kernel is there, and no other operation
    outputs an array of a layer's cache slab or larger, so no slab is
    copied, relayout, repeated or upcast."""
    b, s = 64, 2048
    text, cfg = _compile_ragged_step(list(one_chip.device_set), b, s,
                                     monkeypatch)
    assert "tpu_custom_call" in text
    _assert_no_slab_sized_op(text, b * s * cfg.n_kv_heads * cfg.head_dim)


def _assert_no_slab_sized_op(text, slab, positions=None):
    """No operation of the compiled program but a passive one outputs an
    array of ``slab`` elements or more (with a ``positions``-long axis,
    where given: the weights may be larger than a slab)."""
    import re

    import numpy as np

    passive = {"parameter", "custom-call", "tuple", "get-tuple-element",
               "bitcast", "while", "call", "conditional", "opt-barrier"}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if not m or m.group(2) in passive:
            continue
        shapes = [[int(x) for x in dims.split(",") if x]
                  for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
        sizes = [int(np.prod(sh)) for sh in shapes
                 if positions is None or positions in sh]
        assert max(sizes, default=0) < slab, line[:160]


def test_mla_decode_step_reads_the_latent_cache_in_place(one_chip,
                                                         monkeypatch):
    """The ragged serve step of deepseek-v2-lite-16b at its published widths
    and depth, bfloat16 weights, 8 held experts, 40 slots of a 4096-position
    latent cache: the latent kernel is there, and no operation copies,
    converts, transposes or slices an array of positions the size of a
    layer's cache slab or larger."""
    import dataclasses

    from repro import models as M

    full = M.get("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(
        full, param_dtype="bfloat16",
        moe=dataclasses.replace(full.moe, experts_held=(0, 8)))
    b, s = 40, 4096
    text, cfg = _compile_ragged_step(list(one_chip.device_set), b, s,
                                     monkeypatch, cfg)
    assert "mla_decode_attention" in text and "tpu_custom_call" in text
    _assert_no_slab_sized_op(text, b * s * cfg.kv_dim, positions=s)


def test_serve_decode_step_compiles_across_chips(topo, monkeypatch):
    """With the slots sharded over the four chips of a v5e 2x2 the step
    compiles: a Mosaic call is not partitioned across a mesh, so with more
    than one device in view the decode attention takes its XLA path."""
    text, _ = _compile_ragged_step(list(topo.devices), 8, 256, monkeypatch)
    assert "tpu_custom_call" not in text
