"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel body as traced jnp and accepts what Mosaic
refuses (unaligned blocks, general gathers, ``erf``).  These tests compile
each kernel of the M³ViT serving path at M³ViT widths (d 192, f 768, 16
experts top-4, 3 heads of 64, 128-token groups) for one chip of a ``v5e:2x2``
topology, with ``interpret=False`` and the ``tpu`` tile schedule, and check
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).
The default policy's LUT activation, which is no kernel, is compiled the
same way and must hold no gather.  Nothing runs, so no chip is needed;
where the TPU compiler cannot describe the topology the tests skip.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import ops
from repro.core import routing as R
from repro.kernels import ops as kops
from repro.ops import schedule_for

D, F, E, TOP_K, HEADS, HEAD_DIM = 192, 768, 16, 4, 3, 64
GROUP = 128                      # tokens per routing group (one image)
TOKENS = 4 * GROUP               # a batch of four images
CAPACITY = 68                    # MoEConfig.capacity(128) at factor 2.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler or libtpu held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _tiles(op, impl, dims):
    return schedule_for(op, impl, dims, backend="tpu")


def _flash(spec):
    q = spec((4, HEADS, GROUP, HEAD_DIM), jnp.bfloat16)
    t = _tiles("attention", "pallas",
               {"sq": GROUP, "skv": GROUP, "d": HEAD_DIM})
    return (lambda q, k, v: kops.flash_attention(
        q, k, v, causal=False, interpret=False, **t)), (q, q, q)


def _linear(use_lut):
    def build(spec):
        t = _tiles("linear", "pallas", {"m": TOKENS, "n": F, "k": D})
        return (lambda x, w, b: kops.unified_linear(
            x, w, b, activation="gelu", use_lut=use_lut, interpret=False,
            **t)), (spec((TOKENS, D), jnp.bfloat16),
                    spec((D, F), jnp.bfloat16), spec((F,), jnp.float32))
    return build


def _moe_gemm(spec):
    t = _tiles("moe_grouped_gemm", "pallas",
               {"e": E, "c": CAPACITY, "d": D, "f": F})
    return (lambda buf, w, sizes: kops.moe_gemm(
        buf, w, sizes, interpret=False, **t)), (
        spec((E, CAPACITY, D), jnp.bfloat16), spec((E, D, F), jnp.bfloat16),
        spec((E,), jnp.int32))


def _lut(spec):
    t = _tiles("activation", "pallas", {"rows": E * CAPACITY * F // 128})
    return (lambda x: kops.lut_activation(
        x, "gelu", interpret=False, **t)), (
        spec((E, CAPACITY, F), jnp.float32),)


def _moe_fused(spec):
    t = _tiles("moe_ffn", "pallas_fused",
               {"e": E, "c": CAPACITY, "d": D, "f": F, "t": GROUP})

    def fused(x, w1, b1, w2, b2, logits):
        r = R.route(logits, TOP_K, CAPACITY)
        return kops.fused_moe_ffn(
            x, {"w1": w1, "b1": b1, "w2": w2, "b2": b2}, r.expert, r.gate,
            r.position, r.valid, R.dispatch_counts(r, E), kind="gelu",
            capacity=CAPACITY, use_lut=True, interpret=False, **t)

    return fused, (spec((GROUP, D), jnp.bfloat16),
                   spec((E, D, F), jnp.bfloat16), spec((E, F), jnp.float32),
                   spec((E, F, D), jnp.bfloat16), spec((E, D), jnp.float32),
                   spec((GROUP, E), jnp.float32))


KERNELS = {
    "moe_fused": _moe_fused,
    "flash_attention": _flash,
    "unified_linear_gelu": _linear(use_lut=False),
    "unified_linear_gelu_lut": _linear(use_lut=True),
    "moe_gemm": _moe_gemm,
    "lut_activation": _lut,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_to_mosaic_for_v5e(name, one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = KERNELS[name](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_default_lut_activation_compiles_without_gather_for_v5e(one_chip):
    """The ``activation`` op under the default policy (impl ``lut``) at the
    MoE wave's hidden buffer (4 groups × 8 slots × capacity × f): the TPU
    program evaluates the table's entry in place, with no gather."""
    x = jax.ShapeDtypeStruct((4, 8, CAPACITY, F), jnp.float32,
                             sharding=one_chip)
    ops.reset_dispatch_report()
    with ops.use_policy(ops.ComputePolicy()):
        compiled = jax.jit(
            lambda v: ops.apply_activation(v, "gelu")).lower(x).compile()
    assert ops.dispatch_report()["activation"]["hits"] == {"lut": 1}
    hlo = compiled.as_text()
    assert not re.search(r"\sgather\(", hlo)     # no gather instruction
    assert "tpu_custom_call" not in hlo
