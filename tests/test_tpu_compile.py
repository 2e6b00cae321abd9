"""The wire kernels compile for a TPU v5e at qwen2-0.5b leaf shapes.

Compile only, no chip: the TPU compiler is installed with jax and compiles
for a described ``v5e:2x2`` topology.  It refuses what interpret mode
(every other kernel test) accepts: unsupported vector layouts and casts,
unsigned reductions, scalar stores to VMEM, block shapes the tiling rules
forbid, and blocks that overflow VMEM.  Each case compiles one kernel at
one leaf size of qwen2-0.5b, the 136,134,656-entry tied embedding among
them, on one chip of the topology.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import
this file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compress import TopK, wire
from repro.kernels import ops, pack_codes, qr_pack, quantize
from repro.kernels import select_slots, topk_compress

#: qwen2-0.5b leaf sizes: the tied embedding (the largest leaf), the q
#: projection and a bias
SIZES = {"embed": 151_936 * 896, "q": 896 * 896, "bias": 896}
R = 4                                   # Q_r level bits
B = 1 + R                               # packed code width


def _k(n: int) -> int:
    return TopK(0.05)._k(n)


# name -> n -> (function, [(shape, dtype), ...])
KERNELS = {
    "threshold_bits": lambda n: (
        lambda x: topk_compress.threshold_bits(x, _k(n)),
        [((n,), jnp.bfloat16)]),
    "topk_mask": lambda n: (
        lambda x: topk_compress.topk_mask(x, _k(n)),
        [((n,), jnp.bfloat16)]),
    "compact_slots": lambda n: (
        lambda x, t: select_slots.compact_slots(x, t, _k(n)),
        [((n,), jnp.bfloat16), ((), jnp.uint32)]),
    "compact_code_slots": lambda n: (
        lambda x, u, nrm, t: select_slots.compact_code_slots(
            x, u, nrm, t, R, _k(n)),
        [((n,), jnp.float32), ((n,), jnp.float32), ((), jnp.float32),
         ((), jnp.uint32)]),
    "l2_norm": lambda n: (quantize.l2_norm, [((n,), jnp.bfloat16)]),
    "quantize_qr_with_uniforms": lambda n: (
        lambda x, u: quantize.quantize_qr_with_uniforms(x, R, u),
        [((n,), jnp.bfloat16), ((n,), jnp.float32)]),
    "pack_codes": lambda n: (
        lambda c: pack_codes.pack_codes(c, B), [((n,), jnp.uint32)]),
    "unpack_codes": lambda n: (
        lambda w: pack_codes.unpack_codes(w, B, n),
        [((-(-n // 32) * B,), jnp.uint32)]),
    "quantize_pack_with_uniforms": lambda n: (
        lambda x, u, nrm: qr_pack.quantize_pack_with_uniforms(x, R, u, nrm),
        [((n,), jnp.bfloat16), ((n,), jnp.float32), ((), jnp.float32)]),
    "expand_slots": lambda n: (
        lambda i, v: select_slots.expand_slots(i, v, n),
        [((_k(n),), jnp.uint32), ((_k(n),), jnp.bfloat16)]),
}


#: kernel -> the names its ``pallas_call``s give their instructions, so a
#: device trace shows each apart
KERNEL_NAMES = {
    "threshold_bits": ("topk_count",),
    "topk_mask": ("topk_count", "topk_mask"),
    "compact_slots": ("select_slots",),
    "compact_code_slots": ("select_code_slots",),
    "l2_norm": ("qr_sumsq",),
    "quantize_qr_with_uniforms": ("qr_sumsq", "qr_quantize"),
    "pack_codes": ("pack_codes",),
    "unpack_codes": ("unpack_codes",),
    "quantize_pack_with_uniforms": ("qr_pack",),
    "expand_slots": ("expand_slots",),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache; keep it off while this module compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, avals, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in avals]
    return jax.jit(fn).lower(*args).compile()


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _named_kernels(compiled) -> set:
    """The names of the compiled program's Pallas kernel instructions."""
    return set(re.findall(r"%([a-z_]+?)(?:\.\d+)? = [^\n]*tpu_custom_call",
                          compiled.as_text()))


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, size):
    n = SIZES[size]
    fn, avals = KERNELS[kernel](n)
    compiled = _compile(fn, avals, one_chip)
    assert _custom_calls(compiled) >= 1
    assert _named_kernels(compiled) == set(KERNEL_NAMES[kernel])


def test_vmapped_clients_compile_for_v5e(one_chip):
    """Client-vmapped threshold + compaction: under ``vmap`` a kernel's
    SMEM operands gain a batch dim, which must not break their block
    shape (1-D scalar operands did)."""
    n = SIZES["q"]

    def enc(x):
        t = topk_compress.threshold_bits(x, _k(n))
        return select_slots.compact_slots(x, t, _k(n))

    compiled = _compile(jax.vmap(enc), [((2, n), jnp.bfloat16)], one_chip)
    assert _custom_calls(compiled) >= 2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_vmapped_expand_compiles_for_v5e(one_chip, size, dtype):
    """The decode's slot expand for 2 clients under ``vmap``, at each leaf
    size, with bf16 (``topk``) and f32 (``topk_qr``) values."""
    n = SIZES[size]
    k = _k(n)
    compiled = _compile(
        jax.vmap(lambda i, v: select_slots.expand_slots(i, v, n)),
        [((2, k), jnp.uint32), ((2, k), dtype)], one_chip)
    assert _named_kernels(compiled) == {"expand_slots"}


def test_wire_decode_groups_leaves_by_shape(one_chip):
    """The packed ``topk`` decode of two clients' two-layer trees on the
    Pallas path expands one kernel group per leaf shape."""
    tree = {f"layer_{i}": {"q": jnp.zeros((896, 896), jnp.bfloat16),
                           "b": jnp.zeros((896,), jnp.bfloat16)}
            for i in range(2)}
    payload = jax.eval_shape(lambda t: wire.encode(TopK(0.05), t)[0], tree)
    bufs, treedef = jax.tree_util.tree_flatten(payload)
    avals = [((2,) + b.shape, b.dtype) for b in bufs]

    def decode(*bufs):
        return jax.vmap(wire.decode)(jax.tree_util.tree_unflatten(
            treedef, bufs))

    prev = ops.get_backend()
    ops.set_backend("pallas")
    try:
        compiled = _compile(decode, avals, one_chip)
    finally:
        ops.set_backend(prev)
    assert _custom_calls(compiled) == 2
    assert _named_kernels(compiled) == {"expand_slots"}


def test_wire_encode_groups_leaves_by_shape(one_chip):
    """The packed ``topk`` encode of a two-layer tree on the Pallas path
    traces one kernel group per leaf shape, not one per leaf."""
    tree = {f"layer_{i}": {"q": jnp.zeros((896, 896), jnp.bfloat16),
                           "b": jnp.zeros((896,), jnp.bfloat16)}
            for i in range(2)}
    avals = [((2,) + l.shape, l.dtype) for l in jax.tree_util.tree_leaves(tree)]
    treedef = jax.tree_util.tree_structure(tree)

    def encode(*leaves):
        stacked = jax.tree_util.tree_unflatten(treedef, leaves)
        return jax.vmap(lambda t: wire.encode(TopK(0.05), t)[0])(stacked)

    prev = ops.get_backend()
    ops.set_backend("pallas")
    try:
        compiled = _compile(encode, avals, one_chip)
    finally:
        ops.set_backend(prev)
    # 2 shapes x (8 threshold passes + 1 compaction), whatever the depth
    assert _custom_calls(compiled) == 2 * (len(topk_compress.DIGITS) + 1)
    assert _named_kernels(compiled) == {"topk_count", "select_slots"}
