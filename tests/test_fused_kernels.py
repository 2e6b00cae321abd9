"""Fused select+pack encode kernels (DESIGN.md §8 "fused encode kernels").

Four contracts:

1. **Threshold equivalence** — the bit-pattern binary search that now
   drives ``ref.topk_mask`` / ``topk_mask_dynamic`` equals the naive
   ``lax.top_k`` threshold (the pre-fusion implementation), and the Pallas
   radix walk (``topk_compress.threshold_bits``) returns the same bit
   pattern in interpret mode.
2. **Kernel/oracle parity** — ``select_slots`` and ``qr_pack`` kernels in
   interpret mode are bitwise equal to their ``ref.py`` oracles at the
   edges the codec meets: k=0, k=n, cap±1 tie overflow, r=1, r=MAX_R,
   bf16 leaves, odd/non-block-multiple sizes.
3. **Dispatch parity** — ``ops.topk_slots`` / ``quantize_pack`` /
   ``topk_qr_slots`` agree between the ``ref`` and ``interpret`` backends,
   including under ``vmap`` (the client axis).
4. **Wire integration** — ``wire.encode`` payloads are identical across
   backends, and ``decode(encode(x))`` still equals the transform output.

Everything runs on CPU (interpret mode executes the kernel bodies with
jnp semantics); the CI matrix runs this file on both the single-device
and the 8-host-device legs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import Compose, QuantQr, TopK, wire
from repro.kernels import ops
from repro.kernels import qr_pack
from repro.kernels import ref
from repro.kernels import select_slots as sel
from repro.kernels import topk_compress as tc

SIZES = [33, 67, 128, 1024, 1030, 5000]


@pytest.fixture(autouse=True)
def _ref_backend():
    ops.set_backend("ref")
    yield
    ops.set_backend("auto")


def _vec(n, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=n).astype(np.float32)).astype(dtype)


def _uni(n, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(size=n).astype(np.float32))


def _naive_topk_mask(x, k):
    """The pre-fusion oracle: lax.top_k threshold semantics."""
    if k >= x.size:
        return x
    mag = jnp.abs(x)
    kth = jax.lax.top_k(mag, k)[0][k - 1]
    return jnp.where(mag >= kth, x, jnp.zeros_like(x))


# --------------------------------------------------------------------------- #
# 1. threshold equivalence
# --------------------------------------------------------------------------- #

class TestThreshold:
    @pytest.mark.parametrize("n", SIZES)
    def test_mask_equals_naive_topk(self, n):
        x = _vec(n, seed=n)
        for k in (1, 2, max(1, n // 10), n // 2, n - 1, n):
            assert (ref.topk_mask(x, k) == _naive_topk_mask(x, k)).all(), k

    def test_mask_bf16(self):
        x = _vec(1030, seed=3, dtype=jnp.bfloat16)
        out = ref.topk_mask(x, 100)
        ref_out = _naive_topk_mask(x, 100)
        assert out.dtype == jnp.bfloat16
        assert (out == ref_out).all()

    def test_dynamic_matches_static(self):
        x = _vec(515, seed=5)
        for k in (1, 50, 514, 515, 600):
            got = ref.topk_mask_dynamic(x, jnp.asarray(k, jnp.int32))
            assert (got == ref.topk_mask(x, min(k, x.size))).all(), k

    def test_dynamic_vmap(self):
        xs = jnp.stack([_vec(256, seed=s) for s in range(4)])
        ks = jnp.asarray([1, 16, 128, 256], jnp.int32)
        got = jax.vmap(ref.topk_mask_dynamic)(xs, ks)
        for i in range(4):
            assert (got[i] == ref.topk_mask(xs[i], int(ks[i]))).all()

    @pytest.mark.parametrize("n", [33, 1030])
    def test_radix_kernel_same_bits(self, n):
        x = _vec(n, seed=n + 1)
        for k in (1, n // 3, n - 1):
            t_ref = ref.topk_threshold_bits(x, k)
            t_pal = tc.threshold_bits(x, k, interpret=True)
            assert int(t_ref) == int(t_pal), (n, k)

    def test_k_edges(self):
        x = _vec(100, seed=9)
        # k = 0: all-ones pattern, empty support
        assert int(ref.topk_threshold_bits(x, 0)) == 0xFFFFFFFF
        assert int(tc.threshold_bits(x, 0, interpret=True)) == 0xFFFFFFFF
        # k >= n: every entry kept (bits >= t for all) on both paths
        bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.uint32)
        for t in (ref.topk_threshold_bits(x, 100),
                  tc.threshold_bits(x, 100, interpret=True)):
            assert bool(jnp.all(bits >= t))

    def test_all_zero_input(self):
        x = jnp.zeros(64, jnp.float32)
        assert (ref.topk_mask(x, 7) == x).all()
        _, _, support = ref.topk_slots(x, 7, 7)
        assert int(support.sum()) == 0


# --------------------------------------------------------------------------- #
# 2. kernel/oracle parity (interpret mode)
# --------------------------------------------------------------------------- #

class TestCompactSlots:
    @pytest.mark.parametrize("n", SIZES)
    def test_parity(self, n):
        x = _vec(n, seed=n + 2)
        for k in (1, max(1, n // 10), n // 2):
            idx_r, vals_r, _ = ref.topk_slots(x, k, k)
            t = tc.threshold_bits(x, k, interpret=True)
            idx_p, vals_p = sel.compact_slots(x, t, k, interpret=True)
            assert (idx_r == idx_p.astype(jnp.uint32)).all(), (n, k)
            assert (vals_r == vals_p).all(), (n, k)

    @pytest.mark.parametrize("cap_delta", [-1, 0, 1])
    def test_tie_overflow_keeps_lowest_cap(self, cap_delta):
        x = jnp.ones(50, jnp.float32)            # 50-way tie at the threshold
        k, cap = 10, 10 + cap_delta
        idx_r, vals_r, support = ref.topk_slots(x, k, cap)
        t = tc.threshold_bits(x, k, interpret=True)
        idx_p, vals_p = sel.compact_slots(x, t, cap, interpret=True)
        assert (idx_r == jnp.arange(cap, dtype=jnp.uint32)).all()
        assert (idx_r == idx_p.astype(jnp.uint32)).all()
        assert (vals_r == vals_p).all()
        assert int(support.sum()) == 50          # accounting sees every tie

    def test_underfull_support_sentinels(self):
        x = jnp.zeros(100, jnp.float32).at[7].set(3.0).at[42].set(-1.5)
        idx_r, vals_r, _ = ref.topk_slots(x, 10, 10)
        t = tc.threshold_bits(x, 10, interpret=True)
        idx_p, vals_p = sel.compact_slots(x, t, 10, interpret=True)
        assert (idx_r == idx_p.astype(jnp.uint32)).all()
        assert (vals_r == vals_p).all()
        assert idx_r[0] == 7 and idx_r[1] == 42
        assert (idx_r[2:] == 100).all() and (vals_r[2:] == 0).all()

    def test_cap_beyond_block_boundary(self):
        # cap > one (1, 128) output tile: exercises the padded slot axis
        n = 4000
        x = _vec(n, seed=11)
        k = 300
        idx_r, vals_r, _ = ref.topk_slots(x, k, k)
        t = tc.threshold_bits(x, k, interpret=True)
        idx_p, vals_p = sel.compact_slots(x, t, k, interpret=True)
        assert (idx_r == idx_p.astype(jnp.uint32)).all()
        assert (vals_r == vals_p).all()

    def test_multi_block_parity(self):
        # 2.5 grid blocks of 65,536 entries: survivors cross output blocks
        # mid-grid and the threshold walk accumulates over several steps
        n = 5 * 65536 // 2 + 77
        x = _vec(n, seed=13)
        k = n // 7
        t = tc.threshold_bits(x, k, interpret=True)
        assert int(t) == int(ref.topk_threshold_bits(x, k))
        idx_r, vals_r, _ = ref.topk_slots(x, k, k)
        idx_p, vals_p = sel.compact_slots(x, t, k, interpret=True)
        assert (idx_r == idx_p.astype(jnp.uint32)).all()
        assert (vals_r == vals_p).all()

    def test_last_block_spill_is_flushed(self):
        # the last grid block's survivors straddle an output block: what it
        # spills past that block must still reach the output
        e = 512 * 128
        x = jnp.zeros(2 * e, jnp.float32).at[:e // 2].set(2.0).at[e:].set(1.0)
        cap = e // 2 + e
        t = jax.lax.bitcast_convert_type(jnp.float32(1.0), jnp.uint32)
        idx_r, vals_r, _ = ref.topk_slots(x, cap, cap)
        idx_p, vals_p = sel.compact_slots(x, t, cap, interpret=True)
        assert (idx_r == idx_p.astype(jnp.uint32)).all()
        assert (vals_r == vals_p).all()


class TestQrPack:
    @pytest.mark.parametrize("n", [33, 1024, 1030, 5000])
    @pytest.mark.parametrize("r", [1, 4, wire.MAX_R])
    def test_parity(self, n, r):
        x, u = _vec(n, seed=n + 3), _uni(n, seed=n + 4)
        norm = jnp.sqrt(jnp.sum(x * x))
        w_ref = ref.quantize_pack_with_uniforms(x, r, u, norm)
        w_pal = qr_pack.quantize_pack_with_uniforms(
            x, r, u, norm, interpret=True)
        assert w_ref.shape == (-(-n // 32) * (1 + r),)
        assert (w_ref == w_pal).all()

    def test_matches_unfused_codes(self):
        x, u = _vec(1030, seed=7), _uni(1030, seed=8)
        norm = jnp.sqrt(jnp.sum(x * x))
        codes = ref.qr_codes_with_uniforms(x, 4, u, norm)
        assert (ref.quantize_pack_with_uniforms(x, 4, u, norm)
                == ref.pack_codes(codes, 5)).all()

    def test_saturation(self):
        # one dominant coordinate reaches the top level 2**r -> clamps
        x = jnp.zeros(64, jnp.float32).at[5].set(10.0)
        u = jnp.zeros(64, jnp.float32)
        norm = jnp.sqrt(jnp.sum(x * x))
        for r in (1, 4):
            w = qr_pack.quantize_pack_with_uniforms(x, r, u, norm,
                                                    interpret=True)
            codes = ref.unpack_codes(w, 1 + r, 64)
            assert int(codes[5]) == 2 ** r - 1
            assert (ref.quantize_pack_with_uniforms(x, r, u, norm) == w).all()

    def test_zero_norm(self):
        x = jnp.zeros(40, jnp.float32)
        u = _uni(40, seed=12)
        w = qr_pack.quantize_pack_with_uniforms(x, 4, u, jnp.float32(0.0),
                                                interpret=True)
        assert (w == 0).all()


class TestCompactCodeSlots:
    # 163,917 entries: 2.5 grid blocks, so slots cross output blocks
    @pytest.mark.parametrize("n", [67, 1030, 3000, 163917])
    @pytest.mark.parametrize("r", [1, 4, wire.MAX_R])
    def test_parity(self, n, r):
        x, u = _vec(n, seed=n + 5), _uni(n, seed=n + 6)
        k = cap = max(1, n // 10)
        idx_r, words_r, norm_r, _ = ref.topk_qr_slots(x, k, cap, r, u)
        t = tc.threshold_bits(x, k, interpret=True)
        bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.uint32)
        masked = jnp.where(bits >= t, x, 0.0)
        norm = jnp.sqrt(jnp.sum(masked * masked))
        idx_p, codes_p = sel.compact_code_slots(x, u, norm, t, r, cap,
                                                interpret=True)
        assert (idx_r == idx_p.astype(jnp.uint32)).all()
        assert (words_r == ref.pack_codes(codes_p, 1 + r)).all()


# --------------------------------------------------------------------------- #
# 3. dispatch parity: ref vs interpret backends, incl. vmap
# --------------------------------------------------------------------------- #

class TestOpsParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_topk_slots(self, dtype):
        x = _vec(1030, seed=21, dtype=dtype)
        ops.set_backend("interpret")
        i1, v1, s1 = ops.topk_slots(x, 100, 100)
        ops.set_backend("ref")
        i2, v2, s2 = ops.topk_slots(x, 100, 100)
        assert v1.dtype == v2.dtype == dtype
        assert (i1 == i2).all() and (v1 == v2).all() and (s1 == s2).all()

    def test_quantize_pack(self):
        x = _vec(1030, seed=22)
        key = jax.random.PRNGKey(5)
        ops.set_backend("interpret")
        w1, n1 = ops.quantize_pack(x, 4, key)
        ops.set_backend("ref")
        w2, n2 = ops.quantize_pack(x, 4, key)
        # the norms come from differently-ordered reductions; codes agree
        # whenever the norms do
        np.testing.assert_allclose(float(n1), float(n2), rtol=1e-6)
        if float(n1) == float(n2):
            assert (w1 == w2).all()

    def test_topk_qr_slots(self):
        x = _vec(2050, seed=23)
        key = jax.random.PRNGKey(6)
        ops.set_backend("interpret")
        i1, w1, n1, s1 = ops.topk_qr_slots(x, 200, 200, 4, key)
        ops.set_backend("ref")
        i2, w2, n2, s2 = ops.topk_qr_slots(x, 200, 200, 4, key)
        assert (i1 == i2).all() and (s1 == s2).all()
        np.testing.assert_allclose(float(n1), float(n2), rtol=1e-6)
        if float(n1) == float(n2):
            assert (w1 == w2).all()

    def test_topk_slots_vmap(self):
        xs = jnp.stack([_vec(515, seed=30 + s) for s in range(4)])
        out = {}
        for backend in ("interpret", "ref"):
            ops.set_backend(backend)
            out[backend] = jax.vmap(lambda x: ops.topk_slots(x, 50, 50))(xs)
        for a, b in zip(out["interpret"], out["ref"]):
            assert (a == b).all()

    def test_traced_k_routes_to_ref(self):
        # per-client densities: traced k must not hit the static kernels
        ops.set_backend("interpret")
        xs = jnp.stack([_vec(256, seed=40 + s) for s in range(3)])
        ks = jnp.asarray([8, 64, 256], jnp.int32)
        iv, vv, sv = jax.vmap(
            lambda x, k: ops.topk_slots(x, k, 256))(xs, ks)
        ops.set_backend("ref")
        for i in range(3):
            ir, vr, sr = ops.topk_slots(xs[i], int(ks[i]), 256)
            assert (iv[i] == ir).all() and (vv[i] == vr).all()


# --------------------------------------------------------------------------- #
# 4. wire integration: payload parity across backends, decode bit-identity
# --------------------------------------------------------------------------- #

WIRE_COMPS = [
    TopK(density=0.1),
    TopK(density=0.1, scope="global"),
    Compose(TopK(0.1), QuantQr(4)),
]


def _tree():
    km = jax.random.PRNGKey(0)
    return {
        "w": jax.random.normal(km, (33,)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (8, 8)),
        "v": jax.random.normal(jax.random.PRNGKey(2), (67,)),
    }


class TestWireBackendParity:
    @pytest.mark.parametrize("comp", WIRE_COMPS,
                             ids=lambda c: type(c).__name__ + getattr(
                                 c, "scope", getattr(
                                     getattr(c, "first", None), "scope", "")))
    def test_payload_bitwise_equal(self, comp):
        tree, key = _tree(), jax.random.PRNGKey(7)
        ops.set_backend("ref")
        p_ref, rep_ref = jax.jit(
            lambda t, k: wire.encode(comp, t, k))(tree, key)
        ops.set_backend("interpret")
        p_int, rep_int = jax.jit(
            lambda t, k: wire.encode(comp, t, k))(tree, key)
        for unit_r, unit_i in zip(p_ref.data, p_int.data):
            for buf_r, buf_i in zip(unit_r, unit_i):
                if buf_r.dtype == jnp.float32 and buf_r.ndim == 0:
                    np.testing.assert_allclose(       # the per-unit norm
                        float(buf_r), float(buf_i), rtol=1e-6)
                else:
                    assert (buf_r == buf_i).all()
        assert float(rep_ref.total_bits) == float(rep_int.total_bits)

    def test_decode_roundtrip_interpret(self):
        tree, key = _tree(), jax.random.PRNGKey(8)
        comp = TopK(density=0.1)
        ops.set_backend("interpret")
        payload, _ = jax.jit(lambda t, k: wire.encode(comp, t, k))(tree, key)
        out = wire.decode(payload)
        expect, _ = comp.compress(tree)
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(expect)):
            assert (a == b).all()


class TestPayloadNbytesMemo:
    def test_cached_and_correct(self):
        tree = _tree()
        comp = TopK(density=0.1)
        wire._NBYTES_CACHE.clear()
        n1 = wire.payload_nbytes(comp, tree)
        assert len(wire._NBYTES_CACHE) == 1
        payload, _ = wire.encode(comp, tree, jax.random.PRNGKey(0))
        assert n1 == payload.nbytes
        # second call: pure dict hit (no new entries, same answer)
        assert wire.payload_nbytes(comp, tree) == n1
        assert len(wire._NBYTES_CACHE) == 1
        # a different static config gets its own entry
        wire.payload_nbytes(TopK(density=0.2), tree)
        assert len(wire._NBYTES_CACHE) == 2

    def test_key_separates_dtypes(self):
        tree32 = {"w": jnp.ones((64,), jnp.float32)}
        tree16 = {"w": jnp.ones((64,), jnp.bfloat16)}
        comp = TopK(density=0.5)
        assert (wire.payload_nbytes(comp, tree32)
                != wire.payload_nbytes(comp, tree16))
