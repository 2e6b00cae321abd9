#!/usr/bin/env python3
"""Chip smoke: FedComLoc's packed-uplink round on a TPU at qwen2-0.5b widths.

Drives the main path once through the entry points a user calls and
checks what comes out.  This is a smoke run, not a measurement: the wall
times it prints are those of a cold process, compilation included.

  python chip_smoke.py            # one chip: dispatch, kernel parity, rounds
  python chip_smoke.py --chips 4  # four chips: the mesh phases only

Phases on one chip:

* dispatch — ``repro.kernels.ops`` must pick the Pallas kernels;
* kernels — every wire kernel, at each distinct qwen2-0.5b leaf size
  (the 136,134,656-entry tied embedding among them), against the jnp
  reference on the same chip, then a whole seeded qwen2-0.5b-shaped
  tree through the ``topk`` wire codec with each backend;
* rounds — FedComLoc-Com (Algorithm 1) with a ``TopK(0.05)`` uplink
  packed into real payloads (``wire="packed"``), at the published
  widths and depth in bf16 with weights from ``--seed``, for 3 rounds
  through ``server.run_federated``'s fused engine, then 3 more on the
  same shapes, which must compile nothing.

With ``--chips 4`` it runs only the two mesh phases, each against a
one-chip run of the same job in the same process: (a) the round job on
the composed ``clients x model`` mesh (DESIGN.md §9) and (b) the paper's
cross-device MLP job on a 4-way ``clients`` mesh (DESIGN.md §6).

Without a TPU it exits non-zero and prints no result.  The last line of a
successful run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
DENSITY = 0.05
ROUNDS = 3
SEQ_LEN = 128
SEQS_PER_CLIENT = 8
#: the round phase's population and cohort, cut from 2 and 2: at cohort 2
#: the fused 3-round program needs 16.28 GB (arguments, outputs and
#: temporaries, per the v5e compiler's memory analysis) of the chip's 16 GiB
N_CLIENTS = 2
COHORT = 1
#: the four-chip composed-mesh phase's model.  Depth is cut because the
#: phase compiles the round twice, for one chip and for the mesh.  Leaves
#: are float32 because its bits are compared at the 1e-4 tie tolerance:
#: bf16 leaves tie at the TopK threshold in ~1% of k (8 mantissa bits), and
#: the mesh's reordered float sums move those ties.
MESH_MODEL = {"n_layers": 2, "dtype": np.float32}
#: the big-model sweep's tie tolerance for bits across mesh layouts and its
#: trajectory rule for losses (benchmarks/big_model.py)
BITS_RTOL = 1e-4
LOSS_RTOL = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseFailed(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


# --------------------------------------------------------------------------- #
# kernel phase
# --------------------------------------------------------------------------- #

def leaf_sizes(params) -> dict:
    """Distinct flat leaf sizes of a parameter tree -> how many leaves."""
    import jax
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(params):
        out[leaf.size] = out.get(leaf.size, 0) + 1
    return out


def _kernel_checks(x, u, k: int, r: int, b: int, codes):
    """Every wire kernel on ``x`` next to its jnp reference; returns a dict
    of mismatch counts (plus the support facts the threshold must meet)."""
    import jax.numpy as jnp
    from repro.kernels import pack_codes, qr_pack, quantize, ref
    from repro.kernels import select_slots, topk_compress

    n = x.size
    t = topk_compress.threshold_bits(x, k)
    bits = ref._mag_bits(x)
    out = {
        "threshold": (t != ref.topk_threshold_bits(x, k)).astype(jnp.int32),
        "support": jnp.sum(bits >= t),
        "above": jnp.sum(bits > t),
        "topk_mask": jnp.sum(topk_compress.topk_mask(x, k)
                             != ref.topk_mask(x, k)),
    }
    idx, vals = select_slots.compact_slots(x, t, k)
    ridx, rvals, _ = ref.topk_slots(x, k, k)
    out["compact_slots"] = (jnp.sum(idx.astype(jnp.uint32) != ridx)
                            + jnp.sum(vals != rvals))
    # the decode's placement of those slots, at f32 (topk_qr) and bf16
    # (topk) values, and of a masked client's all-zero payload
    out["expand_slots"] = sum(
        jnp.sum(select_slots.expand_slots(ridx, v, n)
                != ref.expand_slots(ridx, v, n))
        for v in (rvals, rvals.astype(jnp.bfloat16)))
    zeros = jnp.zeros_like(ridx)
    out["expand_slots"] += jnp.sum(
        select_slots.expand_slots(zeros, jnp.zeros_like(rvals), n) != 0)
    norm = quantize.l2_norm(x)
    out["l2_norm_rel"] = jnp.abs(norm - jnp.sqrt(jnp.sum(x * x))) / norm
    q = quantize.quantize_qr_with_uniforms(x, r, u)
    qr = ref.quantize_qr_with_uniforms(x, r, u)
    # the norms differ by summation order, so every value moves by ~1 ulp
    # of relative scale; count the entries that moved by more
    out["quantize_qr"] = jnp.sum(jnp.abs(q - qr) > 1e-5 * jnp.abs(qr) + 1e-30)
    words = qr_pack.quantize_pack_with_uniforms(x, r, u, norm)
    rwords = ref.quantize_pack_with_uniforms(x, r, u, norm)
    out["qr_pack"] = jnp.sum(ref.unpack_codes(words, 1 + r, n)
                             != ref.unpack_codes(rwords, 1 + r, n))
    keep = bits >= t
    masked = jnp.where(keep, x, 0.0)
    mnorm = jnp.sqrt(jnp.sum(masked * masked))
    cidx, ccodes = select_slots.compact_code_slots(x, u, mnorm, t, r, k)
    rcodes = ref.qr_codes_with_uniforms(masked, r, u, mnorm)
    safe = jnp.clip(ridx.astype(jnp.int32), 0, n - 1)
    rkept = jnp.where(ridx < n, rcodes[safe], jnp.uint32(0))
    out["compact_code_slots_idx"] = jnp.sum(cidx.astype(jnp.uint32) != ridx)
    out["compact_code_slots"] = jnp.sum(ccodes != rkept)
    w = pack_codes.pack_codes(codes, b)
    out["pack_codes"] = jnp.sum(w != ref.pack_codes(codes, b))
    out["unpack_codes"] = jnp.sum(pack_codes.unpack_codes(w, b, n) != codes)
    return out


#: Q_r codes go through an f32 division in both the kernel and the jnp
#: reference; XLA and Mosaic may round it one ulp apart, which moves a
#: stochastic-rounding comparison sitting on a level boundary.  Such flips
#: are allowed at this rate and no more (integer and TopK paths are exact).
QR_FLIP_RATE = 1e-5

#: one output line per kernel: (its key in _kernel_checks' result, what the
#: line counts)
KERNEL_LINES = (
    ("threshold", "threshold_bits: 1 where the bit pattern differs"),
    ("topk_mask", "topk_mask: entries differing"),
    ("compact_slots", "compact_slots: slots differing"),
    ("expand_slots", "expand_slots: entries differing"),
    ("compact_code_slots_idx", "compact_code_slots: slot indices differing"),
    ("compact_code_slots", "compact_code_slots: Q_r code flips"),
    ("l2_norm_rel", "l2_norm: relative difference"),
    ("quantize_qr", "quantize_qr_with_uniforms: Q_r code flips"),
    ("qr_pack", "quantize_pack_with_uniforms: Q_r code flips"),
    ("pack_codes", "pack_codes: words differing"),
    ("unpack_codes", "unpack_codes: codes differing"),
)


def kernel_phase(seed: int, params) -> None:
    import jax
    import jax.numpy as jnp
    from repro.compress import TopK, wire
    from repro.kernels import ops

    comp = TopK(DENSITY)
    r, b = 4, 5
    table: dict = {}
    for n, count in sorted(leaf_sizes(params).items()):
        kx, ku, kc = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(seed), n), 3)
        x = jax.random.normal(kx, (n,), jnp.float32)
        u = jax.random.uniform(ku, (n,), jnp.float32)
        codes = jax.random.randint(kc, (n,), 0, 2 ** b).astype(jnp.uint32)
        k = comp._k(n)
        t0 = time.time()
        res = jax.jit(_kernel_checks, static_argnums=(2, 3, 4))(
            x, u, k, r, b, codes)
        res = {key: float(v) for key, v in res.items()}
        flips = max(1.0, QR_FLIP_RATE * n)
        exact = ("threshold", "topk_mask", "compact_slots", "expand_slots",
                 "compact_code_slots_idx", "pack_codes", "unpack_codes")
        for name in exact:
            check(res[name] == 0, f"n={n}: {name} differs from the "
                                  f"reference in {res[name]:.0f} entries")
        check(res["above"] < k <= res["support"],
              f"n={n}: threshold is not the k-th magnitude "
              f"(k={k}, above={res['above']}, support={res['support']})")
        check(res["l2_norm_rel"] < 1e-5,
              f"n={n}: l2_norm rel diff {res['l2_norm_rel']}")
        for name in ("quantize_qr", "qr_pack", "compact_code_slots"):
            check(res[name] <= flips, f"n={n}: {name} differs in "
                                      f"{res[name]:.0f} entries")
        log(f"kernels n={n} ({count} leaves): k={k}, support "
            f"{res['support']:.0f} (above the threshold "
            f"{res['above']:.0f}), every check passed; {time.time() - t0:.1f}s")
        for name, v in res.items():
            table.setdefault(name, []).append(f"{v:.3g}")
    sizes = ", ".join(str(n) for n in sorted(leaf_sizes(params)))
    log(f"kernels: Pallas vs jnp reference on the chip at n = {sizes}")
    for name, what in KERNEL_LINES:
        log(f"kernel {what} by size: {', '.join(table[name])}")

    # the main path's encode on a whole seeded model-shaped tree
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    tree = jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(key, l.shape, jnp.float32).astype(l.dtype)
        for key, l in zip(keys, leaves)])
    encoded = {}
    for backend in ("pallas", "ref"):
        ops.set_backend(backend)
        t0 = time.time()
        payload, rep = jax.jit(lambda t: wire.encode(comp, t))(tree)
        jax.block_until_ready(payload.data)
        encoded[backend] = (payload, rep, time.time() - t0)
    ops.set_backend("auto")
    (pp, rp, tp), (pr, rr, tr) = encoded["pallas"], encoded["ref"]
    bufs_equal = all(
        bool(jnp.array_equal(a, c)) for a, c in zip(
            jax.tree_util.tree_leaves(pp.data),
            jax.tree_util.tree_leaves(pr.data)))
    check(bufs_equal, "tree encode: Pallas payload differs from the reference")
    check(float(rp.total_bits) == float(rr.total_bits),
          "tree encode: bit reports differ")
    dec = jax.jit(wire.decode)
    same = jax.tree_util.tree_map(
        lambda a, c: jnp.array_equal(a, c), dec(pp), dec(pr))
    check(all(bool(v) for v in jax.tree_util.tree_leaves(same)),
          "tree encode: decoded trees differ")
    support = float(rp.index_bits) / 32
    slots = sum(pp.spec.caps)
    log(f"kernels tree encode ({len(leaves)} leaves, bf16): payload, bit "
        f"report and decoded tree identical to the reference; support "
        f"{support:.0f} vs {slots} slots (k per leaf; bf16 magnitude ties "
        f"at the threshold beyond k stay out of the slots); "
        f"{pp.nbytes} B per client; first call pallas {tp:.1f}s, "
        f"ref {tr:.1f}s")


# --------------------------------------------------------------------------- #
# round phase
# --------------------------------------------------------------------------- #

def token_data(vocab: int, n_clients: int, seed: int):
    """Per-client token streams from the seed (benchmarks/big_model.py)."""
    from repro.core import fed_data
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, (n_clients * SEQS_PER_CLIENT, SEQ_LEN)
                     ).astype(np.int32)
    y = np.zeros((n_clients * SEQS_PER_CLIENT,), np.float32)
    parts = [np.arange(i * SEQS_PER_CLIENT, (i + 1) * SEQS_PER_CLIENT)
             for i in range(n_clients)]
    return fed_data.from_numpy_partition(x, y, parts)


def build_job(seed: int, n_clients: int, cohort: int, **model_changes):
    """The round phase's job: FedComLoc-Com + TopK(0.05), packed uplink, on
    qwen2-0.5b at published widths, depth and dtype (bf16) unless
    ``model_changes`` (``n_layers``, ``dtype``) say otherwise.  Returns
    ``(algorithm, params0, spec)``."""
    import dataclasses
    import jax
    from repro.compress import TopK
    from repro.configs import get_spec
    from repro.core.fedcomloc import FedComLoc, FedComLocConfig
    from repro.models import transformer as tfm

    spec = get_spec(ARCH)
    if model_changes:
        spec = dataclasses.replace(
            spec, model=dataclasses.replace(spec.model, **model_changes))
    cfg_m = spec.model
    params0 = tfm.init_params(jax.random.PRNGKey(seed), cfg_m)
    data = token_data(cfg_m.vocab, n_clients, seed)
    loss_fn = lambda p, xb, yb: tfm.loss(p, cfg_m, xb, loss_chunk=SEQ_LEN)
    # p = 1/3 with fixed local phases: 3 local steps per round
    fcfg = FedComLocConfig(gamma=0.05, p=1 / 3, n_clients=n_clients,
                           clients_per_round=cohort, batch_size=2,
                           variant="com")
    alg = FedComLoc(loss_fn, data, fcfg, TopK(DENSITY), wire="packed")
    return alg, params0, spec


def count_compiles():
    """A list that grows by one entry per XLA backend compile."""
    import jax
    seen: list = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def run_job(alg, params0, seed: int, mesh=None):
    """3 fused rounds through ``run_federated``; returns (history, wall s)."""
    import jax
    from repro.core import server
    t0 = time.time()
    hist = server.run_federated(alg, params0, ROUNDS,
                                jax.random.PRNGKey(seed + 2), mesh=mesh)
    jax.block_until_ready(hist.final_params)
    return hist, time.time() - t0


def check_rounds(hist, alg, params0, label: str, nbytes: int) -> list:
    """Finite losses, a moved model and payload bytes that reconcile with
    the accounted bits (``nbytes`` is the static per-client payload);
    returns the per-round (loss, bits) for cross-run comparisons."""
    import jax
    from repro.compress.report import INDEX_BITS

    check(len(hist.round_metrics) == ROUNDS,
          f"{label}: {len(hist.round_metrics)} rounds recorded")
    cohort = alg.cfg.clients_per_round
    value_bits = {jax.numpy.dtype(l.dtype).itemsize * 8
                  for l in jax.tree_util.tree_leaves(params0)}
    check(len(value_bits) == 1, f"{label}: mixed leaf dtypes {value_bits}")
    slot_bits = INDEX_BITS + value_bits.pop()
    out = []
    for i, m in enumerate(hist.round_metrics):
        loss = float(m["train_loss"])
        bits = float(m["uplink_bits"])
        pbytes = float(m["uplink_payload_bytes"])
        check(np.isfinite(loss), f"{label} round {i + 1}: loss {loss}")
        check(pbytes > 0, f"{label} round {i + 1}: no payload bytes")
        # the round metrics are float32: past 2^24 each is exact only to
        # its own spacing (128 bits at qwen2-0.5b's ~1.2e9 uplink bits)
        check(pbytes == float(np.float32(cohort * nbytes)),
              f"{label} round {i + 1}: payload {pbytes} B, static "
              f"{cohort} x {nbytes} B")
        # measured - accounted bits = (slots - support) x slot width, a
        # whole number of slots (negative by magnitude ties beyond k)
        slack = pbytes * 8 - bits
        slots = round(slack / slot_bits)
        resolution = float(np.spacing(np.float32(bits))
                           + np.spacing(np.float32(pbytes * 8)))
        check(abs(slack - slots * slot_bits) <= resolution,
              f"{label} round {i + 1}: {slack} slack bits are not whole "
              f"{slot_bits}-bit slots to within {resolution} bits")
        log(f"{label}, round {i + 1}: train_loss {loss:.6f}  uplink_bits "
            f"{bits:.0f}  uplink_payload_bytes {pbytes:.0f}  slots - support "
            f"{slots:+d} (float32 metrics, resolution {resolution:g} bits)"
            f"  local steps "
            f"{int(m['num_local_steps'])}")
        out.append((loss, bits))
    moved = jax.tree_util.tree_map(
        lambda a, c: jax.numpy.any(a != c), hist.final_params, params0)
    check(any(bool(v) for v in jax.tree_util.tree_leaves(moved)),
          f"{label}: x did not change")
    return out


def round_phase(seed: int) -> None:
    import jax
    from repro.compress import wire

    alg, params0, spec = build_job(seed, N_CLIENTS, COHORT)
    cfg = spec.model
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(params0))
    log(f"rounds: {ARCH} {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {n_params} params in "
        f"{jax.numpy.dtype(cfg.dtype).name}; cuts: population "
        f"{alg.cfg.n_clients} clients, cohort {alg.cfg.clients_per_round} "
        f"(from 2 and 2, to fit one chip's memory), {SEQS_PER_CLIENT} "
        f"sequences of {SEQ_LEN} tokens per client, batch "
        f"{alg.cfg.batch_size}; no layer or width cut")
    compiles = count_compiles()
    hist, wall = run_job(alg, params0, seed)
    log(f"rounds: {ROUNDS} fused rounds in {wall:.1f}s wall, "
        f"{len(compiles)} compiles included (smoke timing, not a "
        f"measurement)")
    nbytes = wire.payload_nbytes(alg.comp, params0)
    check_rounds(hist, alg, params0, "rounds", nbytes)
    del hist                    # its model copy would crowd the repeat
    before = len(compiles)
    hist, wall = run_job(alg, params0, seed + 10)
    check_rounds(hist, alg, params0, "repeat", nbytes)
    check(len(compiles) == before,
          f"rounds: {len(compiles) - before} compiles on repeated shapes")
    log(f"rounds: {ROUNDS} more rounds on the same shapes in {wall:.1f}s "
        f"wall, 0 compiles")


# --------------------------------------------------------------------------- #
# four-chip mesh phases
# --------------------------------------------------------------------------- #

def spans(x) -> int:
    import jax
    return max(len(l.sharding.device_set)
               for l in jax.tree_util.tree_leaves(x))


def composed_mesh_phase(seed: int) -> None:
    """(a) The round job on make_client_mesh(2, model=2) vs one chip."""
    import jax
    from repro.compress import wire
    from repro.launch.mesh import make_client_mesh
    from repro.sharding import specs as sspecs

    alg, params0, spec = build_job(seed, 2, 2, **MESH_MODEL)
    cfg = spec.model
    log(f"mesh (a): {ARCH} at published widths, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}; cuts: {cfg.n_layers} of 24 "
        f"layers, {jax.numpy.dtype(cfg.dtype).name} leaves for the bits "
        f"comparison (see MESH_MODEL), population 2, cohort 2")
    hist1, wall1 = run_job(alg, params0, seed)
    one = check_rounds(hist1, alg, params0, "one-chip",
                       wire.payload_nbytes(alg.comp, params0))
    check(spans(hist1.final_params) == 1, "one-chip run left its chip")

    mesh = make_client_mesh(2, model=2, config=spec)
    alg_m, _, _ = build_job(seed, 2, 2, **MESH_MODEL)
    p0 = jax.device_put(params0, sspecs.param_shardings(params0, mesh))
    histm, wallm = run_job(alg_m, p0, seed, mesh=mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(params0)
    mdims = tuple(sspecs.model_dim_index(path, leaf.shape, 2)
                  for path, leaf in flat)
    nbytes = wire.sharded_wire_spec(alg_m.comp, params0, mdims, 2).nbytes
    four = check_rounds(histm, alg_m, params0, "clients x model", nbytes)
    n_dev = spans(histm.final_params)
    check(n_dev == 4, f"composed mesh: x spans {n_dev} devices")
    (l1, b1), (lm, bm) = (np.array(v).T for v in (one, four))
    bits_rel = float(np.max(np.abs(bm - b1) / np.maximum(b1, 1.0)))
    loss_rel = float(np.max(np.abs(lm - l1) / np.maximum(np.abs(l1), 1e-6)))
    check(bits_rel <= BITS_RTOL, f"composed mesh: bits rel {bits_rel}")
    check(loss_rel <= LOSS_RTOL, f"composed mesh: loss rel {loss_rel}")
    log(f"mesh (a) clients x model {dict(mesh.shape)}: x spans {n_dev} "
        f"devices; bits max rel {bits_rel:.2e} (limit {BITS_RTOL}), loss "
        f"max rel {loss_rel:.2e} (limit {LOSS_RTOL}); one chip "
        f"{wall1:.1f}s, mesh {wallm:.1f}s wall (smoke timing)")


def clients_mesh_phase(seed: int) -> None:
    """(b) The paper's cross-device MLP job on make_client_mesh(4) vs one
    device: bits bit-identical (tests/test_distributed.py's contract)."""
    import jax
    import jax.numpy as jnp
    from repro.compress import TopK
    from repro.core import fed_data, server
    from repro.core.fedcomloc import FedComLoc, FedComLocConfig
    from repro.data import dirichlet, synthetic
    from repro.launch.mesh import make_client_mesh
    from repro.models import small

    ds = synthetic.make_mnist_like(n_train=8000, n_test=1000, seed=seed)
    parts = dirichlet.dirichlet_partition(ds.y_train, n_clients=20,
                                          alpha=0.7, seed=seed)
    data = fed_data.from_numpy_partition(ds.x_train, ds.y_train, parts)
    model = small.MLP(784, 64, 10)
    loss_fn = small.cross_entropy_loss(model.apply)
    cfg = FedComLocConfig(gamma=0.1, p=0.1, n_clients=20,
                          clients_per_round=8, batch_size=32, variant="com")
    eval_fn = server.make_eval_fn(model.apply, jnp.asarray(ds.x_test),
                                  jnp.asarray(ds.y_test))
    params0 = model.init(jax.random.PRNGKey(seed))
    runs = {}
    for n_dev in (1, 4):
        alg = FedComLoc(loss_fn, data, cfg, TopK(density=0.3),
                        wire="packed")
        mesh = make_client_mesh(n_dev)
        hist = server.run_federated(alg, params0, 10,
                                    jax.random.PRNGKey(seed + 1),
                                    eval_fn=eval_fn, eval_every=5,
                                    mesh=mesh)
        runs[n_dev] = (hist, spans(hist.final_params))
    (h1, s1), (h4, s4) = runs[1], runs[4]
    check(s4 == 4, f"clients mesh: x spans {s4} devices")
    for key in ("uplink_bits", "uplink_payload_bytes", "downlink_bits"):
        a = np.array([float(m[key]) for m in h1.round_metrics])
        c = np.array([float(m[key]) for m in h4.round_metrics])
        check(np.array_equal(a, c), f"clients mesh: {key} {a} vs {c}")
    check(np.allclose(h1.test_acc, h4.test_acc, atol=1e-2),
          f"clients mesh: accuracy {h1.test_acc} vs {h4.test_acc}")
    log(f"mesh (b) clients {{'clients': 4}}: x spans {s4} devices; "
        f"uplink/downlink bits and payload bytes bit-identical to 1 device "
        f"over 10 rounds; acc {h1.test_acc} vs {h4.test_acc}")


# --------------------------------------------------------------------------- #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    log(f"chip_smoke (smoke run, not a measurement): {dev.device_kind} x "
        f"{len(devices)}, jax {jax.__version__}, compile cache "
        f"{enable_compile_cache()}")
    try:
        check(ops._resolve() == "pallas",
              f"dispatch resolved to {ops._resolve()!r}, not 'pallas'")
        log("dispatch: pallas (every wire kernel serves every leaf size; "
            "no size rule)")
        if args.chips == 4:
            composed_mesh_phase(args.seed)
            clients_mesh_phase(args.seed)
        else:
            from repro.models import transformer as tfm
            from repro.configs import get_spec
            shapes = jax.eval_shape(
                lambda: tfm.init_params(jax.random.PRNGKey(0),
                                        get_spec(ARCH).model))
            kernel_phase(args.seed, shapes)
            # the kernel phase's programs and buffers leave the chip's
            # memory to the round
            jax.clear_caches()
            round_phase(args.seed)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
