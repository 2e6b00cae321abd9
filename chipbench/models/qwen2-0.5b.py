"""Qwen2-0.5B (arXiv:2407.10671): its weights, the program's loss over it,
its plain float32 reference and its FLOPs per local step.

The reference follows the published decoder: token embedding; per layer
RMSNorm, grouped-query attention with q/k/v biases and rotary positions
(rotate-half, theta from the config), a causal softmax, the output
projection and the residual; RMSNorm and a SiLU-gated MLP and the residual;
a final RMSNorm and logits through the tied embedding; the mean next-token
cross-entropy over every position but the last.  It is written from that
description in ``jax.numpy``, imports nothing of the program, and runs in
float32 at ``highest`` matmul precision.  ``q`` rounds every parameter and
activation it is applied to, so the same code computes the lower-precision
control.

The parameter tree has the layout the program's transformer reads
(``repro.models.transformer``); the weights are the benchmark's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _sizes(cfg: dict) -> dict:
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, f=cfg["intermediate_size"], L=cfg["num_hidden_layers"],
                nh=nh, nkv=cfg["num_key_value_heads"], hd=d // nh,
                V=cfg["vocab_size"], theta=cfg["rope_theta"],
                eps=cfg["rms_norm_eps"])


def dtype(cfg: dict):
    return DTYPES[cfg["torch_dtype"]]


def data_spec(cfg: dict) -> dict:
    return {"vocab": cfg["vocab_size"]}


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #

def init(cfg: dict, key: jax.Array) -> dict:
    """Random weights from ``key`` in the configuration's dtype; call under
    ``jax.jit`` so they are made on the device in one program."""
    s, dt = _sizes(cfg), dtype(cfg)
    d, f, hd = s["d"], s["f"], s["hd"]
    keys = iter(jax.random.split(key, 1 + 7 * s["L"]))

    def dense(n_in, n_out, bias=False):
        w = jax.random.normal(next(keys), (n_in, n_out), jnp.float32)
        p = {"kernel": (w * (1.0 / n_in) ** 0.5).astype(dt)}
        if bias:
            p["bias"] = jnp.zeros((n_out,), dt)
        return p

    def norm():
        return {"scale": jnp.ones((d,), dt)}

    emb = jax.random.normal(next(keys), (s["V"], d), jnp.float32) * 0.02
    layers = {}
    for i in range(s["L"]):
        layers[f"layer_{i}"] = {
            "ln_attn": norm(),
            "q": dense(d, s["nh"] * hd, bias=True),
            "k": dense(d, s["nkv"] * hd, bias=True),
            "v": dense(d, s["nkv"] * hd, bias=True),
            "o": dense(s["nh"] * hd, d),
            "ln_mlp": norm(),
            "mlp": {"wi": dense(d, f), "wo": dense(f, d), "wg": dense(d, f)},
        }
    return {"embed": {"embedding": emb.astype(dt)}, "final_norm": norm(),
            "layers": layers}


# --------------------------------------------------------------------------- #
# the program's loss
# --------------------------------------------------------------------------- #

def program_loss(cfg: dict):
    """``loss_fn(params, xb, yb)`` of the system under test."""
    from repro.models import transformer as tfm
    s = _sizes(cfg)
    model = tfm.ModelConfig(
        name="qwen2-0.5b", n_layers=s["L"], d_model=s["d"], n_heads=s["nh"],
        n_kv_heads=s["nkv"], head_dim=s["hd"], d_ff=s["f"], vocab=s["V"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=s["theta"], norm_eps=s["eps"], act=cfg["hidden_act"],
        dtype=dtype(cfg))

    def loss_fn(params, xb, yb):
        del yb
        # one loss chunk spans the whole sequence
        return tfm.loss(params, model, xb, loss_chunk=xb.shape[1])

    return loss_fn


# --------------------------------------------------------------------------- #
# plain reference
# --------------------------------------------------------------------------- #

def ref_loss(cfg: dict, params: dict, tokens: jax.Array, labels,
             q=lambda a: a) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (b, T), in float32."""
    del labels
    s = _sizes(cfg)
    hd, eps = s["hd"], s["eps"]
    f32 = jnp.float32
    P = jax.tree_util.tree_map(lambda a: q(a.astype(f32)), params)
    emb = P["embed"]["embedding"]
    b, t = tokens.shape

    def rmsnorm(x, scale):
        return q(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                 * scale)

    def heads(x, n):
        return x.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    inv = 1.0 / (s["theta"] ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    ang = jnp.arange(t, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rope(x):
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return q(jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                                 -1))

    causal = jnp.tril(jnp.ones((t, t), bool))
    x = q(emb[tokens])
    group = s["nh"] // s["nkv"]
    for i in range(s["L"]):
        p = P["layers"][f"layer_{i}"]
        h = rmsnorm(x, p["ln_attn"]["scale"])
        qh = rope(heads(q(h @ p["q"]["kernel"] + p["q"]["bias"]), s["nh"]))
        kh = rope(heads(q(h @ p["k"]["kernel"] + p["k"]["bias"]), s["nkv"]))
        vh = heads(q(h @ p["v"]["kernel"] + p["v"]["bias"]), s["nkv"])
        kh = jnp.repeat(kh, group, axis=1)
        vh = jnp.repeat(vh, group, axis=1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / jnp.sqrt(f32(hd))
        scores = jnp.where(causal, scores, -jnp.inf)
        att = q(jax.nn.softmax(scores, axis=-1))
        o = q(jnp.einsum("bhqk,bhkd->bhqd", att, vh))
        o = o.transpose(0, 2, 1, 3).reshape(b, t, s["nh"] * hd)
        x = q(x + q(o @ p["o"]["kernel"]))
        h = rmsnorm(x, p["ln_mlp"]["scale"])
        m = p["mlp"]
        gate = q(jax.nn.silu(q(h @ m["wg"]["kernel"])) * q(h @ m["wi"]["kernel"]))
        x = q(x + q(gate @ m["wo"]["kernel"]))
    h = rmsnorm(x, P["final_norm"]["scale"])
    logits = h[:, :-1] @ emb.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean()


# --------------------------------------------------------------------------- #
# work
# --------------------------------------------------------------------------- #

def step_flops(cfg: dict, traffic: dict) -> int:
    """FLOPs one client's local step requires: forward and backward of the
    loss over one minibatch, without recomputation."""
    from chipbench import flops
    s = _sizes(cfg)
    return flops.decoder_step(
        d=s["d"], f=s["f"], layers=s["L"], heads=s["nh"], kv_heads=s["nkv"],
        head_dim=s["hd"], vocab=s["V"], batch=traffic["batch"],
        seq=traffic["data"]["seq_len"])
