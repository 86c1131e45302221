"""Plain reference of the Mistral-7B decoder: straightforward ``jax.numpy``,
float32, no kernels, no cache, no batching tricks.

Follows the published architecture (Mistral-7B-v0.1 ``config.json`` and the
``MistralForCausalLM`` equations): pre-norm RMSNorm, rotary embedding in the
half-split ("rotate_half") layout, grouped-query attention, SwiGLU MLP, untied
output head.  One departure, stated in every configuration file: the published
``sliding_window`` of 4096 is not applied, which is exact while no context
exceeds 4096 positions (none does in these cells).

It reads the parameter tree of the program under test (``layers`` stacked on
a leading ``L`` axis, ``[in, out]`` kernels) so both sides run on the very same
weights.  Attention runs in query blocks so the scores of a 4096-token
sequence never exist at once.  Every matmul runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise computed in bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [b, s, h, d]; rotates (x1, x2) = halves of the head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, :, None].astype(jnp.float32) * inv[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal GQA attention, q [b, s, hq, d], k/v [b, s, hkv, d], in query
    blocks of ``Q_BLOCK``."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    q = q.reshape(b, s, hkv, g, d)
    nblk = -(-s // Q_BLOCK)
    pad = nblk * Q_BLOCK - s
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qp = qp.reshape(b, nblk, Q_BLOCK, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(s)

    def block(args):
        i, qb = args  # qb [b, Q, hkv, g, d]
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) / jnp.sqrt(jnp.float32(d))
        mask = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    out = jax.lax.map(block, (jnp.arange(nblk), qp))  # [nblk, b, Q, hkv, g, d]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nblk * Q_BLOCK, hq * d)
    return out[:, :s]


def hidden_states(params, tokens, m: dict):
    """tokens [b, s] int32 -> final-norm hidden states [b, s, d] float32.
    ``m`` holds the published config keys."""
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // hq
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"])[tokens]

        def layer(x, lw):
            h = _rms(x, lw["attn_norm"]["scale"], eps)
            a = lw["attn"]
            q = (h @ f32(a["wq"])).reshape(b, s, hq, hd)
            k = (h @ f32(a["wk"])).reshape(b, s, hkv, hd)
            v = (h @ f32(a["wv"])).reshape(b, s, hkv, hd)
            o = _attention(_rope(q, pos, theta), _rope(k, pos, theta), v)
            x = x + o @ f32(a["wo"])
            h = _rms(x, lw["mlp_norm"]["scale"], eps)
            w = lw["mlp"]
            x = x + (jax.nn.silu(h @ f32(w["w_gate"])) * (h @ f32(w["w_up"]))) @ f32(w["w_down"])
            return x, None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        return _rms(x, params["final_norm"]["scale"], eps)


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32."""
    h = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return h @ params["lm_head"]["kernel"].astype(jnp.float32)


def make_loss_fn(m: dict):
    """``loss(params, batch, rng=None)``: token-mean next-token cross entropy
    of ``batch["input_ids"]`` [b, s+1], the signature the train engine's
    ``eval_fn`` takes."""

    def loss(params, batch, rng=None):
        ids = batch["input_ids"]
        lg = logits(params, ids[:, :-1], m)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return loss
