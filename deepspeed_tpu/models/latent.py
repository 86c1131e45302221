"""Decoder whose layers are of several KINDS (``TransformerConfig.latent``):
latent attention (MLA) on every layer, either over the keys a learned indexer
selects (``full``) or over a sliding window with its own ranks and head count
(``sliding``); a dense SwiGLU on the leading layers and sigmoid-routed experts
with a shared expert after them, of which this process may hold a SHARE
(``moe/layer.py:moe_block_held``).

Parameters are grouped per kind (``layers/full``, ``layers/sliding``,
``layers/mlp``, ``layers/moe``: a tuple with one tree per layer of the kind,
NOT one stacked array, because a slice of a stacked array handed to a Pallas
kernel is copied first: 6 GB a dispatch for the experts; the two norms are
stacked ``[L, d]``); ``layer_params`` picks layer ``l``'s trees.  The per-token halves of a layer (``attn_inputs``,
``indexer_inputs``, ``attn_output``, ``ffn``) are shared by the uncached
``forward`` here and by the serving runner (``inference/latent_runner.py``),
which differ only in where a layer's keys live.  Forward only: there is no
backward, pipeline or tensor-parallel path for these layers yet.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import latent_attention as la

Params = Dict[str, Any]


@dataclass(frozen=True)
class LatentAttn:
    """One kind of latent-attention layer."""

    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    window: int = 0  # 0: the indexer selects the keys; n: the last n positions

    @property
    def row(self) -> int:  # what the cache keeps per key
        return self.kv_rank + self.rope_dim

    @property
    def scale(self) -> float:
        return float(self.nope_dim + self.rope_dim) ** -0.5


@dataclass(frozen=True)
class LatentSpec:
    layer_kinds: Tuple[str, ...]  # 'full' | 'sliding', one per layer held
    full: LatentAttn
    sliding: LatentAttn
    index_heads: int
    index_dim: int
    index_topk: int
    first_dense: int        # leading layers with the dense SwiGLU
    n_routed: int           # experts the router scores (the whole deployment's)
    n_held: int             # experts whose weights are here ...
    held_offset: int        # ... starting at this expert
    experts_per_tok: int
    moe_width: int
    n_shared: int
    routed_scale: float = 1.0
    rescale_lora: bool = True

    def attn(self, kind: str) -> LatentAttn:
        return self.full if kind == "full" else self.sliding

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_kinds)

    @property
    def index_scale(self) -> float:
        return float(self.index_heads * self.index_dim) ** -0.5


def _attn_shapes(d: int, a: LatentAttn) -> Dict[str, tuple]:
    h = a.num_heads
    return {
        "w_dq": (d, a.q_rank), "w_uq": (a.q_rank, h * (a.nope_dim + a.rope_dim)),
        "w_dkv": (d, a.row), "w_uk": (a.kv_rank, h * a.nope_dim),
        "w_uv": (a.kv_rank, h * a.v_dim), "w_g": (d, h), "wo": (h * a.v_dim, d),
    }


def _index_shapes(d: int, s: LatentSpec) -> Dict[str, tuple]:
    return {"w_iq": (s.full.q_rank, s.index_heads * s.index_dim),
            "w_ik": (d, s.index_dim), "w_iw": (d, s.index_heads)}


def param_count(cfg) -> int:
    """Parameters HELD here (a share of the experts and of the vocabulary
    where the configuration says so)."""
    s, d = cfg.latent, cfg.hidden_size
    size = lambda shapes: sum(int(np.prod(v)) for v in shapes.values())
    n = 2 * cfg.vocab_size * d + d
    for l, kind in enumerate(s.layer_kinds):
        a = s.attn(kind)
        n += 2 * d + size(_attn_shapes(d, a)) + a.q_rank + a.kv_rank
        if kind == "full":
            n += size(_index_shapes(d, s)) + 2 * s.index_dim
        if l < s.first_dense:
            n += 3 * d * cfg.intermediate_size
        else:
            n += d * s.n_routed + s.n_routed
            n += 3 * d * s.moe_width * (s.n_held + s.n_shared)
    return n


def init_params(rng, cfg, dtype=jnp.float32) -> Params:
    s, d, L = cfg.latent, cfg.hidden_size, cfg.num_layers
    if len(s.layer_kinds) != L:
        raise ValueError(f"{len(s.layer_kinds)} layer kinds for {L} layers")
    keys = iter(jax.random.split(rng, 32 * (L + 1)))

    def dense(shape, fan_in):
        w = jax.random.normal(next(keys), shape, jnp.float32) / np.sqrt(fan_in)
        return w.astype(dtype)

    def attn(kind):
        a = s.attn(kind)
        w = {k: dense(sh, sh[0]) for k, sh in _attn_shapes(d, a).items()}
        w["q_norm"] = jnp.ones((a.q_rank,), dtype)
        w["kv_norm"] = jnp.ones((a.kv_rank,), dtype)
        if kind == "full":
            w.update({k: dense(sh, sh[0]) for k, sh in _index_shapes(d, s).items()})
            w["ik_norm"] = {"scale": jnp.ones((s.index_dim,), dtype),
                            "bias": jnp.zeros((s.index_dim,), dtype)}
        return w

    def experts():
        return {
            "router": dense((d, s.n_routed), d),
            # the selection bias: small and non-zero, so that it decides some
            # selections and no expert's score drowns in it
            "bias": (0.02 * jax.random.normal(next(keys), (s.n_routed,))
                     ).astype(jnp.float32),
            "w_gate": dense((s.n_held, d, fm), d),
            "w_up": dense((s.n_held, d, fm), d),
            "w_down": dense((s.n_held, fm, d), fm),
            "s_gate": dense((d, fs), d), "s_up": dense((d, fs), d),
            "s_down": dense((fs, d), fs),
        }

    layers: Params = {
        "attn_norm": {"scale": jnp.ones((L, d), dtype)},
        "mlp_norm": {"scale": jnp.ones((L, d), dtype)},
    }
    nd, nm = min(s.first_dense, L), max(L - s.first_dense, 0)
    f, fm, fs = cfg.intermediate_size, s.moe_width, s.moe_width * s.n_shared
    for kind in ("full", "sliding"):
        layers[kind] = tuple(attn(kind) for _ in range(s.count(kind)))
    layers["mlp"] = tuple(
        {"w_gate": dense((d, f), d), "w_up": dense((d, f), d), "w_down": dense((f, d), f)}
        for _ in range(nd))
    layers["moe"] = tuple(experts() for _ in range(nm))
    return {
        "embed": {"embedding": dense((cfg.vocab_size, d), d)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": dense((d, cfg.vocab_size), d)},
    }


def layer_params(layers: Params, l: int, s: LatentSpec):
    """(kind, the two norms, attention weights, feed-forward weights, whether
    the feed-forward is the expert layer) of layer ``l``."""
    kind = s.layer_kinds[l]
    aw = layers[kind][s.layer_kinds[:l].count(kind)]
    norms = ({"scale": layers["attn_norm"]["scale"][l]},
             {"scale": layers["mlp_norm"]["scale"][l]})
    if l < s.first_dense:
        return kind, norms, aw, layers["mlp"][l], False
    return kind, norms, aw, layers["moe"][l - s.first_dense], True


# ---------------------------------------------------------------------------
# the per-token halves of a layer: rows are tokens, [T, ...]
# ---------------------------------------------------------------------------
def rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf.astype(x.dtype) * scale


def _rope(x, pos, theta: float):
    """x [T, h, r] rotated in the half-split layout at ``pos`` [T]."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def attn_inputs(aw, h, pos, a: LatentAttn, cfg):
    """Normed input ``h`` [T, d] -> (c_q [T, r_q], absorbed queries [T, H,
    row], the key's cache row [T, row], gate [T, H])."""
    t, d, eps = h.shape[0], cfg.hidden_size, cfg.norm_eps
    up = lambda r: (d / r) ** 0.5 if cfg.latent.rescale_lora else 1.0
    c_q = rms(h @ aw["w_dq"], aw["q_norm"], eps) * jnp.asarray(up(a.q_rank), h.dtype)
    q = (c_q @ aw["w_uq"]).reshape(t, a.num_heads, a.nope_dim + a.rope_dim)
    q_r = _rope(q[..., a.nope_dim:], pos, a.rope_theta)
    kv = h @ aw["w_dkv"]
    c_kv = rms(kv[:, :a.kv_rank], aw["kv_norm"], eps) * jnp.asarray(up(a.kv_rank), h.dtype)
    k_r = _rope(kv[:, None, a.kv_rank:], pos, a.rope_theta)[:, 0]
    w_uk = aw["w_uk"].reshape(a.kv_rank, a.num_heads, a.nope_dim)
    q_abs = jnp.concatenate(
        [jnp.einsum("thn,rhn->thr", q[..., :a.nope_dim], w_uk), q_r], axis=-1)
    gate = jax.nn.sigmoid((h @ aw["w_g"]).astype(jnp.float32))
    return c_q, q_abs, jnp.concatenate([c_kv, k_r], axis=-1), gate


def indexer_inputs(aw, h, c_q, pos, s: LatentSpec, cfg):
    """(index queries [T, J, D], index key [T, D], head weights [T, J] f32);
    RoPE on the first ``rope_dim`` of the D dims of both."""
    t, r = h.shape[0], s.full.rope_dim
    q_i = (c_q @ aw["w_iq"]).reshape(t, s.index_heads, s.index_dim)
    k = (h @ aw["w_ik"]).astype(jnp.float32)
    mu = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(k - mu), -1, keepdims=True) + cfg.norm_eps)
    k_i = (k.astype(h.dtype) * aw["ik_norm"]["scale"] + aw["ik_norm"]["bias"])[:, None]
    rot = lambda x: jnp.concatenate(
        [_rope(x[..., :r], pos, s.full.rope_theta), x[..., r:]], axis=-1)
    return rot(q_i), rot(k_i)[:, 0], (h @ aw["w_iw"]).astype(jnp.float32)


def attn_output(aw, o_lat, gate, a: LatentAttn):
    """Attention over latent rows [T, H, r_kv] -> the sublayer's output [T, d]:
    through ``W_uv`` per head, the headwise gate, ``W_o``."""
    w_uv = aw["w_uv"].reshape(a.kv_rank, a.num_heads, a.v_dim)
    o = jnp.einsum("thr,rhv->thv", o_lat, w_uv) * gate[..., None].astype(o_lat.dtype)
    return o.reshape(o.shape[0], -1) @ aw["wo"]


def ffn(fw, h, is_moe: bool, cfg, valid=None):
    """(output [T, d], and of an expert layer (routing stats, experts picked
    [T, k]), else None)."""
    if not is_moe:
        return (jax.nn.silu(h @ fw["w_gate"]) * (h @ fw["w_up"])) @ fw["w_down"], None
    from ..moe.layer import moe_block_held

    return moe_block_held(fw, h, cfg.latent, valid)


# ---------------------------------------------------------------------------
# the uncached forward: every sequence is its own keys
# ---------------------------------------------------------------------------
def forward(params: Params, tokens, cfg, *, return_hidden: bool = False):
    """tokens [b, s] -> (logits [b, s, v] | hidden, None, 0.0)."""
    s_, (b, n) = cfg.latent, tokens.shape
    pos = jnp.tile(jnp.arange(n), b)
    x = params["embed"]["embedding"][tokens.reshape(-1)].astype(cfg.dtype)
    grouped = lambda a: a.reshape(b, n, *a.shape[1:])
    for l in range(cfg.num_layers):
        kind, (n1, n2), aw, fw, is_moe = layer_params(params["layers"], l, s_)
        a = s_.attn(kind)
        h = rms(x, n1["scale"], cfg.norm_eps)
        c_q, q_abs, row, gate = attn_inputs(aw, h, pos, a, cfg)
        q_abs, rows, q_pos = grouped(q_abs), grouped(row), grouped(pos)
        if kind == "full":
            q_i, k_i, w = indexer_inputs(aw, h, c_q, pos, s_, cfg)

            def group(q_i, w, k_i, q_pos, q_abs, rows):
                sc = la.index_scores(q_i, w, q_pos, lambda blk: k_i, 1, n, n,
                                     s_.index_scale)
                vals, idx = la.select_topk(sc, s_.index_topk)
                return la.sparse_attention(q_abs, idx, vals > -jnp.inf,
                                           lambda ix: rows[ix], a.kv_rank, a.scale)

            o = jax.vmap(group)(grouped(q_i), grouped(w), grouped(k_i), q_pos,
                                q_abs, rows)
        else:
            o = la.window_attention(q_abs, q_pos, rows, q_pos, a.window, a.kv_rank,
                                    a.scale)
        x = x + attn_output(aw, o.reshape(b * n, *o.shape[2:]), gate, a).astype(x.dtype)
        h = rms(x, n2["scale"], cfg.norm_eps)
        x = x + ffn(fw, h, is_moe, cfg)[0].astype(x.dtype)
    x = rms(x, params["final_norm"]["scale"], cfg.norm_eps).reshape(b, n, -1)
    if return_hidden:
        return x, None, jnp.asarray(0.0, jnp.float32)
    return x @ params["lm_head"]["kernel"], None, jnp.asarray(0.0, jnp.float32)


def refuse(option: str, why: str):
    raise NotImplementedError(
        f"{option} is not supported for a model with layers of several kinds "
        f"(TransformerConfig.latent): {why}")
