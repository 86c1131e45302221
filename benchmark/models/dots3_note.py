"""``model_type`` "dots3_note": what the harness needs from this architecture
(the five callables ``models/__init__.py`` lists), and ``probe`` for the
serving driver's checks of what was SELECTED.

The plain reference is dots3-note-prev's language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no cache, no kernels, no
absorbed form.  Keys and values are decompressed per head from the latent
(``k_h = [W_uk,h c_kv ; k_rope]``, ``v_h = W_uv,h c_kv``); selection and window
are dense masks over a whole ``[query block, s]`` score matrix; an expert layer
runs EVERY held expert on every token and masks by what the router picked.  It
reads the program's parameter tree (``models/latent.py``: per kind of layer a
tuple of per-layer trees), so both sides run on the same weights, and follows the
equations of the configuration file's ``equations`` key:

- full layer: MLA as DeepSeek-V3 (``q_lora_rank`` / ``kv_lora_rank``, RoPE on
  the rope dims in the half-split layout, scale ``(nope + rope)^-1/2``) over
  the keys the indexer selects; DeepSeek-V3.2's indexer without its FP8 and
  Hadamard steps: ``I[t,s] = (J D)^-1/2 sum_j w[t,j] relu(q_I[t,j] . k_I[s])``,
  the ``index_topk`` largest of ``s <= t`` (all of them while ``t <
  index_topk``); headwise sigmoid gate on the heads' outputs.
- sliding layer: the same attention with the ``swa_*`` sizes, keys
  ``0 <= t - s < sliding_window_size``, no indexer.
- ``apply_mla_qkv_lora_rescale``: ``c_q`` and ``c_kv`` times ``sqrt(hidden /
  rank)`` after their norms (an ``assumed`` reading).
- expert layer: sigmoid scores in float32, the ``num_experts_per_tok`` largest
  of score + bias, weights the picked scores normalised; ONLY the experts held
  here (``deployment.expert_offset`` .. + ``n_routed_experts``) are computed,
  plus the shared expert: the partial sum an expert-parallel member hands on.

Attention runs in query blocks of ``Q_BLOCK`` so that a 3k-token sample's
index scores ``[Q, 64, s]`` fit beside the weights on one chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import contextlib

Q_BLOCK = 256
_WEIGHTS_AS = None  # the control's precision, while ``weights_rounded_to`` is open


def _F32(a):
    """A weight (or an array already float32) as the reference uses it."""
    if _WEIGHTS_AS is not None and a.ndim >= 2 and a.dtype != jnp.float32:
        a = a.astype(_WEIGHTS_AS)
    return a.astype(jnp.float32)


@contextlib.contextmanager
def weights_rounded_to(dtype):
    """Inside (at TRACE time), the reference reads every weight matrix rounded
    to ``dtype``: the serving driver's control, one precision down, without a
    second copy of the weights on the device."""
    global _WEIGHTS_AS
    _WEIGHTS_AS = dtype
    try:
        yield
    finally:
        _WEIGHTS_AS = None


def _attn_kinds(m: dict):
    full = dict(heads=m["num_attention_heads"], q_rank=m["q_lora_rank"],
                kv_rank=m["kv_lora_rank"], nope=m["qk_nope_head_dim"],
                rope=m["qk_rope_head_dim"], v=m["v_head_dim"],
                theta=float(m["rope_theta"]), window=0)
    swa = dict(heads=m["swa_num_attention_heads"], q_rank=m["swa_q_lora_rank"],
               kv_rank=m["swa_kv_lora_rank"], nope=m["swa_qk_nope_head_dim"],
               rope=m["swa_qk_rope_head_dim"], v=m["swa_v_head_dim"],
               theta=float(m["swa_rope_theta"]), window=int(m["sliding_window_size"]))
    return {"full_attention": full, "sliding_attention": swa}


def _kinds(m: dict):
    return [m["layer_types"][l] for l in range(m["num_hidden_layers"])]


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the layers."""
    from deepspeed_tpu.models.latent import LatentAttn, LatentSpec
    from deepspeed_tpu.models.transformer import TransformerConfig

    if model["hidden_act"] != "silu" or model["scoring_func"] != "sigmoid" \
            or model["topk_method"] != "noaux_tc" or not model["norm_topk_prob"] \
            or model["attention_gate_type"] != "headwise" \
            or model["swa_attention_gate_type"] != "headwise" \
            or model["moe_layer_freq"] != 1 or model["rope_scaling"] is not None:
        raise ValueError("only the published dots3_note block is mapped here")
    kinds = _attn_kinds(model)
    attn = lambda a: LatentAttn(
        num_heads=a["heads"], q_rank=a["q_rank"], kv_rank=a["kv_rank"],
        nope_dim=a["nope"], rope_dim=a["rope"], v_dim=a["v"], rope_theta=a["theta"],
        window=a["window"])
    dep = model["deployment"]
    spec = LatentSpec(
        layer_kinds=tuple("full" if k == "full_attention" else "sliding"
                          for k in _kinds(model)),
        full=attn(kinds["full_attention"]), sliding=attn(kinds["sliding_attention"]),
        index_heads=model["index_n_heads"], index_dim=model["index_head_dim"],
        index_topk=model["index_topk"], first_dense=model["first_k_dense_replace"],
        n_routed=dep["n_routed_experts_total"], n_held=model["n_routed_experts"],
        held_offset=dep["expert_offset"], experts_per_tok=model["num_experts_per_tok"],
        moe_width=model["moe_intermediate_size"], n_shared=model["n_shared_experts"],
        routed_scale=float(model["routed_scaling_factor"]),
        rescale_lora=bool(model["apply_mla_qkv_lora_rescale"]))
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=dtypes[model["torch_dtype"]], latent=spec)
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _F32(scale)


def _rope(x, theta):
    """x [b, s, h, r] at positions 0..s-1; rotates (x1, x2) = halves."""
    s, r = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _by_query_block(fn, s: int, *per_query):
    """``fn(first position, block of each per-query array [b, Q, ...])`` over
    query blocks, results concatenated back to ``s`` positions."""
    q = min(Q_BLOCK, s)
    n = -(-s // q)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, n * q - s)) + ((0, 0),) * (a.ndim - 2))
    split = lambda a: jnp.moveaxis(pad(a).reshape(a.shape[0], n, q, *a.shape[2:]), 1, 0)
    out = jax.lax.map(lambda args: fn(args[0], *args[1:]),
                      (jnp.arange(n) * q, *map(split, per_query)))
    join = lambda o: jnp.moveaxis(o, 0, 1).reshape(o.shape[1], n * q, *o.shape[3:])[:, :s]
    return jax.tree_util.tree_map(join, out)


def _index_scores(aw, h, c_q, m):
    """Dense ``I`` [b, s, s] with ``-inf`` above the diagonal, and each
    position's cut-off [b, s]: its ``index_topk``-th largest score, ``-inf``
    while the position has no more keys than that."""
    b, s, _ = h.shape
    j, dim, r = m["index_n_heads"], m["index_head_dim"], m["qk_rope_head_dim"]
    theta, topk = float(m["rope_theta"]), min(int(m["index_topk"]), s)
    rot = lambda x: jnp.concatenate([_rope(x[..., :r], theta), x[..., r:]], -1)
    q_i = rot((c_q @ _F32(aw["w_iq"])).reshape(b, s, j, dim))
    k = h @ _F32(aw["w_ik"])
    mu = jnp.mean(k, -1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(jnp.mean((k - mu) ** 2, -1, keepdims=True) + m["rms_norm_eps"])
    k_i = rot((k * _F32(aw["ik_norm"]["scale"]) + _F32(aw["ik_norm"]["bias"]))[:, :, None])[:, :, 0]
    w = h @ _F32(aw["w_iw"])
    kpos = jnp.arange(s)

    def block(p0, q_b, w_b):
        sc = jax.nn.relu(jnp.einsum("bqjd,bkd->bqjk", q_b, k_i))
        sc = jnp.einsum("bqjk,bqj->bqk", sc, w_b) * float(j * dim) ** -0.5
        qpos = p0 + jnp.arange(q_b.shape[1])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        return sc, jax.lax.top_k(sc, topk)[0][..., -1]

    return _by_query_block(block, s, q_i, w)


def _attention(q, k, v, mask, scale):
    """q [b, s, h, e], k [b, s, h, e], v [b, s, h, f]; ``mask`` bool [b, s, s]
    (query, key).  Softmax over the allowed keys, in query blocks."""
    s = q.shape[1]

    def block(p0, q_b, m_b):
        sc = jnp.einsum("bqhe,bkhe->bhqk", q_b, k) * scale
        sc = jnp.where(m_b[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhf->bqhf", jax.nn.softmax(sc, axis=-1), v)

    return _by_query_block(block, s, q, mask)


def _own_selection(scores, cut, topk: int):
    """The ``topk`` largest of each row of ``scores``, equal scores to the
    lower position (as ``lax.top_k`` orders them): all above the cut-off, and
    of those AT it the first few (relu makes exact ties, at toy sizes many)."""
    above, at = scores > cut[..., None], scores == cut[..., None]
    room = topk - jnp.sum(above, -1, keepdims=True)
    return (above | (at & (jnp.cumsum(at, -1) <= room))) & (scores > -jnp.inf)


def _attention_layer(aw, h, a, m, probe, forced):
    b, s, d = h.shape
    eps, hh = m["rms_norm_eps"], a["heads"]
    up = lambda r: (d / r) ** 0.5 if m["apply_mla_qkv_lora_rescale"] else 1.0
    c_q = _rms(h @ _F32(aw["w_dq"]), aw["q_norm"], eps) * up(a["q_rank"])
    q = (c_q @ _F32(aw["w_uq"])).reshape(b, s, hh, a["nope"] + a["rope"])
    q = jnp.concatenate([q[..., :a["nope"]], _rope(q[..., a["nope"]:], a["theta"])], -1)
    kv = h @ _F32(aw["w_dkv"])
    c_kv = _rms(kv[..., :a["kv_rank"]], aw["kv_norm"], eps) * up(a["kv_rank"])
    k_r = _rope(kv[:, :, None, a["kv_rank"]:], a["theta"])
    k_n = (c_kv @ _F32(aw["w_uk"])).reshape(b, s, hh, a["nope"])
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (b, s, hh, a["rope"]))], -1)
    v = (c_kv @ _F32(aw["w_uv"])).reshape(b, s, hh, a["v"])
    if a["window"]:
        dist = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        mask = jnp.broadcast_to((dist >= 0) & (dist < a["window"]), (b, s, s))
    else:
        scores, cut = _index_scores(aw, h, c_q, m)
        if probe is not None:
            probe.append({"index_scores": scores, "index_cutoff": cut})
        mask = _own_selection(scores, cut, min(int(m["index_topk"]), s)) \
            if forced is None else forced
    o = _attention(q, k, v, mask, float(a["nope"] + a["rope"]) ** -0.5)
    o = o * jax.nn.sigmoid(h @ _F32(aw["w_g"]))[..., None]
    return o.reshape(b, s, hh * a["v"]) @ _F32(aw["wo"])


def _experts(fw, h, m, probe, forced):
    """The held experts' share of the routed sum, plus the shared expert.
    ``forced`` [b, s, k]: experts to take in place of the router's own picks
    (their weights are still this router's scores of them)."""
    dep = m["deployment"]
    off, held, k = dep["expert_offset"], m["n_routed_experts"], m["num_experts_per_tok"]
    score = jax.nn.sigmoid(h @ _F32(fw["router"]))
    biased = score + _F32(fw["bias"])
    top, idx = jax.lax.top_k(biased, k)
    if forced is not None:
        idx = forced
    picked = jnp.take_along_axis(score, idx, -1)
    wts = picked / jnp.sum(picked, -1, keepdims=True) * float(m["routed_scaling_factor"])
    if probe is not None:
        probe.append({"router_biased": biased, "router_cutoff": top[..., -1]})
    # weight of expert e for each token: 0 where it was not picked
    dense = jnp.sum(jnp.where(idx[..., None] == jnp.arange(off, off + held), wts[..., None], 0.0), -2)
    swiglu = lambda g, u, dn: (jax.nn.silu(h @ _F32(g)) * (h @ _F32(u))) @ _F32(dn)

    def one(y, e):
        g, u, dn, w_e = e
        return y + swiglu(g, u, dn) * w_e[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (fw["w_gate"], fw["w_up"], fw["w_down"], jnp.moveaxis(dense, -1, 0)))
    return y + swiglu(fw["s_gate"], fw["s_up"], fw["s_down"])


def hidden_states(params, tokens, m: dict, probe=None, forced=None):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32.  ``probe``
    (a list) collects, per full layer, the index scores and cut-offs, and per
    expert layer the biased router scores and cut-offs.  ``forced`` (an
    iterator, in the same order: a key mask [b, s, s] per full layer, experts
    [b, s, k] per expert layer) replaces the reference's own selections by
    given ones: selection is discontinuous, so a comparison of LOGITS is made
    on the same selections, and the selections are held to the reference's
    scores separately."""
    eps, layers = m["rms_norm_eps"], params["layers"]
    kinds, n_dense = _attn_kinds(m), m["first_k_dense_replace"]
    seen = {"full_attention": 0, "sliding_attention": 0}
    stack = {"full_attention": "full", "sliding_attention": "sliding"}
    with jax.default_matmul_precision("highest"):
        x = _F32(params["embed"]["embedding"])[tokens]
        for l, kind in enumerate(_kinds(m)):
            aw = layers[stack[kind]][seen[kind]]
            seen[kind] += 1
            h = _rms(x, layers["attn_norm"]["scale"][l], eps)
            own = forced is None or kind != "full_attention"
            x = x + _attention_layer(aw, h, kinds[kind], m, probe,
                                     None if own else next(forced))
            h = _rms(x, layers["mlp_norm"]["scale"][l], eps)
            if l < n_dense:
                w = layers["mlp"][l]
                x = x + (jax.nn.silu(h @ _F32(w["w_gate"])) * (h @ _F32(w["w_up"]))) @ _F32(w["w_down"])
            else:
                x = x + _experts(layers["moe"][l - n_dense], h, m, probe,
                                 None if forced is None else next(forced))
        return _rms(x, params["final_norm"]["scale"], eps)


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32."""
    h = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"])


def probe(params, tokens, m: dict, forced=None):
    """(logits, what the selections were made from): for the serving driver,
    which holds the program's picks against the reference's cut-offs and, with
    ``forced`` (a list, see ``hidden_states``), its logits against the
    reference's on the program's own selections."""
    seen: list = []
    h = hidden_states(params, tokens, m, seen, None if forced is None else iter(forced))
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"]), seen


def make_loss_fn(m: dict):
    """``loss(params, batch, rng=None)``: token-mean next-token cross entropy
    of ``batch["input_ids"]`` [b, s+1]."""

    def loss(params, batch, rng=None):
        ids = batch["input_ids"]
        lg = logits(params, ids[:, :-1], m)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return loss


# ---------------------------------------------------------------------------
# what a token requires
# ---------------------------------------------------------------------------
def _attn_params(d: int, a: dict) -> int:
    h = a["heads"]
    return (d * a["q_rank"] + a["q_rank"] * h * (a["nope"] + a["rope"])
            + d * (a["kv_rank"] + a["rope"]) + a["kv_rank"] * h * (a["nope"] + a["v"])
            + d * h + h * a["v"] * d)


def matmul_params(m: dict) -> int:
    """Parameters a token's forward pass multiplies by HERE: attention and
    indexer projections, the dense block, and of an expert layer the router,
    the shared expert and the token's expected share of held experts
    (``num_experts_per_tok`` x held / routed), the head's held rows."""
    d, kinds = m["hidden_size"], _attn_kinds(m)
    total = m["deployment"]["n_routed_experts_total"]
    per_tok = m["num_experts_per_tok"] * m["n_routed_experts"] / total
    n = d * m["vocab_size"]
    for l, kind in enumerate(_kinds(m)):
        n += _attn_params(d, kinds[kind])
        if kind == "full_attention":
            n += m["q_lora_rank"] * m["index_n_heads"] * m["index_head_dim"] \
                + d * (m["index_head_dim"] + m["index_n_heads"])
        if l < m["first_k_dense_replace"]:
            n += 3 * d * m["intermediate_size"]
        else:
            n += d * total + 3 * d * m["moe_intermediate_size"] * (m["n_shared_experts"] + per_tok)
    return int(n)


def attention_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token's attention over a context of ``ctx`` keys,
    absorbed form: the indexer scores every key (2 J D each), a full layer
    attends min(ctx, index_topk) rows and a sliding layer min(ctx, window), at
    2 H (2 r_kv + rope) a row."""
    kinds, fl = _attn_kinds(m), 0.0
    for kind in _kinds(m):
        a = kinds[kind]
        row = 2.0 * a["heads"] * (2 * a["kv_rank"] + a["rope"])
        if kind == "full_attention":
            fl += 2.0 * m["index_n_heads"] * m["index_head_dim"] * ctx
            fl += row * min(ctx, m["index_topk"])
        else:
            fl += row * min(ctx, a["window"])
    return fl


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's attention
    at the mean context (seq+1)/2.  (No training cell runs this architecture;
    the serving rooflines use ``attention_flops_per_token``.)"""
    return 6.0 * matmul_params(m) + 3.0 * attention_flops_per_token(m, (seq + 1) / 2)
