"""``model_type`` "deepseek_v2": what the harness needs from this architecture
(the five callables ``models/__init__.py`` lists), and ``probe`` for the serving
driver's checks of what the routers PICKED.

The plain reference is DeepSeek-V2's language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no cache, no kernels and
NO ABSORBED FORM: keys and values are decompressed per head from the latent
(``k_h = [c_kv W_uk,h ; k_rope]``, ``v_h = c_kv W_uv,h``: plain multi-head
attention), causal over every earlier position; an expert layer runs EVERY held
expert on every token and masks by what the router picked.  It reads the
program's parameter tree (``models/latent.py``: ``layers/every`` a tuple of
per-layer trees), so both sides run on the same weights.

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, no bias anywhere, untied head.
Layer ``l``: ``x <- x + attn_l(rms(x)); x <- x + ffn_l(rms(x))``; final norm; head.

- Attention (``H`` = ``num_attention_heads``): ``c_q = rms(h W_dq)``
  (``q_lora_rank``), ``q = c_q W_uq`` -> per head ``[q_nope | q_rope]``; ``[c |
  k_r] = h W_dkv`` (``kv_lora_rank`` | ``qk_rope_head_dim``), ``c_kv = rms(c)``;
  ``q_rope`` and ``k_r`` rotated (``k_r`` ONE a token for all heads); per head
  ``k_nope = c_kv W_uk``, ``v = c_kv W_uv``; ``score(t, s) = scale (q_nope .
  k_nope + q_rope . k_rope)`` for ``s <= t``, softmax in float32, ``o = sum p
  v``, ``y = concat(o) W_o``.  No gate, no rescale of ``c_q`` / ``c_kv``.
- Rotary: theta ``rope_theta`` on the ``qk_rope_head_dim`` dims under YaRN
  (``rope_scaling``): ``inv_i = theta^(-2i/r)``; ``c(t) = r ln(orig / (2 pi t)) /
  (2 ln theta)``, ``lo = max(floor(c(beta_fast)), 0)``, ``hi = min(ceil(c(
  beta_slow)), r - 1)``, ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``, ``inv'_i
  = inv_i / factor x ramp_i + inv_i (1 - ramp_i)``; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` with ``mscale(f,
  m) = 0.1 m ln f + 1``; ``scale = (nope + rope)^-1/2 x mscale(factor,
  mscale_all_dim)^2``.  The source pairs the rope dims interleaved and re-orders
  them before ``rotate_half``; with seeded weights that is a relabelling of
  columns: half-split here (the configuration's ``assumed``).
- Feed-forward: the ``first_k_dense_replace`` leading layers a SwiGLU of
  ``intermediate_size``.  After them ``s = softmax(h W_r)`` in float32 over all
  ``deployment.n_routed_experts_total`` experts; ``g_j`` = the largest ``s`` in
  group ``j`` (``n_group`` groups of consecutive experts); the ``topk_group``
  groups of largest ``g`` kept; the ``num_experts_per_tok`` largest ``s`` inside
  them picked; ``y = routed_scaling_factor sum_picked s_e E_e(h) + S(h)``, ``s_e``
  NOT renormalised (``norm_topk_prob`` false), ``E`` a SwiGLU of
  ``moe_intermediate_size``, ``S`` one SwiGLU of ``n_shared_experts`` times that.
  ONLY the experts held here (``deployment.expert_offset`` .. +
  ``n_routed_experts``) are computed, plus the shared expert: the partial sum an
  expert-parallel member hands on.  ``seq_aux`` and the auxiliary losses are
  training's and are left out.

Everything is computed in BLOCKS so that a request of 17k tokens fits beside the
served model: attention a group of heads and a block of query rows at a time,
the feed-forward a block of tokens at a time.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 16      # heads decompressed and attended at once
Q_BLOCK = 256        # query rows scored against every key at once
TOKEN_BLOCK = 2048   # tokens a block of the feed-forward
_WEIGHTS_AS = None   # the control's precision, while ``weights_rounded_to`` is open
# a control of the MATHEMATICS, while ``departure`` is open: the reference
# computes something else in one place and has to come out NOT correct
_DEPARTURE = None
DEPARTURES = ("no_softmax_mscale", "no_yarn", "no_group_limit", "routing_renormalised",
              "routing_not_scaled")


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


@contextlib.contextmanager
def departure(name: str):
    """Inside (at TRACE time), the reference leaves ``name`` (``DEPARTURES``)
    out of the mathematics: the serving driver's controls of what ``correct``
    can see."""
    global _DEPARTURE
    if name not in DEPARTURES:
        raise ValueError(f"no departure {name!r}; there are {DEPARTURES}")
    _DEPARTURE = name
    try:
        yield
    finally:
        _DEPARTURE = None


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the layers."""
    from deepspeed_tpu.models.latent import LatentAttn, LatentSpec, Yarn
    from deepspeed_tpu.models.transformer import TransformerConfig

    r = model["rope_scaling"]
    if model["hidden_act"] != "silu" or model["scoring_func"] != "softmax" \
            or model["topk_method"] != "group_limited_greedy" or model["norm_topk_prob"] \
            or model["attention_bias"] or model["moe_layer_freq"] != 1 \
            or model["tie_word_embeddings"] or r["type"] != "yarn":
        raise ValueError("only the published deepseek_v2 block is mapped here")
    dep = model["deployment"]
    yarn = Yarn(factor=float(r["factor"]), original_max=int(r["original_max_position_embeddings"]),
                beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
                attention_factor=yarn_mscale(r["factor"], r["mscale"])
                / yarn_mscale(r["factor"], r["mscale_all_dim"]))
    attn = LatentAttn(
        num_heads=model["num_attention_heads"], q_rank=model["q_lora_rank"],
        kv_rank=model["kv_lora_rank"], nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]), rope_scaling=yarn,
        scale_factor=yarn_mscale(r["factor"], r["mscale_all_dim"]) ** 2, gate=False)
    spec = LatentSpec(
        layer_kinds=("every",) * model["num_hidden_layers"], full=None, sliding=None,
        index_heads=0, index_dim=0, index_topk=0, first_dense=model["first_k_dense_replace"],
        n_routed=dep["n_routed_experts_total"], n_held=model["n_routed_experts"],
        held_offset=dep["expert_offset"], experts_per_tok=model["num_experts_per_tok"],
        moe_width=model["moe_intermediate_size"], n_shared=model["n_shared_experts"],
        routed_scale=float(model["routed_scaling_factor"]), rescale_lora=False,
        routing="group_limited", every=attn, n_group=model["n_group"],
        topk_group=model["topk_group"])
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"], num_heads=model["num_attention_heads"],
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
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _F32(w)


def rotary_table(m: dict):
    """(the ``qk_rope_head_dim / 2`` frequencies float32, what cos and sin are
    multiplied by), YaRN's formula written out (module docstring)."""
    rot, theta, r = m["qk_rope_head_dim"], float(m["rope_theta"]), m["rope_scaling"]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if _DEPARTURE == "no_yarn":
        return inv, 1.0
    orig = r["original_max_position_embeddings"]
    c = lambda turns: rot * math.log(orig / (2 * math.pi * turns)) / (2 * math.log(theta))
    lo, hi = max(math.floor(c(r["beta_fast"])), 0), min(math.ceil(c(r["beta_slow"])), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    factor = yarn_mscale(r["factor"], r["mscale"]) / yarn_mscale(r["factor"], r["mscale_all_dim"])
    return inv / r["factor"] * ramp + inv * (1.0 - ramp), factor


def softmax_scale(m: dict) -> float:
    scale = float(m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    if _DEPARTURE in ("no_softmax_mscale", "no_yarn"):
        return scale
    r = m["rope_scaling"]
    return scale * yarn_mscale(r["factor"], r["mscale_all_dim"]) ** 2


def _rotary(x, m: dict):
    """x [b, s, h, rope]: rotate-half at positions 0..s-1."""
    s, rot = x.shape[1], x.shape[-1]
    inv, factor = rotary_table(m)
    ang = jnp.arange(s, dtype=jnp.float32)[None, :, None, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(w, u, m: dict):
    b, s, _ = u.shape
    hh, eps = m["num_attention_heads"], m["rms_norm_eps"]
    nope, rope, vd, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    c_q = _rms(u @ _F32(w["w_dq"]), w["q_norm"], eps)
    kv = u @ _F32(w["w_dkv"])
    c_kv = _rms(kv[..., :r], w["kv_norm"], eps)
    k_r = _rotary(kv[:, :, None, r:], m)                               # [b, s, 1, rope]
    scale = softmax_scale(m)
    hb = math.gcd(hh, HEAD_BLOCK)
    blk = math.gcd(s, Q_BLOCK)
    heads = lambda a, n: jnp.moveaxis(_F32(a).reshape(a.shape[0], hh // hb, hb, n), 1, 0)

    def head_group(y, ws):
        """``hb`` heads: decompressed keys and values, a block of query rows at
        a time against every key under the causal mask; their part of ``W_o``'s
        product is added to ``y`` (all heads' values of a long request at once
        would not fit beside the served model)."""
        uq, uk, uv, wo = ws                                            # [rank, hb, n]; [hb v, d]
        q = jnp.einsum("bsr,rhn->bshn", c_q, uq)
        q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], m)], -1)
        k = jnp.concatenate([jnp.einsum("bsr,rhn->bshn", c_kv, uk),
                             jnp.broadcast_to(k_r, (b, s, hb, rope))], -1)
        v = jnp.einsum("bsr,rhn->bshn", c_kv, uv)

        def rows(at):
            q_b = jax.lax.dynamic_slice_in_dim(q, at, blk, axis=1)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * scale
            ok = (at + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1), v)

        o = jax.lax.map(rows, jnp.arange(0, s, blk))                   # [s / blk, b, blk, hb, v]
        return y + jnp.moveaxis(o, 0, 1).reshape(b, s, hb * vd) @ wo, None

    wo = _F32(w["wo"]).reshape(hh // hb, hb * vd, -1)
    y, _ = jax.lax.scan(head_group, jnp.zeros_like(u),
                        (heads(w["w_uq"], nope + rope), heads(w["w_uk"], nope),
                         heads(w["w_uv"], vd), wo))
    return y


def _swiglu(x, gt, up, dn):
    return (jax.nn.silu(x @ _F32(gt)) * (x @ _F32(up))) @ _F32(dn)


def _by_token_block(fn, x, *per_token):
    """``fn(block of x [b, T, d], block of each per-token array)`` over blocks
    of ``TOKEN_BLOCK`` tokens, joined back."""
    s = x.shape[1]
    blk = math.gcd(s, TOKEN_BLOCK)
    split = lambda a: jnp.moveaxis(a.reshape(a.shape[0], s // blk, blk, *a.shape[2:]), 1, 0)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(map(split, (x, *per_token))))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], s, *out.shape[3:])


def route(w, u, m: dict):
    """(softmax scores [b, s, E] float32, each group's largest [b, s, n_group],
    the kept groups as a mask over the experts, the experts picked [b, s, k])."""
    k, ng, tg = m["num_experts_per_tok"], m["n_group"], m["topk_group"]
    s = jax.nn.softmax(u @ _F32(w["router"]), axis=-1)
    best = jnp.max(s.reshape(*s.shape[:-1], ng, -1), axis=-1)
    third = jax.lax.top_k(best, tg)[0][..., -1:]
    # the ``topk_group`` groups of largest maximum; equal maxima: the lower group
    rank = jnp.sum((best[..., None, :] > best[..., :, None])
                   | ((best[..., None, :] == best[..., :, None])
                      & (jnp.arange(ng)[None, :] < jnp.arange(ng)[:, None])), axis=-1)
    kept = rank < tg if _DEPARTURE != "no_group_limit" else jnp.ones_like(best, bool)
    inside = jnp.repeat(kept, s.shape[-1] // ng, axis=-1)
    top, idx = jax.lax.top_k(jnp.where(inside, s, -1.0), k)
    return s, best, third[..., 0], inside, top[..., -1], idx


def _experts(w, u, m: dict, probe, forced):
    """The held experts' share of the routed sum plus the shared expert.
    ``forced`` [b, s, k]: experts to take in place of the router's own picks
    (their weights are still this router's scores of them)."""
    dep = m["deployment"]
    off, held = dep["expert_offset"], m["n_routed_experts"]
    s, best, third, inside, cutoff, idx = route(w, u, m)
    if probe is not None:
        probe.append({"router_scores": s, "router_cutoff": cutoff, "group_best": best,
                      "group_cutoff": third, "router_inside": inside})
    if forced is not None:
        idx = forced
    wts = jnp.take_along_axis(s, idx, -1)
    if _DEPARTURE == "routing_renormalised":
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    if _DEPARTURE != "routing_not_scaled":
        wts = wts * m["routed_scaling_factor"]
    # weight of expert e for each token: 0 where it was not picked
    dense = jnp.sum(jnp.where(idx[..., None] == jnp.arange(off, off + held), wts[..., None], 0.0), -2)

    def block(u_b, dense_b):
        def one(y, e):
            gt, up, dn, w_e = e
            return y + _swiglu(u_b, gt, up, dn) * w_e[..., None], None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u_b),
                            (w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(dense_b, -1, 0)))
        return y + _swiglu(u_b, w["s_gate"], w["s_up"], w["s_down"])

    return _by_token_block(block, u, dense)


def hidden_states(params, tokens, m: dict, probe=None, forced=None):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32.  ``probe``
    (a list) collects per expert layer the router's scores, the groups' largest
    and the cut-offs; ``forced`` (an iterator of experts [b, s, k], one per
    expert layer) replaces the reference's own picks: selection is
    discontinuous, so LOGITS are compared on the same picks and the picks are
    held to the reference's scores separately."""
    eps, layers, n_dense = m["rms_norm_eps"], params["layers"], m["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        x = _F32(params["embed"]["embedding"])[tokens]
        for l in range(m["num_hidden_layers"]):
            u = _rms(x, layers["attn_norm"]["scale"][l], eps)
            x = x + _attention(layers["every"][l], u, m)
            u = _rms(x, layers["mlp_norm"]["scale"][l], eps)
            if l < n_dense:
                fw = layers["mlp"][l]
                x = x + _by_token_block(
                    lambda u_b: _swiglu(u_b, fw["w_gate"], fw["w_up"], fw["w_down"]), u)
            else:
                x = x + _experts(layers["moe"][l - n_dense], u, m, probe,
                                 None if forced is None else next(forced))
        return _rms(x, params["final_norm"]["scale"], eps)


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32."""
    h = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"])


def probe(params, tokens, m: dict, forced=None, at=0, rows=None):
    """(logits, what the routers' picks were made from, layer by layer), for
    the serving driver; with ``forced`` (a list, see ``hidden_states``) the
    logits are the reference's on the program's own picks; with ``rows``
    (static) only the ``rows`` positions from ``at`` on get logits ([b, rows,
    vocab]: a long request's 25 600 logits a position would not fit beside the
    served model)."""
    seen: list = []
    h = hidden_states(params, tokens, m, seen, None if forced is None else iter(forced))
    if rows is not None:
        h = jax.lax.dynamic_slice_in_dim(h, at, rows, axis=1)
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"]), seen


def uncut_expert_layer(w, u, m: dict):
    """The expert layer with EVERY routed expert (``w`` holds all
    ``deployment.n_routed_experts_total`` of them) on u [b, s, d]: what the
    members' partial sums, the shared expert counted once, add up to."""
    whole = dict(m, n_routed_experts=m["deployment"]["n_routed_experts_total"],
                 deployment=dict(m["deployment"], expert_offset=0))
    with jax.default_matmul_precision("highest"):
        return _experts(w, u, whole, None, None)


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
def matmul_params(m: dict) -> int:
    """Parameters a token's forward pass multiplies by HERE: each layer's
    attention projections (``W_uk`` and ``W_uv`` among them: a token's own row
    is decompressed once, or its query and output absorbed through them), a
    dense layer's SwiGLU, of an expert layer the router, the shared experts and
    the token's expected share of held experts (``num_experts_per_tok`` x held /
    routed), the head's held rows."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    attn = d * m["q_lora_rank"] + m["q_lora_rank"] * h * (nope + rope) \
        + d * (m["kv_lora_rank"] + rope) + m["kv_lora_rank"] * h * (nope + vd) + h * vd * d
    total = m["deployment"]["n_routed_experts_total"]
    per_tok = m["num_experts_per_tok"] * m["n_routed_experts"] / total
    sparse = d * total + 3 * d * m["moe_intermediate_size"] * (m["n_shared_experts"] + per_tok)
    n_dense = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    return int(d * m["vocab_size"] + m["num_hidden_layers"] * attn
               + n_dense * 3 * d * m["intermediate_size"]
               + (m["num_hidden_layers"] - n_dense) * sparse)


def mixer_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token's attention over ``ctx`` keys in every
    layer, decompressed form: 2 (nope + rope) + 2 v a key and head."""
    per_key = 2.0 * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) + 2.0 * m["v_head_dim"]
    return m["num_hidden_layers"] * m["num_attention_heads"] * per_key * ctx


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's attention at
    the mean context (seq+1)/2.  (No training cell runs this architecture.)"""
    return 6.0 * matmul_params(m) + 3.0 * mixer_flops_per_token(m, (seq + 1) / 2)
