"""``model_type`` "qwen3_next": what the harness needs from this architecture
(the five callables ``models/__init__.py`` lists), and ``probe`` /
``recurrence`` for the serving driver's checks of what the routers PICKED and
of the state a Gated DeltaNet block is left KEEPING.

The plain reference is the Qwen3-Next language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no cache, no chunked form,
no batching of the recurrence (``lax.scan`` one token at a time), dense causal
mask (a block of query rows at a time against every key), every held expert on
every token, masked by what the router picked.  It
reads the program's parameter tree (``models/latent.py``: ``layers/attn_norm``,
``layers/mlp_norm`` and one tuple of per-layer trees per kind), so both sides
run on the same weights.

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, no bias anywhere.  RMSNorm is
zero-centred: ``rms(x) = x rsqrt(mean(x^2) + eps) (1 + w)``.  Block ``l`` is
``x <- x + mixer_l(rms(x)); x <- x + moe(rms(x))``; the mixer is full attention
where ``(l + 1) % full_attention_interval == 0`` and Gated DeltaNet elsewhere;
``decoder_sparse_step`` 1 and ``mlp_only_layers`` [] put the expert layer in
every block (``intermediate_size`` is unused).  Final norm, untied head.

- Gated DeltaNet.  ``Hk`` = ``linear_num_key_heads`` of ``Dk`` =
  ``linear_key_head_dim``, ``Hv`` = ``linear_num_value_heads`` of ``Dv`` =
  ``linear_value_head_dim``, ``K`` = ``linear_conv_kernel_dim``.  ``[q | k | v |
  z] = h W_qkvz`` of widths ``Hk Dk | Hk Dk | Hv Dv | Hv Dv``, ``[b | a] = h
  W_ba`` of ``Hv | Hv``.  ``[q | k | v] <- silu(sum_{j<K} w_j * [q | k |
  v]_{t-K+1+j})``, depthwise, zeros before the first token.  Value head ``j``
  reads key head ``j // (Hv / Hk)``.  Per value head: ``q^ = q rsqrt(|q|^2 +
  1e-6) Dk^-1/2``, ``k^ = k rsqrt(|k|^2 + 1e-6)``, ``beta_t = sigmoid(b_t)``,
  ``g_t = -exp(A_log_j) softplus(a_t + dt_bias_j)``, and with ``S`` [Dk, Dv]
  from zeros: ``S' = exp(g_t) S_{t-1}``, ``S_t = S' + k^_t (x) (beta_t (v_t -
  S'^T k^_t))``, ``o_t = S_t^T q^_t``.  ``y = (o rsqrt(mean(o^2) + eps) w_n)
  silu(z)`` per head (the norm BEFORE the gate, a plain weight), then ``W_o``.
- Gated attention.  ``W_q``: d -> ``num_attention_heads`` x 2 ``head_dim``,
  each head's output ``[q | gate]``; ``W_k``, ``W_v``: d ->
  ``num_key_value_heads`` x ``head_dim``.  ``q <- rms(q)``, ``k <- rms(k)`` over
  the head (zero-centred).  Rotary (rotate-half, ``rope_theta``) on the first
  ``partial_rotary_factor head_dim`` dims.  Causal softmax of ``q k^T
  head_dim^-1/2``; ``out = attn o sigmoid(gate)``; ``W_o``.
- Expert layer.  ``p = softmax(h W_r)`` over all
  ``deployment.num_experts_total`` experts; the ``num_experts_per_tok`` largest;
  ``w = p_top / sum p_top`` (``norm_topk_prob``); ``y = sum_e w_e W_down,e(
  silu(W_gate,e h) o W_up,e h) + sigmoid(h . w_sg) SwiGLU_shared(h)``.  ONLY the
  experts held here (``deployment.expert_offset`` .. + ``num_experts``) are
  computed: the partial sum an expert-parallel member hands on, in program and
  reference alike.
- Left out: the MTP layer; the source's interleaved layout of the rows of
  ``W_qkvz`` / ``W_ba`` (a permutation of seeded weights).  Every reading the
  source does not settle is under the configuration file's ``assumed``.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

_WEIGHTS_AS = None  # the control's precision, while ``weights_rounded_to`` is open
# a control of the MATHEMATICS, while ``departure`` is open: the reference
# computes something else in one place and has to come out NOT correct
_DEPARTURE = None
DEPARTURES = ("no_output_gate", "rotary_on_whole_head", "no_beta", "no_decay",
              "routing_not_renormalised")


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


def _kinds(m: dict):
    every = m["full_attention_interval"]
    return ["gattn" if (l + 1) % every == 0 else "gdn" for l in range(m["num_hidden_layers"])]


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the blocks."""
    from deepspeed_tpu.models.latent import GatedGqa, Gdn, LatentSpec
    from deepspeed_tpu.models.transformer import TransformerConfig

    if model["hidden_act"] != "silu" or model["decoder_sparse_step"] != 1 \
            or model["mlp_only_layers"] or not model["norm_topk_prob"] \
            or model["rope_scaling"] is not None or model["use_sliding_window"] \
            or model["tie_word_embeddings"]:
        raise ValueError("only the published qwen3_next block is mapped here")
    dep = model["deployment"]
    hd = model["head_dim"]
    spec = LatentSpec(
        layer_kinds=tuple(_kinds(model)), full=None, sliding=None, index_heads=0,
        index_dim=0, index_topk=0, first_dense=0,
        n_routed=dep["num_experts_total"], n_held=model["num_experts"],
        held_offset=dep["expert_offset"], experts_per_tok=model["num_experts_per_tok"],
        moe_width=model["moe_intermediate_size"], n_shared=1,
        shared_width=model["shared_expert_intermediate_size"],
        gdn=Gdn(num_k_heads=model["linear_num_key_heads"], k_dim=model["linear_key_head_dim"],
                num_v_heads=model["linear_num_value_heads"],
                v_dim=model["linear_value_head_dim"], conv=model["linear_conv_kernel_dim"],
                chunk=int(model.get("scan_chunk", 64))),
        gattn=GatedGqa(num_heads=model["num_attention_heads"],
                       num_kv_heads=model["num_key_value_heads"], head_dim=hd,
                       rope_dim=int(hd * model["partial_rotary_factor"]),
                       rope_theta=float(model["rope_theta"])),
        routing="softmax", shared_gate=True, unit_offset=True)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=hd,
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=dtypes[model["torch_dtype"]], latent=spec)
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + _F32(w))


def recurrence(q, k, v, g, beta):
    """The gated delta rule, one token at a time, float32: q, k [b, s, Hk, Dk]
    as the recurrence consumes them (normalised), v [b, s, Hv, Dv], g and beta
    [b, s, Hv] (a token with ``g`` = 0 and ``beta`` = 0 leaves the state as it
    was) -> (o [b, s, Hv, Dv], the state after the last token [b, Hv, Dk, Dv])."""
    f32 = lambda t: t.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    (b, _, hv, dv), dk = v.shape, q.shape[-1]
    q, k = (jnp.repeat(t, hv // t.shape[2], axis=2) for t in (q, k))

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t                       # [b,h,dk] [b,h,dk] [b,h,dv] [b,h] [b,h]
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.sum(state * k_t[..., None], axis=-2)   # S'^T k
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    first = lambda t: jnp.moveaxis(t, 1, 0)
    last, o = jax.lax.scan(token, jnp.zeros((b, hv, dk, dv), jnp.float32),
                           tuple(map(first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), last


def _deltanet(w, u, m):
    """u [b, s, d] -> [b, s, d]: the recurrence, one token at a time."""
    b, s, _ = u.shape
    hk, dk, hv, dv, kk = (m["linear_num_key_heads"], m["linear_key_head_dim"],
                          m["linear_num_value_heads"], m["linear_value_head_dim"],
                          m["linear_conv_kernel_dim"])
    kw, vw = hk * dk, hv * dv
    qkvz = u @ _F32(w["w_qkvz"])
    qkv, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
    ba = u @ _F32(w["w_ba"])
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(_F32(w["a_log"])) * jax.nn.softplus(ba[..., hv:] + _F32(w["dt_bias"]))
    if _DEPARTURE == "no_beta":
        beta = jnp.ones_like(beta)
    if _DEPARTURE == "no_decay":
        g = jnp.zeros_like(g)
    padded = jnp.pad(qkv, ((0, 0), (kk - 1, 0), (0, 0)))  # zeros before the first token
    conv = jax.nn.silu(sum(_F32(w["conv_w"])[j] * padded[:, j:j + s] for j in range(kk)))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = unit(conv[..., :kw].reshape(b, s, hk, dk)) * dk ** -0.5
    k = unit(conv[..., kw:2 * kw].reshape(b, s, hk, dk))
    v = conv[..., 2 * kw:].reshape(b, s, hv, dv)
    o, _ = recurrence(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m["rms_norm_eps"]) * _F32(w["norm"])
    return (o.reshape(b, s, vw) * jax.nn.silu(z)) @ _F32(w["w_out"])


def _rotary(x, theta: float, r: int):
    """x [b, s, h, hd]: rotate-half on the first ``r`` dims at positions 0..s-1."""
    s = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(s, dtype=jnp.float32)[None, :, None, None] * inv
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest], -1)


def _attention(w, u, m):
    b, s, _ = u.shape
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    qg = (u @ _F32(w["wq"])).reshape(b, s, hq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(b, s, hq * hd)
    k = (u @ _F32(w["wk"])).reshape(b, s, hkv, hd)
    v = (u @ _F32(w["wv"])).reshape(b, s, hkv, hd)
    r = hd if _DEPARTURE == "rotary_on_whole_head" else int(hd * m["partial_rotary_factor"])
    q = _rotary(_rms(q, w["q_norm"], eps), float(m["rope_theta"]), r)
    k = _rotary(_rms(k, w["k_norm"], eps), float(m["rope_theta"]), r)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))

    def rows(at):
        """A block of query rows against every key under the dense causal mask
        (all of a long sequence's [s, s] scores at once would not fit the chip)."""
        q_b = jax.lax.dynamic_slice_in_dim(q, at, blk, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * hd ** -0.5
        sc = jnp.where((at + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    blk = math.gcd(s, 128)
    o = jax.lax.map(rows, jnp.arange(0, s, blk))                   # [s / blk, b, blk, hq, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, hq * hd)
    if _DEPARTURE != "no_output_gate":
        o = o * jax.nn.sigmoid(gate)
    return o @ _F32(w["wo"])


def _experts(w, u, m, probe, forced):
    """The held experts' share of the routed sum plus the gated shared expert.
    ``forced`` [b, s, k]: experts to take in place of the router's own picks
    (their weights are still this router's probabilities)."""
    dep = m["deployment"]
    off, held, k = dep["expert_offset"], m["num_experts"], m["num_experts_per_tok"]
    logit = u @ _F32(w["router"])
    prob = jax.nn.softmax(logit, -1)
    top, idx = jax.lax.top_k(logit, k)
    if forced is not None:
        idx = forced
    wts = jnp.take_along_axis(prob, idx, -1)
    if _DEPARTURE != "routing_not_renormalised":
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    if probe is not None:  # the softmax is monotone: picks are held to the LOGITS' cut-off
        probe.append({"router_biased": logit, "router_cutoff": top[..., -1]})
    dense = jnp.sum(jnp.where(idx[..., None] == jnp.arange(off, off + held), wts[..., None], 0.0), -2)
    swiglu = lambda x, gt, up, dn: (jax.nn.silu(x @ _F32(gt)) * (x @ _F32(up))) @ _F32(dn)

    def one(y, e):
        gt, up, dn, w_e = e
        return y + swiglu(u, gt, up, dn) * w_e[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(dense, -1, 0)))
    shared = swiglu(u, w["s_gate"], w["s_up"], w["s_down"])
    return y + jax.nn.sigmoid(u @ _F32(w["w_sg"])) * shared


def hidden_states(params, tokens, m: dict, probe=None, forced=None):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32.  ``probe``
    (a list) collects per expert layer the router's logits and cut-offs;
    ``forced`` (an iterator of experts [b, s, k], one per expert layer)
    replaces the reference's own picks: selection is discontinuous, so LOGITS
    are compared on the same picks and the picks are held to the reference's
    scores separately."""
    eps, layers, seen = m["rms_norm_eps"], params["layers"], {}
    with jax.default_matmul_precision("highest"):
        x = _F32(params["embed"]["embedding"])[tokens]
        for l, kind in enumerate(_kinds(m)):
            w = layers[kind][seen.get(kind, 0)]
            seen[kind] = seen.get(kind, 0) + 1
            u = _rms(x, layers["attn_norm"]["scale"][l], eps)
            x = x + (_deltanet(w, u, m) if kind == "gdn" else _attention(w, u, m))
            u = _rms(x, layers["mlp_norm"]["scale"][l], eps)
            x = x + _experts(layers["moe"][l], u, m, probe,
                             None if forced is None else next(forced))
        return _rms(x, params["final_norm"]["scale"], eps)


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32."""
    h = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"])


def probe(params, tokens, m: dict, forced=None):
    """(logits, what the routers' picks were made from), for the serving
    driver; with ``forced`` (a list, see ``hidden_states``) the logits are the
    reference's on the program's own picks."""
    seen: list = []
    h = hidden_states(params, tokens, m, seen, None if forced is None else iter(forced))
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"]), seen


def uncut_expert_layer(w, u, m: dict):
    """The expert layer with EVERY routed expert (``w`` holds all
    ``deployment.num_experts_total`` of them) on u [b, s, d]: what the members'
    partial sums, the gated shared expert counted once, add up to."""
    whole = dict(m, num_experts=m["deployment"]["num_experts_total"],
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
    """Parameters a token's forward pass multiplies by HERE: the mixers'
    projections, of an expert layer the router, the shared expert and its gate
    and the token's expected share of held experts (``num_experts_per_tok`` x
    held / routed), the head's held rows."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    kw = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    hv = m["linear_num_value_heads"]
    vw = hv * m["linear_value_head_dim"]
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    total = m["deployment"]["num_experts_total"]
    per_tok = m["num_experts_per_tok"] * m["num_experts"] / total
    per_kind = {
        "gdn": d * (2 * kw + 2 * vw + 2 * hv) + vw * d,
        "gattn": d * hd * (3 * hq + 2 * hkv),
    }
    experts = d * total + d + 3 * d * m["shared_expert_intermediate_size"] + 3 * d * f * per_tok
    return int(d * m["vocab_size"] + sum(per_kind[k] + experts for k in _kinds(m)))


def mixer_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token outside the matmuls by parameters: per Gated
    DeltaNet block the state's decay, read, update and read-out (4 Hv Dk Dv
    multiply-adds) and the convolution; per attention block ``ctx`` keys at 4
    Hq hd."""
    hv, dk, dv = (m["linear_num_value_heads"], m["linear_key_head_dim"],
                  m["linear_value_head_dim"])
    conv_width = 2 * m["linear_num_key_heads"] * dk + hv * dv
    kinds = _kinds(m)
    gdn = 8.0 * hv * dk * dv + 2.0 * m["linear_conv_kernel_dim"] * conv_width
    attn = 4.0 * m["num_attention_heads"] * m["head_dim"] * ctx
    return kinds.count("gdn") * gdn + kinds.count("gattn") * attn


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's mixers at
    the mean context (seq+1)/2.  (No training cell runs this architecture.)"""
    return 6.0 * matmul_params(m) + 3.0 * mixer_flops_per_token(m, (seq + 1) / 2)
