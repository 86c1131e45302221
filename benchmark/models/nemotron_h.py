"""``model_type`` "nemotron_h": what the harness needs from this architecture
(the five callables ``models/__init__.py`` lists), and ``probe`` for the
serving driver's check of what the routers PICKED.

The plain reference is the Nemotron-H language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no cache, no chunked form,
no batching of the recurrence (``lax.scan`` one token at a time), dense causal
mask, every held expert on every token, masked by what the router picked.  It
reads the program's parameter tree (``models/latent.py``: ``layers/norm`` and
one tuple of per-layer trees per kind), so both sides run on the same weights.

``d`` = ``hidden_size``, eps = ``layer_norm_epsilon``, ``RMSNorm(x) = x
rsqrt(mean(x^2) + eps) w``.  Block ``l`` is of the kind
``hybrid_override_pattern[l]`` and is ONE norm and ONE mixer,
``x <- x + mixer_l(RMSNorm_l(x))``; after the last block ``norm_f`` and the
untied head.  No bias but the convolution's.

- ``M``, Mamba-2.  ``H`` = ``mamba_num_heads``, ``P`` = ``mamba_head_dim``,
  ``d_in = H P``, ``G`` = ``n_groups``, ``N`` = ``ssm_state_size``, ``K`` =
  ``conv_kernel``.  ``[z | xBC | dt] = W_in u`` of widths ``d_in | d_in + 2 G N
  | H``.  ``xBC_t <- silu(b + sum_{j<K} w_j * xBC_{t-K+1+j})``, depthwise, zeros
  before the first token.  ``xBC`` splits into ``x`` [H, P], ``B``, ``C`` [G, N];
  head ``h`` reads group ``h // (H / G)``.  ``dt_{t,h} = softplus(dt + dt_bias_h)``,
  ``A_h = -exp(A_log_h)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` with
  ``S_h`` [P, N]; ``y_t = S_t C_t + D_h x_t``; ``y <- RMSNorm_grouped(y *
  silu(z))`` over each of the ``G`` groups of ``d_in / G`` channels (the gate
  BEFORE the norm); ``out = W_out y``.
- ``*``, attention.  ``num_attention_heads`` query and ``num_key_value_heads``
  KV heads of ``head_dim``, causal softmax at ``head_dim^-1/2``, NO positional
  embedding (the family's modelling code applies none; ``rope_theta`` and
  ``partial_rotary_factor`` are keys it does not read).
- ``E``, LatentMoE.  ``s = sigmoid(W_g h)`` in float32 over all
  ``deployment.n_routed_experts_total`` experts; the ``num_experts_per_tok``
  largest of ``s + e_score_correction_bias`` are picked (``n_group`` =
  ``topk_group`` = 1: no group limit); ``w_e = routed_scaling_factor s_e / sum
  of the picked s``: the bias selects and does not weigh.  ``l = W_down h`` (to
  ``moe_latent_size``); ``E_e(l) = W2_e relu(W1_e l)^2`` (no gate matrix);
  ``routed = W_up sum_e w_e E_e(l)``; the shared expert at full width,
  ``W2_s relu(W1_s h)^2``; ``out = routed + shared``.  No norm on the latent.
  ONLY the experts held here (``deployment.expert_offset`` .. +
  ``n_routed_experts``) are computed: the partial sum an expert-parallel member
  hands on, in program and reference alike.
- Left out: the MTP layer (``num_nextn_predict_layers``,
  ``mtp_hybrid_override_pattern``): serving without self-drafting.
  ``time_step_*`` and ``rescale_prenorm_residual`` only initialise;
  ``chunk_size`` and ``moe_shared_expert_overlap`` are schedules, not
  mathematics.  Every reading the source does not settle is under the
  configuration file's ``assumed``.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

KINDS = {"M": "mamba", "*": "gqa", "E": "experts"}
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


def _kinds(m: dict):
    return [KINDS[c] for c in m["hybrid_override_pattern"][: m["num_hidden_layers"]]]


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the blocks."""
    from deepspeed_tpu.models.latent import Gqa, LatentSpec, Mamba
    from deepspeed_tpu.models.transformer import TransformerConfig

    if model["mlp_hidden_act"] != "relu2" or model["mamba_hidden_act"] != "silu" \
            or model["n_group"] != 1 or model["topk_group"] != 1 \
            or not model["norm_topk_prob"] or not model["use_conv_bias"] \
            or model["use_bias"] or model["mamba_proj_bias"] or model["mlp_bias"] \
            or model["attention_bias"] or model["n_shared_experts"] != 1 \
            or model["tie_word_embeddings"]:
        raise ValueError("only the published nemotron_h block is mapped here")
    dep = model["deployment"]
    spec = LatentSpec(
        layer_kinds=tuple(_kinds(model)), full=None, sliding=None, index_heads=0,
        index_dim=0, index_topk=0, first_dense=0,
        n_routed=dep["n_routed_experts_total"], n_held=model["n_routed_experts"],
        held_offset=dep["expert_offset"], experts_per_tok=model["num_experts_per_tok"],
        moe_width=model["moe_intermediate_size"], n_shared=model["n_shared_experts"],
        routed_scale=float(model["routed_scaling_factor"]),
        mamba=Mamba(num_heads=model["mamba_num_heads"], head_dim=model["mamba_head_dim"],
                    n_groups=model["n_groups"], state=model["ssm_state_size"],
                    conv=model["conv_kernel"], chunk=model["chunk_size"]),
        gqa=Gqa(num_heads=model["num_attention_heads"],
                num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"]),
        expert_form="relu2", moe_latent=model["moe_latent_size"],
        shared_width=model["moe_shared_expert_intermediate_size"])
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["layer_norm_epsilon"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=dtypes[model["torch_dtype"]], latent=spec)
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _F32(scale)


def recurrence(x, bm, cm, dt, a):
    """The state-space recurrence, one token at a time, float32: x [b, s, H, P],
    bm and cm [b, s, R, N] (head ``h`` reads group ``h // (H / R)``), dt
    [b, s, H] (a token with ``dt`` = 0 leaves the state as it was), a [H] ->
    (y [b, s, H, P] less the skip, the state after the last token [b, H, P, N])."""
    f32 = lambda t: t.astype(jnp.float32)
    x, bm, cm, dt, a = map(f32, (x, bm, cm, dt, a))
    (b, _, h, p), n = x.shape, bm.shape[-1]
    bm, cm = (jnp.repeat(t, h // t.shape[2], axis=2) for t in (bm, cm))

    def token(state, t):
        x_t, b_t, c_t, dt_t = t                              # [b,h,p] [b,h,n] [b,h,n] [b,h]
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    first = lambda t: jnp.moveaxis(t, 1, 0)
    last, y = jax.lax.scan(token, jnp.zeros((b, h, p, n), jnp.float32),
                           (first(x), first(bm), first(cm), first(dt)))
    return jnp.moveaxis(y, 0, 1), last


def _mamba(w, u, m):
    """u [b, s, d] -> [b, s, d]: the recurrence, one token at a time."""
    b, s, _ = u.shape
    h, p, g, n, k = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                     m["ssm_state_size"], m["conv_kernel"])
    d_in = h * p
    zxd = u @ _F32(w["w_in"])
    z, xbc, dt = zxd[..., :d_in], zxd[..., d_in:2 * d_in + 2 * g * n], zxd[..., 2 * d_in + 2 * g * n:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))  # zeros before the first token
    conv = _F32(w["conv_b"]) + sum(_F32(w["conv_w"])[j] * padded[:, j:j + s] for j in range(k))
    conv = jax.nn.silu(conv)
    x = conv[..., :d_in].reshape(b, s, h, p)
    bm = conv[..., d_in:d_in + g * n].reshape(b, s, g, n)
    cm = conv[..., d_in + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + _F32(w["dt_bias"]))            # [b, s, h]
    y, _ = recurrence(x, bm, cm, dt, -jnp.exp(_F32(w["a_log"])))
    y = y + _F32(w["d_skip"])[:, None] * x
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    yg = y.reshape(b, s, g, d_in // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + m["layer_norm_epsilon"])
    return (yg.reshape(b, s, d_in) * _F32(w["norm"])) @ _F32(w["w_out"])


def _attention(w, u, m):
    b, s, _ = u.shape
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (u @ _F32(w["wq"])).reshape(b, s, hq, hd)
    k = jnp.repeat((u @ _F32(w["wk"])).reshape(b, s, hkv, hd), hq // hkv, axis=2)
    v = jnp.repeat((u @ _F32(w["wv"])).reshape(b, s, hkv, hd), hq // hkv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    sc = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return o.reshape(b, s, hq * hd) @ _F32(w["wo"])


def _experts(w, u, m, probe, forced):
    """The held experts' share of the routed sum through ``W_up``, plus the
    shared expert.  ``forced`` [b, s, k]: experts to take in place of the
    router's own picks (their weights are still this router's scores)."""
    dep = m["deployment"]
    off, held, k = dep["expert_offset"], m["n_routed_experts"], m["num_experts_per_tok"]
    score = jax.nn.sigmoid(u @ _F32(w["router"]))
    biased = score + _F32(w["bias"])
    top, idx = jax.lax.top_k(biased, k)
    if forced is not None:
        idx = forced
    picked = jnp.take_along_axis(score, idx, -1)
    wts = picked / jnp.sum(picked, -1, keepdims=True) * float(m["routed_scaling_factor"])
    if probe is not None:
        probe.append({"router_biased": biased, "router_cutoff": top[..., -1]})
    dense = jnp.sum(jnp.where(idx[..., None] == jnp.arange(off, off + held), wts[..., None], 0.0), -2)
    relu2 = lambda x, up, dn: jnp.square(jax.nn.relu(x @ _F32(up))) @ _F32(dn)
    lat = u @ _F32(w["w_lat_down"])

    def one(y, e):
        up, dn, w_e = e
        return y + relu2(lat, up, dn) * w_e[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                        (w["w_up"], w["w_down"], jnp.moveaxis(dense, -1, 0)))
    return y @ _F32(w["w_lat_up"]) + relu2(u, w["s_up"], w["s_down"])


def hidden_states(params, tokens, m: dict, probe=None, forced=None):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32.  ``probe``
    (a list) collects per expert layer the biased router scores and cut-offs;
    ``forced`` (an iterator of experts [b, s, k], one per expert layer)
    replaces the reference's own picks: selection is discontinuous, so LOGITS
    are compared on the same picks and the picks are held to the reference's
    scores separately."""
    eps, layers, seen = m["layer_norm_epsilon"], params["layers"], {}
    with jax.default_matmul_precision("highest"):
        x = _F32(params["embed"]["embedding"])[tokens]
        for l, kind in enumerate(_kinds(m)):
            w = layers[kind][seen.get(kind, 0)]
            seen[kind] = seen.get(kind, 0) + 1
            u = _rms(x, layers["norm"]["scale"][l], eps)
            if kind == "mamba":
                x = x + _mamba(w, u, m)
            elif kind == "gqa":
                x = x + _attention(w, u, m)
            else:
                x = x + _experts(w, u, m, probe, None if forced is None else next(forced))
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
    projections, of an expert layer the router, the latent pair, the shared
    expert and the token's expected share of held experts
    (``num_experts_per_tok`` x held / routed), the head's held rows."""
    d, r = m["hidden_size"], m["moe_latent_size"]
    h, p, g, n = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"]
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    total = m["deployment"]["n_routed_experts_total"]
    per_tok = m["num_experts_per_tok"] * m["n_routed_experts"] / total
    per_kind = {
        "mamba": d * (2 * h * p + 2 * g * n + h) + h * p * d,
        "gqa": d * hd * (2 * hq + 2 * hkv),
        "experts": d * total + 2 * d * r + 2 * d * m["moe_shared_expert_intermediate_size"]
        + 2 * r * m["moe_intermediate_size"] * per_tok,
    }
    return int(d * m["vocab_size"] + sum(per_kind[k] for k in _kinds(m)))


def mixer_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token outside the matmuls by parameters: per
    state-space block the state's update and read-out (3 H P N multiply-adds)
    and the convolution; per attention block ``ctx`` keys at 4 Hq hd."""
    h, p, g, n, k = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                     m["ssm_state_size"], m["conv_kernel"])
    kinds = _kinds(m)
    ssm = 6.0 * h * p * n + 2.0 * k * (h * p + 2 * g * n)
    attn = 4.0 * m["num_attention_heads"] * m["head_dim"] * ctx
    return kinds.count("mamba") * ssm + kinds.count("gqa") * attn


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's mixers at
    the mean context (seq+1)/2.  (No training cell runs this architecture.)"""
    return 6.0 * matmul_params(m) + 3.0 * mixer_flops_per_token(m, (seq + 1) / 2)
