"""``model_type`` "mellum": what the harness needs from this architecture (the
five callables ``models/__init__.py`` lists), and for the training driver's
``correct`` the reference's loss and gradients on the PROGRAM's expert picks,
what the routers' picks were made from, the masks' edges, and the controls.

The plain reference is the Mellum 2 language model in straightforward
``jax.numpy``: float32, every product at ``highest``, no kernels, a dense mask
(a block of query rows at a time against every key), every held expert on
every token, masked by what the router picked.  It reads the program's
parameter tree (``models/latent.py``: ``layers/attn_norm``, ``layers/mlp_norm``
and one tuple of per-layer trees per kind), so both sides run on the same
weights.  For layer l of kind sliding or full, x ``[n, 2304]``:

    a = rms(x) * w1                                   (eps 1e-6, plain weight)
    q = a Wq [32 x 128]   k = a Wk [4 x 128]   v = a Wv [4 x 128]        (no bias)
    q, k <- rms over the head's 128, a plain weight each, BEFORE rotary
    rotary on all 128 dims, theta 500000: sliding layers plain; full layers YaRN
        inv_i = theta^(-2i/128); c(t) = 128 ln(8192 / (2 pi t)) / (2 ln theta),
        lo = floor(c(32)), hi = ceil(c(1)), ramp_i = clip((i - lo) / (hi - lo), 0, 1),
        inv'_i = inv_i / 16 x ramp_i + inv_i (1 - ramp_i); cos and sin times
        attention_factor 1.2772588722239782
    p_ij = softmax_j(q_i . k_j / sqrt(128)) in float32 over j <= i, and on a
        sliding layer 0 <= i - j < 1024 (1024 keys with its own), 8 query heads a K-V head
    y = x + (p v) Wo
    b = rms(y) * w2;  r = softmax(b Wr) over ALL 64 experts in float32;
    T = the 8 largest;  w_e = r_e / sum_T r
    z = y + sum over e in T that is HELD HERE of  w_e * (silu(b Wg_e) * (b Wu_e)) Wd_e
        (width 896; no shared expert)
    logits = rms(z_L) * w_f  @  W_head [2304 x vocabulary held]
    loss = mean CE + lambda * mean_l (64 * sum_e f_e P_e)
        f_e = share of the layer's (token, pick) pairs on expert e (all 64, no
        gradient), P_e = mean over tokens of r_e, lambda = training.router_aux_loss_coef

ONLY the experts held here (``deployment.expert_offset`` .. + ``num_experts``)
are computed; ids and the loss run over the vocabulary rows held.  The file
keeps ``layer_types`` and ``mlp_layer_types`` whole; layer ``l <
num_hidden_layers`` reads entry ``l``.  Every reading the source does not settle
is under the configuration file's ``assumed``.
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
DEPARTURES = ("no_window", "window_off_by_one", "no_yarn", "routing_not_renormalised",
              "aux_loss_dropped", "expert_offset_shifted")
KINDS = {"full_attention": "gattn", "sliding_attention": "wattn"}  # the program's names
Q_BLOCK = 512  # query rows a block of the dense mask


def _F32(a):
    """A weight (or an array already float32) as the reference uses it."""
    if _WEIGHTS_AS is not None and a.ndim >= 2:
        a = a.astype(_WEIGHTS_AS)
    return a.astype(jnp.float32)


@contextlib.contextmanager
def weights_rounded_to(dtype):
    """Inside (at TRACE time), the reference reads every weight matrix rounded
    to ``dtype``: the driver's control, one precision down, without a second
    copy of the weights on the device."""
    global _WEIGHTS_AS
    _WEIGHTS_AS = dtype
    try:
        yield
    finally:
        _WEIGHTS_AS = None


@contextlib.contextmanager
def departure(name: str):
    """Inside (at TRACE time), the reference leaves ``name`` (``DEPARTURES``)
    out of the mathematics: the driver's controls of what ``correct`` can see."""
    global _DEPARTURE
    if name not in DEPARTURES:
        raise ValueError(f"no departure {name!r}; there are {DEPARTURES}")
    _DEPARTURE = name
    try:
        yield
    finally:
        _DEPARTURE = None


def _layers(m: dict):
    """(layer type, feed-forward type) of each layer held."""
    n = m["num_hidden_layers"]
    return list(zip(m["layer_types"][:n], m["mlp_layer_types"][:n]))


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the blocks."""
    from deepspeed_tpu.models.latent import GatedGqa, LatentSpec, Yarn
    from deepspeed_tpu.models.transformer import TransformerConfig

    layers = _layers(model)
    if model["attention_bias"] or model["tie_word_embeddings"] or not model["norm_topk_prob"] \
            or model["hidden_act"] != "silu" or {f for _, f in layers} != {"sparse"} \
            or not model["use_sliding_window"]:
        raise ValueError("only the published mellum block is mapped here")
    hd, hkv, dep = model["head_dim"], model["num_key_value_heads"], model["deployment"]

    def kind(layer_type: str, window: int) -> GatedGqa:
        r = model["rope_parameters"][layer_type]
        yarn = None
        if r["rope_type"] == "yarn":
            yarn = Yarn(factor=float(r["factor"]),
                        original_max=int(r["original_max_position_embeddings"]),
                        beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
                        attention_factor=float(r["attention_factor"]))
        elif r["rope_type"] != "default":
            raise ValueError(f"rope_type {r['rope_type']!r} is not mapped here")
        return GatedGqa(num_heads=model["num_attention_heads"], num_kv_heads=hkv, head_dim=hd,
                        rope_dim=hd, rope_theta=float(r["rope_theta"]), window=window,
                        gate="none", rope_scaling=yarn)

    spec = LatentSpec(
        layer_kinds=tuple(KINDS[t] for t, _ in layers), full=None, sliding=None,
        index_heads=0, index_dim=0, index_topk=0, first_dense=0,
        n_routed=dep["num_experts_total"], n_held=model["num_experts"],
        held_offset=dep["expert_offset"], experts_per_tok=model["num_experts_per_tok"],
        moe_width=model["moe_intermediate_size"], n_shared=0,
        gattn=kind("full_attention", 0),
        wattn=kind("sliding_attention", int(model["sliding_window"])),
        routing="softmax", shared_gate=False, unit_offset=False,
        router_aux_loss_coef=float(model["training"]["router_aux_loss_coef"]))
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"], num_kv_heads=hkv, head_dim=hd,
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
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _F32(w)


def rotary_table(r: dict, rot: int):
    """(the ``rot / 2`` frequencies of a kind's ``rope_parameters`` float32, what
    cos and sin are multiplied by)."""
    theta = float(r["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if r["rope_type"] != "yarn" or _DEPARTURE == "no_yarn":
        return inv, 1.0
    orig = r["original_max_position_embeddings"]
    c = lambda turns: rot * math.log(orig / (2 * math.pi * turns)) / (2 * math.log(theta))
    lo, hi = max(math.floor(c(r["beta_fast"])), 0), min(math.ceil(c(r["beta_slow"])), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv / r["factor"] * ramp + inv * (1.0 - ramp), float(r["attention_factor"])


def _rotary(x, r: dict):
    """x [b, s, h, hd]: rotate-half on all of the head at positions 0..s-1."""
    s, rot = x.shape[1], x.shape[-1]
    inv, factor = rotary_table(r, rot)
    ang = jnp.arange(s, dtype=jnp.float32)[None, :, None, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def window_of(m: dict, layer_type: str) -> int:
    """The keys a query of this kind of layer sees at most (0: every key at or
    before it), as the reference masks them (a control may move the edge)."""
    if layer_type != "sliding_attention" or _DEPARTURE == "no_window":
        return 0
    return m["sliding_window"] + (_DEPARTURE == "window_off_by_one")


def _attention(w, u, m, layer_type: str):
    b, s, _ = u.shape
    hq, hkv, hd, eps = (m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
                        m["rms_norm_eps"])
    r = m["rope_parameters"][layer_type]
    q = (u @ _F32(w["wq"])).reshape(b, s, hq, hd)
    k = (u @ _F32(w["wk"])).reshape(b, s, hkv, hd)
    v = (u @ _F32(w["wv"])).reshape(b, s, hkv, hd)
    q, k = _rotary(_rms(q, w["q_norm"], eps), r), _rotary(_rms(k, w["k_norm"], eps), r)
    window = window_of(m, layer_type)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    blk = math.gcd(s, Q_BLOCK)

    @jax.checkpoint
    def rows(at):
        """A block of query rows against every key under the dense mask (all of
        a long sequence's [s, s] scores at once would not fit the chip)."""
        q_b = jax.lax.dynamic_slice_in_dim(q, at, blk, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * hd ** -0.5
        back = (at + jnp.arange(blk))[:, None] - jnp.arange(s)[None, :]
        ok = (back >= 0) & (back < window) if window else back >= 0
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1), v)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))  # [s / blk, b, blk, hq, hd]
    return jnp.moveaxis(o, 0, 1).reshape(b, s, hq * hd) @ _F32(w["wo"])


def _experts(w, u, m, forced):
    """(the held experts' share of the routed sum, (the two factors of this
    layer's balance term over these tokens, f_e [64] and P_e [64], and how far
    the picks' lowest score lies over this router's cut-off: 0 for its own
    picks, below 0 where a forced pick is not among its 8 largest)).  ``forced``
    [b, s, k]: experts to take in place of the router's own picks (their
    weights are still this router's scores)."""
    dep = m["deployment"]
    off, held, k = dep["expert_offset"], m["num_experts"], m["num_experts_per_tok"]
    if _DEPARTURE == "expert_offset_shifted":
        off += 1
    score = jax.nn.softmax(u @ _F32(w["router"]), -1)
    top, idx = jax.lax.top_k(score, k)
    if forced is not None:
        idx = forced
    picked = jnp.take_along_axis(score, idx, -1)
    wts = picked if _DEPARTURE == "routing_not_renormalised" \
        else picked / jnp.sum(picked, -1, keepdims=True)
    margin = jax.lax.stop_gradient(jnp.min(picked - top[..., -1:]))
    n = dep["num_experts_total"]
    share = jax.lax.stop_gradient(jnp.mean(
        jnp.sum(idx[..., None] == jnp.arange(n), -2).astype(jnp.float32), (0, 1)) / k)
    dense = jnp.sum(jnp.where(idx[..., None] == jnp.arange(off, off + held), wts[..., None], 0.0), -2)

    @jax.checkpoint
    def one(y, e):
        gt, up, dn, w_e = e
        return y + ((jax.nn.silu(u @ _F32(gt)) * (u @ _F32(up))) @ _F32(dn)) * w_e[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(dense, -1, 0)))
    return y, (share, jnp.mean(score, (0, 1)), margin)


def hidden_states(params, tokens, m: dict, forced=None):
    """tokens [b, s] -> (final-norm hidden states [b, s, d] float32, of the
    expert layers (``_experts``) f [L, 64], P [L, 64] and the picks' margins [L]).
    ``forced`` (an iterator of experts [b, s, k], one per expert layer)
    replaces the reference's own picks: selection is discontinuous, so losses
    and gradients are compared on the same picks and the picks are held to the
    reference's scores separately."""
    eps, layers, at = m["rms_norm_eps"], params["layers"], {}
    factors = []
    with jax.default_matmul_precision("highest"):
        x = _F32(params["embed"]["embedding"])[tokens]
        for l, (layer_type, _) in enumerate(_layers(m)):
            kind = KINDS[layer_type]
            w = layers[kind][at.get(kind, 0)]
            at[kind] = at.get(kind, 0) + 1

            def block(x, w, fw, n1, n2, picks, layer_type=layer_type):
                x = x + _attention(w, _rms(x, n1, eps), m, layer_type)
                y, factor = _experts(fw, _rms(x, n2, eps), m, picks)
                return x + y, factor

            x, factor = jax.checkpoint(block)(
                x, w, layers["moe"][l], layers["attn_norm"]["scale"][l],
                layers["mlp_norm"]["scale"][l], None if forced is None else next(forced))
            factors.append(factor)
        return _rms(x, params["final_norm"]["scale"], eps), \
            tuple(jnp.stack(t) for t in zip(*factors))


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32."""
    h, _ = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"])


def loss_on(params, ids, m: dict, forced=None, margins: bool = False):
    """Token-mean next-token cross entropy of ids [b, s + 1] plus
    ``training.router_aux_loss_coef`` x the balance term, a sequence at a time
    (its [s, vocab] float32 logits under ``jax.checkpoint``); ``forced``: a list
    of experts [b, s, k], one per expert layer.  With ``margins``: (loss, the
    picks' margins over each router's cut-off [rows, L]), ``jax.grad``'s
    ``has_aux`` form."""
    coef = 0.0 if _DEPARTURE == "aux_loss_dropped" else m["training"]["router_aux_loss_coef"]

    def one(args):
        row, picks = args
        h, factors = hidden_states(params, row[None, :-1], m,
                                   None if picks is None else iter(p[None] for p in picks))

        @jax.checkpoint
        def ce(h):
            with jax.default_matmul_precision("highest"):
                lg = h[0] @ _F32(params["lm_head"]["kernel"])
            gold = jnp.take_along_axis(lg, row[1:, None], axis=-1)[..., 0]
            return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)

        return ce(h), factors

    ce, (share, mean, margin) = jax.lax.map(one, (ids, None if forced is None else list(forced)))
    # f_e and P_e are means over ALL the batch's tokens: rows of equal length, so the
    # rows' means; [rows, L, 64] -> a layer's term 64 x sum_e f_e P_e, the layers' mean
    balance = share.shape[-1] * jnp.sum(jnp.mean(share, 0) * jnp.mean(mean, 0), -1)
    loss = jnp.mean(ce) + coef * jnp.mean(balance)
    return (loss, margin) if margins else loss


def make_loss_fn(m: dict):
    """``loss(params, batch, rng=None)`` of ``batch["input_ids"]`` [b, s+1]:
    the signature the train engine's ``eval_fn`` takes."""

    def loss(params, batch, rng=None):
        return loss_on(params, batch["input_ids"], m)

    return loss


def uncut_expert_layer(w, u, m: dict):
    """The expert layer with EVERY routed expert (``w`` holds all
    ``deployment.num_experts_total`` of them) on u [b, s, d]: what the members'
    partial sums add up to."""
    whole = dict(m, num_experts=m["deployment"]["num_experts_total"],
                 deployment=dict(m["deployment"], expert_offset=0))
    with jax.default_matmul_precision("highest"):
        return _experts(w, u, whole, None)[0]


# ---------------------------------------------------------------------------
# what a token requires
# ---------------------------------------------------------------------------
def matmul_params(m: dict) -> int:
    """Parameters a token's forward pass multiplies by HERE: each layer's
    attention projections, the router, the token's expected share of held
    experts (``num_experts_per_tok`` x held / routed), the head's slice."""
    d, hd, hq, hkv = (m["hidden_size"], m["head_dim"], m["num_attention_heads"],
                      m["num_key_value_heads"])
    total = m["deployment"]["num_experts_total"]
    per_tok = m["num_experts_per_tok"] * m["num_experts"] / total
    layer = d * hd * (2 * hq + 2 * hkv) + d * total + 3 * d * m["moe_intermediate_size"] * per_tok
    return int(len(_layers(m)) * layer + d * m["vocab_size"])


def allowed_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs of a causal sequence of ``seq`` positions: every key
    at or before a query, its last ``window`` where there is a window."""
    w = min(window, seq) if window else seq
    return w * (w + 1) // 2 + (seq - w) * w


def attended_pairs(m: dict, seq: int) -> dict:
    """Pairs a sequence of ``seq`` attends in the layers held, by layer type."""
    out = {}
    for layer_type, _ in _layers(m):
        window = m["sliding_window"] if layer_type == "sliding_attention" else 0
        out[layer_type] = out.get(layer_type, 0) + allowed_pairs(seq, window)
    return out


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES here: 6 per matmul parameter and, for the (query, key) pairs the
    two masks allow, three times the forward's 4 x heads x head_dim a pair."""
    pairs = sum(attended_pairs(m, seq).values()) / seq
    return 6.0 * matmul_params(m) + 12.0 * m["num_attention_heads"] * m["head_dim"] * pairs
