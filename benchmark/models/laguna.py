"""``model_type`` "laguna": what the harness needs from this architecture (the
five callables ``models/__init__.py`` lists), and ``probe`` for the serving
driver's checks of what the routers PICKED and of the rows a window layer's
ring is left KEEPING.

The plain reference is the Laguna language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no cache, dense causal mask
(a block of query rows at a time against every key), every held expert on every
token, masked by what the router picked.  It reads the program's parameter tree
(``models/latent.py``: ``layers/attn_norm``, ``layers/mlp_norm`` and one tuple of
per-layer trees per kind), so both sides run on the same weights.

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, no bias, untied head, RMSNorm
with a plain weight.  Block ``l``: ``x <- x + attn_l(rms(x)); x <- x +
ffn_l(rms(x))``; final norm; head.

- Attention, kind by ``layer_types[l]``, heads by
  ``num_attention_heads_per_layer[l]``: ``H_l`` query heads, ``num_key_value_heads``
  K / V heads of ``head_dim``.  ``W_q``: d -> H_l x hd, ``W_k``, ``W_v``: d -> Hkv x
  hd, ``W_g``: d -> H_l, ``W_o``: H_l x hd -> d.  q and k normed over the head
  (RMSNorm, plain weight), then rotary (rotate-half) on their first
  ``partial_rotary_factor x hd`` dims with the kind's ``rope_parameters``.  A
  ``yarn`` table: ``inv_i = theta^(-2i/r)``; ``c(t) = r ln(orig / (2 pi t)) / (2
  ln theta)``, ``lo = floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))``,
  ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``, ``inv'_i = inv_i / factor x
  ramp_i + inv_i (1 - ramp_i)``; cos and sin times ``attention_factor``.  Scores
  ``q k^T hd^-1/2``, causal; a ``sliding_attention`` layer's query at position i
  sees key j iff ``0 <= i - j < sliding_window``.  ``out_h = softmax(.) v x g_h``,
  ``g = sigmoid(rms(x) W_g)`` one value a head; then ``W_o``.
- Feed-forward by ``mlp_layer_types[l]``.  ``dense``: SwiGLU of
  ``intermediate_size``.  ``sparse``: ``s = sigmoid(h W_r)`` over all
  ``deployment.num_experts_total`` experts; the ``num_experts_per_tok`` largest
  of ``s + b``; ``w = s_top / sum s_top x moe_routed_scaling_factor`` (on the
  expert's OUTPUT); ``y = sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)``, the shared
  expert ungated.  ONLY the experts held here (``deployment.expert_offset`` .. +
  ``num_experts``) are computed; this configuration holds them all.
- The file keeps ``layer_types``, ``mlp_layer_types`` and
  ``num_attention_heads_per_layer`` whole; layer ``l < num_hidden_layers`` reads
  entry ``l``.  Every reading the source does not settle is under the
  configuration file's ``assumed``.
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
DEPARTURES = ("no_window", "window_off_by_one", "rotary_sets_swapped", "no_yarn",
              "no_output_gate", "routing_not_scaled")
KINDS = {"full_attention": "gattn", "sliding_attention": "wattn"}  # the program's names


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


def _layers(m: dict):
    """(layer type, query heads, feed-forward type) of each layer held."""
    n = m["num_hidden_layers"]
    return list(zip(m["layer_types"][:n], m["num_attention_heads_per_layer"][:n],
                    m["mlp_layer_types"][:n]))


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the blocks."""
    from deepspeed_tpu.models.latent import GatedGqa, LatentSpec, Yarn
    from deepspeed_tpu.models.transformer import TransformerConfig

    layers = _layers(model)
    ffns = [f for _, _, f in layers]
    first_dense = ffns.index("sparse") if "sparse" in ffns else len(ffns)
    if model["attention_bias"] or model["tie_word_embeddings"] or model["gating"] is not True \
            or model["moe_apply_router_weight_on_input"] \
            or set(ffns[:first_dense]) - {"dense"} or set(ffns[first_dense:]) - {"sparse"}:
        raise ValueError("only the published laguna block is mapped here")
    hd, hkv, dep = model["head_dim"], model["num_key_value_heads"], model["deployment"]

    def kind(layer_type: str, window: int) -> GatedGqa:
        heads = {h for t, h, _ in layers if t == layer_type}
        if len(heads) != 1:
            raise ValueError(f"{layer_type} layers of {sorted(heads)} heads: one count a kind")
        r = model["rope_parameters"][layer_type]
        yarn = None
        if r["rope_type"] == "yarn":
            yarn = Yarn(factor=float(r["factor"]),
                        original_max=int(r["original_max_position_embeddings"]),
                        beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
                        attention_factor=float(r["attention_factor"]))
        elif r["rope_type"] != "default":
            raise ValueError(f"rope_type {r['rope_type']!r} is not mapped here")
        return GatedGqa(num_heads=heads.pop(), num_kv_heads=hkv, head_dim=hd,
                        rope_dim=int(hd * r["partial_rotary_factor"]),
                        rope_theta=float(r["rope_theta"]), window=window, gate="head",
                        rope_scaling=yarn)

    spec = LatentSpec(
        layer_kinds=tuple(KINDS[t] for t, _, _ in layers), full=None, sliding=None,
        index_heads=0, index_dim=0, index_topk=0, first_dense=first_dense,
        n_routed=dep["num_experts_total"], n_held=model["num_experts"],
        held_offset=dep["expert_offset"], experts_per_tok=model["num_experts_per_tok"],
        moe_width=model["moe_intermediate_size"], n_shared=1,
        shared_width=model["shared_expert_intermediate_size"],
        routed_scale=float(model["moe_routed_scaling_factor"]),
        gattn=kind("full_attention", 0),
        wattn=kind("sliding_attention", int(model["sliding_window"])),
        routing="sigmoid", shared_gate=False, unit_offset=False)
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
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    return inv / r["factor"] * ramp + inv * (1.0 - ramp), float(r["attention_factor"])


def _rotary(x, r: dict):
    """x [b, s, h, hd]: rotate-half on the first ``partial_rotary_factor hd``
    dims at positions 0..s-1."""
    s, rot = x.shape[1], int(x.shape[-1] * r["partial_rotary_factor"])
    inv, factor = rotary_table(r, rot)
    ang = jnp.arange(s, dtype=jnp.float32)[None, :, None, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _attention(w, u, m, layer_type: str, hq: int, probe):
    b, s, _ = u.shape
    hkv, hd, eps = m["num_key_value_heads"], m["head_dim"], m["rms_norm_eps"]
    other = {"full_attention": "sliding_attention", "sliding_attention": "full_attention"}
    ropes = m["rope_parameters"]
    r = ropes[other[layer_type] if _DEPARTURE == "rotary_sets_swapped" else layer_type]
    q = (u @ _F32(w["wq"])).reshape(b, s, hq, hd)
    k = (u @ _F32(w["wk"])).reshape(b, s, hkv, hd)
    v = (u @ _F32(w["wv"])).reshape(b, s, hkv, hd)
    gate = jax.nn.sigmoid(u @ _F32(w["w_g"]))                       # [b, s, hq]
    q, k = _rotary(_rms(q, w["q_norm"], eps), r), _rotary(_rms(k, w["k_norm"], eps), r)
    window = 0
    if layer_type == "sliding_attention":
        window = m["sliding_window"] + (_DEPARTURE == "window_off_by_one")
        if _DEPARTURE == "no_window":
            window = 0
    kept = (k, v)  # what a ring keeps: the keys as attended, the values
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))

    def rows(at):
        """A block of query rows against every key under the dense mask (all of
        a long sequence's [s, s] scores at once would not fit the chip)."""
        q_b = jax.lax.dynamic_slice_in_dim(q, at, blk, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * hd ** -0.5
        back = (at + jnp.arange(blk))[:, None] - jnp.arange(s)[None, :]
        ok = (back >= 0) & (back < window) if window else back >= 0
        sc = jnp.where(ok, sc, -jnp.inf)
        seen = jnp.sum(ok, -1), jnp.min(jnp.where(ok, jnp.arange(s)[None, :], s), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v), seen

    blk = math.gcd(s, 128)
    o, (n_seen, oldest) = jax.lax.map(rows, jnp.arange(0, s, blk))  # [s / blk, b, blk, hq, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, hq, hd)
    if probe is not None and layer_type == "sliding_attention":
        # ... and what the mask let each query see: how many keys, the oldest one
        probe.append({"ring_k": kept[0], "ring_v": kept[1],
                      "window_seen": jnp.broadcast_to(n_seen.reshape(1, s), (b, s)),
                      "window_oldest": jnp.broadcast_to(oldest.reshape(1, s), (b, s))})
    if _DEPARTURE != "no_output_gate":
        o = o * gate[..., None]
    return o.reshape(b, s, hq * hd) @ _F32(w["wo"])


def _swiglu(x, gt, up, dn):
    return (jax.nn.silu(x @ _F32(gt)) * (x @ _F32(up))) @ _F32(dn)


def _experts(w, u, m, probe, forced):
    """The held experts' share of the routed sum plus the shared expert.
    ``forced`` [b, s, k]: experts to take in place of the router's own picks
    (their weights are still this router's scores)."""
    dep = m["deployment"]
    off, held, k = dep["expert_offset"], m["num_experts"], m["num_experts_per_tok"]
    score = jax.nn.sigmoid(u @ _F32(w["router"]))
    biased = score + _F32(w["bias"])
    top, idx = jax.lax.top_k(biased, k)
    if forced is not None:
        idx = forced
    wts = jnp.take_along_axis(score, idx, -1)
    wts = wts / jnp.sum(wts, -1, keepdims=True)
    if _DEPARTURE != "routing_not_scaled":
        wts = wts * m["moe_routed_scaling_factor"]
    if probe is not None:
        probe.append({"router_biased": biased, "router_cutoff": top[..., -1]})
    dense = jnp.sum(jnp.where(idx[..., None] == jnp.arange(off, off + held), wts[..., None], 0.0), -2)

    def one(y, e):
        gt, up, dn, w_e = e
        return y + _swiglu(u, gt, up, dn) * w_e[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(dense, -1, 0)))
    return y + _swiglu(u, w["s_gate"], w["s_up"], w["s_down"])


def hidden_states(params, tokens, m: dict, probe=None, forced=None):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32.  ``probe``
    (a list) collects per window layer the keys (normed, rotated) and values a
    ring keeps and per expert layer the router's biased scores and cut-offs;
    ``forced`` (an iterator of experts [b, s, k], one per expert layer)
    replaces the reference's own picks: selection is discontinuous, so LOGITS
    are compared on the same picks and the picks are held to the reference's
    scores separately."""
    eps, layers, seen = m["rms_norm_eps"], params["layers"], {}
    with jax.default_matmul_precision("highest"):
        x = _F32(params["embed"]["embedding"])[tokens]
        for l, (layer_type, heads, ffn) in enumerate(_layers(m)):
            kind = KINDS[layer_type]
            w = layers[kind][seen.get(kind, 0)]
            seen[kind] = seen.get(kind, 0) + 1
            u = _rms(x, layers["attn_norm"]["scale"][l], eps)
            x = x + _attention(w, u, m, layer_type, heads, probe)
            u = _rms(x, layers["mlp_norm"]["scale"][l], eps)
            if ffn == "dense":
                fw = layers["mlp"][seen.get("mlp", 0)]
                seen["mlp"] = seen.get("mlp", 0) + 1
                x = x + _swiglu(u, fw["w_gate"], fw["w_up"], fw["w_down"])
            else:
                fw = layers["moe"][seen.get("moe", 0)]
                seen["moe"] = seen.get("moe", 0) + 1
                x = x + _experts(fw, u, m, probe, None if forced is None else next(forced))
        return _rms(x, params["final_norm"]["scale"], eps)


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32."""
    h = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"])


def probe(params, tokens, m: dict, forced=None, at=0, rows=None):
    """(logits, what the rings keep and what the routers' picks were made
    from, layer by layer), for the serving driver; with ``forced`` (a list, see
    ``hidden_states``) the logits are the reference's on the program's own
    picks; with ``rows`` (static) only the ``rows`` positions from ``at`` on get
    logits ([b, rows, vocab]: a long request's 100 352 logits a position would
    not fit beside the served model)."""
    seen: list = []
    h = hidden_states(params, tokens, m, seen, None if forced is None else iter(forced))
    if rows is not None:
        h = jax.lax.dynamic_slice_in_dim(h, at, rows, axis=1)
    with jax.default_matmul_precision("highest"):
        return h @ _F32(params["lm_head"]["kernel"]), seen


def uncut_expert_layer(w, u, m: dict):
    """The expert layer with EVERY routed expert (``w`` holds all
    ``deployment.num_experts_total`` of them) on u [b, s, d]: what the members'
    partial sums, the shared expert counted once, add up to."""
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
    """Parameters a token's forward pass multiplies by HERE: each layer's
    attention projections and gate, a dense layer's SwiGLU, of an expert layer
    the router, the shared expert and the token's expected share of held
    experts (``num_experts_per_tok`` x held / routed), the head."""
    d, hd, hkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    per_tok = m["num_experts_per_tok"] * m["num_experts"] / m["deployment"]["num_experts_total"]
    sparse = d * m["deployment"]["num_experts_total"] \
        + 3 * d * m["shared_expert_intermediate_size"] + 3 * d * m["moe_intermediate_size"] * per_tok
    total = d * m["vocab_size"]
    for _, heads, ffn in _layers(m):
        total += d * hd * (2 * heads + 2 * hkv) + d * heads
        total += 3 * d * m["intermediate_size"] if ffn == "dense" else sparse
    return int(total)


def mixer_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token's attention over its keys: ``ctx`` keys in a
    full layer, at most ``sliding_window`` in a window layer, at 4 H_l hd each."""
    keys = {"full_attention": ctx, "sliding_attention": min(ctx, m["sliding_window"])}
    return sum(4.0 * heads * m["head_dim"] * keys[t] for t, heads, _ in _layers(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's attention at
    the mean context (seq+1)/2.  (No training cell runs this architecture.)"""
    return 6.0 * matmul_params(m) + 3.0 * mixer_flops_per_token(m, (seq + 1) / 2)
