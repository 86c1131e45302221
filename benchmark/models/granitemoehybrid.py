"""``model_type`` "granitemoehybrid": what the harness needs from this
architecture (the five callables ``models/__init__.py`` lists), and for the
serving driver the one-token ``recurrence`` its state comparison runs,
``logits_in_blocks`` (the same forward a block and a column block of the head at
a time, on the program's expert picks, so that it fits beside 8.86 GiB of bf16
weights on the chip) and the controls' ``departure``.

The plain reference is the Granite 4.0-H language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no kernels, no cache, no
chunked form and no batching of the recurrence (``lax.scan`` one token at a
time), attention a dense masked softmax (a K / V head's group of query heads at a
time, so that a 4k-token request's scores fit), a loop over the held experts,
each on every token, masked by what the router picked.  It reads the program's
parameter tree (``models/latent.py``: ``layers/attn_norm`` and ``layers/mlp_norm``
stacked, ``layers/mamba`` and ``layers/gqa`` a tuple a kind, ``layers/moe`` a
tuple a block; no ``lm_head``: the head is the embedding), so both sides run on
the same weights.

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, ``RMSNorm(x) = x rsqrt(mean(x^2)
+ eps) w`` (plain weights); every constant below is a key of the configuration.

- ``x0 = embedding_multiplier E[token]``.
- Block ``l`` is of the kind ``layer_types[l]`` and has TWO norms: ``x <- x +
  residual_multiplier mixer_l(RMSNorm(x))``; ``h = RMSNorm(x)``; ``x <- x +
  residual_multiplier (routed(h) + shared(h))``.
- ``mamba``, Mamba-2 (as Bamba's).  ``H`` = ``mamba_n_heads``, ``P`` =
  ``mamba_d_head``, ``d_in = H P`` (= ``mamba_expand d``), ``G`` =
  ``mamba_n_groups``, ``N`` = ``mamba_d_state``, ``K`` = ``mamba_d_conv``.  ``[z |
  xBC | dt] = W_in u`` of widths ``d_in | d_in + 2 G N | H``, no bias.  ``xBC_t <-
  silu(b + sum_{j<K} w_j * xBC_{t-K+1+j})``, depthwise, zeros before the first
  token.  ``xBC`` splits into ``x`` [H, P], ``B``, ``C`` [G, N]; head ``h`` reads
  group ``h // (H / G)`` (ONE group here: every head reads the same B and C).
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one each a head; ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` with ``S_h`` [P, N]; ``y_t = S_t C_t +
  D_h x_t``; ``y <- RMSNorm_grouped(y * silu(z))`` over each of the ``G`` groups
  of ``d_in / G`` channels (the gate BEFORE the norm); ``out = W_out y``.
- ``attention``.  ``num_attention_heads`` query and ``num_key_value_heads`` K / V
  heads of ``hidden_size / num_attention_heads``, no bias, NO positional
  embedding (``position_embedding_type`` "nope": ``rope_theta`` unread), causal
  ``softmax(q k^T attention_multiplier) v`` (the scale is the configuration's
  constant, NOT ``head_dim^-1/2``), ``W_o``.
- ``routed``: ``s = W_r h`` (``deployment.n_routed_experts_total`` logits, no
  bias), the ``num_experts_per_tok`` largest picked, ``g = softmax`` over the
  picked (= the softmax over all, the picked renormalised); ``sum over picked e
  of g_e W_down,e (silu(W_gate,e h) * W_up,e h)`` at width ``intermediate_size``.
  ONLY the experts held here (``deployment.expert_offset`` .. +
  ``num_local_experts``) are computed: a pick on an absent expert keeps its
  place in the softmax and adds nothing, and the partial sum goes on to the next
  block, in program and reference alike.  ``shared``: the same SwiGLU at width
  ``shared_intermediate_size``, no gate on it, added.
- ``logits = E RMSNorm(x_L) / logits_scaling`` over the held rows of the
  vocabulary (``tie_word_embeddings``: the head IS the embedding).

A SHARE (``share=``: ``{"experts": (first, count), "vocab_rows": (first,
count)}``) cuts a tree that holds MORE down to what one member of the deployment
holds before the forward runs, so that one test can give the reference every
share of an uncut tree (``cut_to_share``); without one the tree is taken as the
share the configuration's ``deployment`` states.

Departures from the published description: none in the mathematics.  Readings
no key settles (``head_dim`` = ``hidden_size / num_attention_heads``, the gated
norm's order, ``D``'s skip per head, ``intermediate_size`` as one expert's width)
are under the configuration file's ``assumed``; ``mamba_chunk_size`` is a schedule.
"""
from __future__ import annotations

import contextlib
import functools
import json

import jax
import jax.numpy as jnp

KINDS = {"mamba": "mamba", "attention": "gqa"}  # ``layer_types`` -> the program's kinds
_WEIGHTS_AS = None   # the control's precision, while ``weights_rounded_to`` is open (``_as_read``)
_DEPARTURE = None    # the control's departure, while ``departure`` is open
# what a control changes in the reference (the serving driver's ``CONTROLS``)
DEPARTURES = {
    "softmax_scale_rsqrt": "attention's softmax at head_dim^-1/2, not attention_multiplier",
    "residual_multiplier_one": "both residual branches added as they are (residual_multiplier = 1)",
}


def _F32(a):
    """A weight (or an array already float32) as the reference uses it."""
    return a.astype(jnp.float32)


@contextlib.contextmanager
def weights_rounded_to(dtype):
    """Inside, ``logits_in_blocks`` reads every weight matrix rounded to ``dtype``
    (``_as_read``: eagerly, a block at a time): the serving driver's control, one
    precision down, without a second copy of the weights on the device."""
    global _WEIGHTS_AS
    _WEIGHTS_AS = dtype
    try:
        yield
    finally:
        _WEIGHTS_AS = None


@contextlib.contextmanager
def departure(name: str):
    """Inside (at TRACE time), the reference departs from the model in ONE place
    (``DEPARTURES``): what a fault of that kind in the program would compute."""
    global _DEPARTURE
    if name not in DEPARTURES:
        raise KeyError(name)
    _DEPARTURE = name
    try:
        yield
    finally:
        _DEPARTURE = None


def _kinds(m: dict):
    return [KINDS[t] for t in m["layer_types"][: m["num_hidden_layers"]]]


def _sizes(m: dict):
    """(H, P, G, N, K) of the state-space mixer."""
    h, p = m["mamba_n_heads"], m["mamba_d_head"]
    if h * p != m["mamba_expand"] * m["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is the inner width, mamba_expand x hidden_size")
    return h, p, m["mamba_n_groups"], m["mamba_d_state"], m["mamba_d_conv"]


def _head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the blocks."""
    from deepspeed_tpu.models.latent import Gqa, LatentSpec, Mamba
    from deepspeed_tpu.models.transformer import TransformerConfig

    if model["hidden_act"] != "silu" or not model["mamba_conv_bias"] or model["mamba_proj_bias"] \
            or model["attention_bias"] or not model["tie_word_embeddings"] \
            or model["position_embedding_type"] != "nope" or model["rope_scaling"] is not None \
            or model["normalization_function"] != "rmsnorm" \
            or model["hidden_size"] % model["num_attention_heads"]:
        raise ValueError("only the published granitemoehybrid block is mapped here")
    h, p, g, n, k = _sizes(model)
    L, dep = model["num_hidden_layers"], model["deployment"]
    spec = LatentSpec(
        layer_kinds=tuple(_kinds(model)), full=None, sliding=None, index_heads=0, index_dim=0,
        index_topk=0, first_dense=0, n_routed=dep["n_routed_experts_total"],
        n_held=model["num_local_experts"], held_offset=dep["expert_offset"],
        experts_per_tok=model["num_experts_per_tok"], moe_width=model["intermediate_size"],
        n_shared=1, shared_width=model["shared_intermediate_size"], routing="softmax",
        two_norms=True,
        mamba=Mamba(num_heads=h, head_dim=p, n_groups=g, state=n, conv=k,
                    chunk=model["mamba_chunk_size"]),
        gqa=Gqa(num_heads=model["num_attention_heads"],
                num_kv_heads=model["num_key_value_heads"], head_dim=_head_dim(model),
                scale=float(model["attention_multiplier"])),
        embedding_multiplier=float(model["embedding_multiplier"]),
        logits_multiplier=1.0 / float(model["logits_scaling"]),
        residual_multiplier=float(model["residual_multiplier"]),
        fp32_logits=True)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=L,
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
        head_dim=_head_dim(model), max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=dtypes[model["torch_dtype"]], latent=spec)
    kw.update(overrides)
    return TransformerConfig(**kw)


def cut_to_share(params, m: dict, share: dict):
    """(the tree, the configuration) of ONE member's share of a tree that holds
    more: ``share["experts"]`` = (first, count) of the routed experts' axis (the
    tree's first expert being ``deployment.expert_offset``), ``share["vocab_rows"]``
    = (first, count) of the embedding's rows.  The router, the shared expert, the
    mixers and the norms are every member's alike and are left whole."""
    layers, dep = dict(params["layers"]), dict(m["deployment"])
    m = dict(m, deployment=dep)
    if "experts" in share:
        first, count = share["experts"]
        at = first - dep["expert_offset"]
        layers["moe"] = tuple(
            {k: (w[at:at + count] if k in ("w_gate", "w_up", "w_down") else w)
             for k, w in fw.items()} for fw in layers["moe"])
        dep["expert_offset"], m["num_local_experts"] = first, count
    embed = params["embed"]["embedding"]
    if "vocab_rows" in share:
        first, count = share["vocab_rows"]
        embed, m["vocab_size"] = embed[first:first + count], count
    return {**params, "layers": layers, "embed": {"embedding": embed}}, m


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _F32(scale)


def recurrence(x, bm, cm, dt, a):
    """The state-space recurrence, one token at a time, float32: x [b, s, H, P],
    bm and cm [b, s, G, N] (head ``h`` reads group ``h // (H / G)``), dt
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
    h, p, g, n, k = _sizes(m)
    d_in, gn = h * p, g * n
    zxd = u @ _F32(w["w_in"])
    z, xbc, dt = zxd[..., :d_in], zxd[..., d_in:2 * d_in + 2 * gn], zxd[..., 2 * d_in + 2 * gn:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))  # zeros before the first token
    conv = _F32(w["conv_b"]) + sum(_F32(w["conv_w"])[j] * padded[:, j:j + s] for j in range(k))
    conv = jax.nn.silu(conv)
    x = conv[..., :d_in].reshape(b, s, h, p)
    bm = conv[..., d_in:d_in + gn].reshape(b, s, g, n)
    cm = conv[..., d_in + gn:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + _F32(w["dt_bias"]))            # [b, s, h]
    y, _ = recurrence(x, bm, cm, dt, -jnp.exp(_F32(w["a_log"])))
    y = y + _F32(w["d_skip"])[:, None] * x
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    yg = y.reshape(b, s, g, d_in // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + m["rms_norm_eps"])
    return (yg.reshape(b, s, d_in) * _F32(w["norm"])) @ _F32(w["w_out"])


def _attention(w, u, m):
    """u [b, s, d] -> [b, s, d]: no positions; a dense causal mask, one K / V head's
    group of query heads at a time."""
    b, s, _ = u.shape
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], _head_dim(m)
    scale = hd ** -0.5 if _DEPARTURE == "softmax_scale_rsqrt" else float(m["attention_multiplier"])
    q = (u @ _F32(w["wq"])).reshape(b, s, hkv, hq // hkv, hd)
    k = (u @ _F32(w["wk"])).reshape(b, s, hkv, hd)
    v = (u @ _F32(w["wv"])).reshape(b, s, hkv, hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def group(qkv):
        q_g, k_g, v_g = qkv                                  # [b,s,r,hd] [b,s,hd] [b,s,hd]
        sc = jnp.einsum("bqrd,bkd->brqk", q_g, k_g) * scale
        sc = jnp.where(causal, sc, -jnp.inf)
        return jnp.einsum("brqk,bkd->bqrd", jax.nn.softmax(sc, -1), v_g)

    o = jax.lax.map(group, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(o, 0, 2).reshape(b, s, hq * hd) @ _F32(w["wo"])


def ffn_parts(w, u, m, probe=None, forced=None):
    """(the held experts' share of the routed sum, the shared expert) on normed
    rows u [b, s, d].  ``forced`` [b, s, k]: experts to take in place of the
    router's own picks (their weights are still this router's softmax over them).
    ``probe`` (a list) is handed every expert's router logit and the cut-off."""
    dep = m["deployment"]
    off, held, k = dep["expert_offset"], m["num_local_experts"], m["num_experts_per_tok"]
    score = u @ _F32(w["router"])                            # [b, s, n_routed] logits
    top, idx = jax.lax.top_k(score, k)
    if forced is not None:
        idx = forced
    wts = jax.nn.softmax(jnp.take_along_axis(score, idx, -1), -1)
    if probe is not None:
        probe.append({"router_biased": score, "router_cutoff": top[..., -1]})
    dense = jnp.sum(jnp.where(idx[..., None] == jnp.arange(off, off + held), wts[..., None], 0.0), -2)
    swiglu = lambda x, gate, up, dn: (jax.nn.silu(x @ _F32(gate)) * (x @ _F32(up))) @ _F32(dn)

    def one(y, e):
        gate, up, dn, w_e = e
        return y + swiglu(u, gate, up, dn) * w_e[..., None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(dense, -1, 0)))
    return routed, swiglu(u, w["s_gate"], w["s_up"], w["s_down"])


def block(x, n1, n2, mw, fw, m: dict, kind: str, probe=None, forced=None):
    """One block on x [b, s, d] float32: its mixer, then the held experts' share
    and the shared expert, the constant on both branches."""
    eps = m["rms_norm_eps"]
    c = 1.0 if _DEPARTURE == "residual_multiplier_one" else float(m["residual_multiplier"])
    with jax.default_matmul_precision("highest"):
        x = x + c * (_mamba if kind == "mamba" else _attention)(mw, _rms(x, n1, eps), m)
        routed, shared = ffn_parts(fw, _rms(x, n2, eps), m, probe, forced)
        return x + c * (routed + shared)


def _embedded(rows, m):
    return _F32(rows) * float(m["embedding_multiplier"])


def _block_weights(params, m: dict, l: int):
    layers, kinds = params["layers"], _kinds(m)
    return (layers["attn_norm"]["scale"][l], layers["mlp_norm"]["scale"][l],
            layers[kinds[l]][kinds[:l].count(kinds[l])], layers["moe"][l])


def hidden_states(params, tokens, m: dict, probe=None, forced=None):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32.  ``probe`` (a
    list) collects per block the router's logits and cut-offs; ``forced`` (an
    iterator of experts [b, s, k], one a block) replaces the reference's own
    picks: selection is discontinuous, so LOGITS are compared on the same picks
    and the picks are held to the reference's scores separately."""
    x = _embedded(params["embed"]["embedding"][tokens], m)
    for l, kind in enumerate(_kinds(m)):
        x = block(x, *_block_weights(params, m, l), m, kind, probe,
                  None if forced is None else next(forced))
    return _rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])


def _head(h, rows, m):
    """Normed rows h [.., d] against the embedding's rows [v, d]: the tied head."""
    with jax.default_matmul_precision("highest"):
        return (h @ _F32(rows).T) / float(m["logits_scaling"])


def logits(params, tokens, m: dict, share=None):
    """[b, s, vocab] float32 (of a tree that holds more, the ``share``'s)."""
    return probe(params, tokens, m, share=share)[0]


def probe(params, tokens, m: dict, forced=None, share=None):
    """(logits, what the routers' picks were made from, a block each), for the
    serving driver; with ``forced`` (a list, see ``hidden_states``) the logits are
    the reference's on the program's own picks."""
    if share is not None:
        params, m = cut_to_share(params, m, share)
    seen: list = []
    h = hidden_states(params, tokens, m, seen, None if forced is None else iter(forced))
    return _head(h, params["embed"]["embedding"], m), seen


def logits_in_blocks(params, tokens, m: dict, rows, forced=None, cols: int = 32768):
    """``probe(params, tokens, m, forced)`` for ONE sequence (tokens [1, s]) as
    numpy arrays, (logits[0, rows] [len(rows), vocab], per block the router's
    logits [s, n_routed] and cut-offs [s]), computed a block at a time on the
    device: ONE jitted program a kind of block (its matrices cast up as it runs and
    dropped with it) and one a column block of the head, so that the float32
    copies never stand side by side.  Inside ``departure`` the programs are traced
    with it open; inside ``weights_rounded_to`` each block's matrices are rounded
    before its program reads them (``_as_read``): a control's reference."""
    import numpy as np

    embed, blocks, final, head = _programs(json.dumps(m, sort_keys=True), _DEPARTURE)
    x = embed(_as_read(params["embed"]["embedding"][tokens]))
    seen = []
    for l, kind in enumerate(_kinds(m)):
        picks = jnp.zeros((0,), jnp.int32) if forced is None else jnp.asarray(forced[l])
        x, score, cutoff = blocks[kind, forced is not None](
            x, *_as_read(_block_weights(params, m, l)), picks)
        seen.append({"router_biased": np.asarray(score[0]), "router_cutoff": np.asarray(cutoff[0])})
    h = final(x, params["final_norm"]["scale"], jnp.asarray(rows, jnp.int32))
    del x
    table = params["embed"]["embedding"]
    out = [np.asarray(head(h, _as_read(table[at:at + cols])))
           for at in range(0, table.shape[0], cols)]
    return np.concatenate(out, axis=1), seen


def _as_read(tree):
    """``tree``'s weight matrices rounded to the precision ``weights_rounded_to``
    has open, EAGERLY: a program of its own a tensor, so that no compiler folds the
    round trip away inside the program that uses it; a block at a time, so no second
    copy of the weights stands on the device."""
    if _WEIGHTS_AS is None:
        return tree
    return jax.tree_util.tree_map(
        lambda a: a.astype(_WEIGHTS_AS).astype(a.dtype)
        if a.ndim >= 2 and a.dtype != jnp.float32 else a, tree)


@functools.lru_cache(maxsize=None)
def _programs(config: str, dep):
    """``logits_in_blocks``' jitted programs for one configuration (its JSON),
    traced with the departure ``dep`` open as it was when it was called (a jitted
    body is traced at its first call, which may come after the context closed):
    (embed, {(kind, forced?): block}, final norm at rows, a head's column block)."""
    m = json.loads(config)

    def traced(fn):
        def body(*args):
            global _DEPARTURE
            was, _DEPARTURE = _DEPARTURE, dep
            try:
                return fn(*args)
            finally:
                _DEPARTURE = was
        return jax.jit(body)

    def one_block(kind, is_forced):
        def run(x, n1, n2, mw, fw, picks):
            seen: list = []
            x = block(x, n1, n2, mw, fw, m, kind, seen, picks if is_forced else None)
            return x, seen[0]["router_biased"], seen[0]["router_cutoff"]
        return traced(run)

    return (traced(lambda rows: _embedded(rows, m)),
            {(kind, f): one_block(kind, f) for kind in KINDS.values() for f in (False, True)},
            traced(lambda x, w, r: _rms(x, w, m["rms_norm_eps"])[0][r]),
            traced(lambda h, rows: _head(h, rows, m)))


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
    """Parameters a token's forward pass multiplies by HERE: its block's mixer's
    projections; of every block's expert layer the router, the shared expert and
    the token's expected share of held experts (``num_experts_per_tok`` x held /
    routed); the head's held rows."""
    d = m["hidden_size"]
    h, p, g, n, _ = _sizes(m)
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], _head_dim(m)
    total = m["deployment"]["n_routed_experts_total"]
    per_tok = m["num_experts_per_tok"] * m["num_local_experts"] / total
    mixer = {"mamba": d * (2 * h * p + 2 * g * n + h) + h * p * d,
             "gqa": d * hd * (2 * hq + 2 * hkv)}
    ffn = d * total + 3 * d * m["shared_intermediate_size"] \
        + 3 * d * m["intermediate_size"] * per_tok
    return int(d * m["vocab_size"] + sum(mixer[k] + ffn for k in _kinds(m)))


def mixer_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token outside the matmuls by parameters: per
    state-space block the state's update and read-out (3 H P N multiply-adds)
    and the convolution; per attention block ``ctx`` keys at 4 Hq hd."""
    h, p, g, n, k = _sizes(m)
    kinds = _kinds(m)
    ssm = 6.0 * h * p * n + 2.0 * k * (h * p + 2 * g * n)
    attn = 4.0 * m["num_attention_heads"] * _head_dim(m) * ctx
    return kinds.count("mamba") * ssm + kinds.count("gqa") * attn


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's mixers at
    the mean context (seq+1)/2.  (No training cell runs this architecture.)"""
    return 6.0 * matmul_params(m) + 3.0 * mixer_flops_per_token(m, (seq + 1) / 2)
