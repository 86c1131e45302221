"""``model_type`` "falcon_h1": what the harness needs from this architecture
(the five callables ``models/__init__.py`` lists), and for the serving driver
the one-token ``recurrence`` its state comparison runs, ``logits_in_blocks`` (the
same forward a layer and a column block of the head at a time, so that it fits
beside 9.79 GiB of bf16 weights on the chip) and the controls' ``departure``.

The plain reference is the Falcon-H1 language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no kernels, no cache, no
chunked form and no batching of the recurrence (``lax.scan`` one token at a
time), attention a dense masked softmax.  It reads the program's parameter tree
(``models/latent.py``: ``layers/attn_norm`` and ``layers/mlp_norm`` stacked,
``layers/par`` a tuple of ``{"mamba", "gqa"}`` a block, ``layers/mlp``), so both
sides run on the same weights.

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, ``RMSNorm(x) = x rsqrt(mean(x^2)
+ eps) w``; every multiplier below is a key of the configuration.

- ``x0 = embedding_multiplier E[token]``.
- Block ``l`` holds TWO mixers on ONE normed input, summed: ``h = RMSNorm(x)``;
  ``x <- x + ssm_out_multiplier Mamba2(ssm_in_multiplier h) +
  attention_out_multiplier GQA(attention_in_multiplier h)``; then ``x <- x +
  MLP(RMSNorm(x))``, ``MLP(u) = mlp_multipliers[1] W_down(W_up u *
  silu(mlp_multipliers[0] W_gate u))``, no bias.
- ``Mamba2(u)``.  ``H`` = ``mamba_n_heads``, ``P`` = ``mamba_d_head``, ``d_in`` =
  ``mamba_d_ssm`` = ``H P``, ``G`` = ``mamba_n_groups``, ``N`` = ``mamba_d_state``,
  ``K`` = ``mamba_d_conv``.  ``[z | x | B | C | dt] = (W_in u) * mup`` of widths
  ``d_in | d_in | G N | G N | H``, ``mup`` the five ``ssm_multipliers`` laid over
  those segments.  ``xBC_t <- silu(b + sum_{j<K} w_j * xBC_{t-K+1+j})``,
  depthwise, zeros before the first token.  Head ``h`` reads group ``h // (H /
  G)``.  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t = exp(dt_t
  A) S_{t-1} + dt_t x_t (x) B_t`` with ``S_h`` [P, N]; ``y_t = S_t C_t + D_h
  x_t``; ``y <- RMSNorm_grouped(y * silu(z))`` over each of the ``G`` groups of
  ``d_in / G`` channels (``mamba_rms_norm`` true, ``mamba_norm_before_gate``
  false: the gate BEFORE the norm); ``out = W_out y``.  No bias but the
  convolution's.
- ``GQA(u)``.  ``num_attention_heads`` query and ``num_key_value_heads`` K / V
  heads of ``head_dim``, no bias; ``k <- key_multiplier k`` BEFORE the rotation;
  rotary positions (rotate-half) over the whole head at ``rope_theta``
  (``rope_scaling`` null); causal softmax at ``head_dim^-1/2``; ``W_o``.
- ``logits = lm_head_multiplier W_head RMSNorm(x_L)``, head untied.

Departures from the published description: none in the mathematics.  Readings
of the family's public modelling code that no key settles (where each
multiplier sits, the gated norm's order and grouping, ``D``'s skip per head,
``mamba_expand`` and ``mlp_expansion_factor`` unread where ``mamba_d_ssm`` and
``intermediate_size`` are given, ``attn_layer_indices`` null = every block
attends) are under the configuration file's ``assumed``; ``mamba_chunk_size`` is
a schedule, ``num_logits_to_keep`` a switch of the source's runtime.
"""
from __future__ import annotations

import contextlib
import functools
import json

import jax
import jax.numpy as jnp

_WEIGHTS_AS = None   # the control's precision, while ``weights_rounded_to`` is open (``_as_read``)
_DEPARTURE = None    # the control's departure, while ``departure`` is open
# what a control changes in the reference (the serving driver's ``CONTROLS``)
DEPARTURES = {
    "key_multiplier_left_out": "the keys are not scaled before their rotation (key_multiplier = 1)",
    "ssm_c_multiplier_left_out": "C's segment of W_in's output is not scaled (ssm_multipliers[3] = 1)",
    "attention_dropped": "the block's sum leaves the attention side out",
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


def _sizes(m: dict):
    """(H, P, G, N, K) of the state-space mixer."""
    h, p = m["mamba_n_heads"], m["mamba_d_head"]
    if m["mamba_d_ssm"] != h * p:
        raise ValueError("mamba_d_ssm is the heads' channels, mamba_n_heads x mamba_d_head")
    return h, p, m["mamba_n_groups"], m["mamba_d_state"], m["mamba_d_conv"]


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the blocks."""
    from deepspeed_tpu.models.latent import Gqa, LatentSpec, Mamba
    from deepspeed_tpu.models.transformer import TransformerConfig

    if model["hidden_act"] != "silu" or not model["mamba_conv_bias"] \
            or model["mamba_proj_bias"] or model["projectors_bias"] or model["mlp_bias"] \
            or model["attention_bias"] or model["tie_word_embeddings"] \
            or not model["mamba_rms_norm"] or model["mamba_norm_before_gate"] \
            or not model["mamba_use_mlp"] or model["rope_scaling"] is not None \
            or model["attn_layer_indices"] is not None or len(model["ssm_multipliers"]) != 5 \
            or len(model["mlp_multipliers"]) != 2:
        raise ValueError("only the published falcon_h1 block is mapped here")
    h, p, g, n, k = _sizes(model)
    L = model["num_hidden_layers"]
    spec = LatentSpec(
        layer_kinds=("par",) * L, full=None, sliding=None, index_heads=0, index_dim=0,
        index_topk=0, first_dense=L, n_routed=0, n_held=0, held_offset=0, experts_per_tok=0,
        moe_width=0, n_shared=0,
        mamba=Mamba(num_heads=h, head_dim=p, n_groups=g, state=n, conv=k,
                    chunk=model["mamba_chunk_size"],
                    in_multiplier=float(model["ssm_in_multiplier"]),
                    multipliers=tuple(float(x) for x in model["ssm_multipliers"]),
                    out_multiplier=float(model["ssm_out_multiplier"])),
        gqa=Gqa(num_heads=model["num_attention_heads"],
                num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
                rope_theta=float(model["rope_theta"]),
                in_multiplier=float(model["attention_in_multiplier"]),
                key_multiplier=float(model["key_multiplier"]),
                out_multiplier=float(model["attention_out_multiplier"])),
        embedding_multiplier=float(model["embedding_multiplier"]),
        logits_multiplier=float(model["lm_head_multiplier"]),
        mlp_gate_multiplier=float(model["mlp_multipliers"][0]),
        mlp_down_multiplier=float(model["mlp_multipliers"][1]),
        fp32_logits=True)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=L,
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]),
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
    """u [b, s, d] (already times ``ssm_in_multiplier``) -> [b, s, d]."""
    b, s, _ = u.shape
    h, p, g, n, k = _sizes(m)
    d_in, gn = h * p, g * n
    mz, mx, mb, mc, mdt = (float(v) for v in m["ssm_multipliers"])
    if _DEPARTURE == "ssm_c_multiplier_left_out":
        mc = 1.0
    zxd = u @ _F32(w["w_in"])
    z = zxd[..., :d_in] * mz
    xbc = jnp.concatenate([zxd[..., d_in:2 * d_in] * mx, zxd[..., 2 * d_in:2 * d_in + gn] * mb,
                           zxd[..., 2 * d_in + gn:2 * d_in + 2 * gn] * mc], -1)
    dt = zxd[..., 2 * d_in + 2 * gn:] * mdt
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


def _rotated(x, theta: float):
    """x [b, s, heads, hd] at positions 0..s-1, rotate-half over the whole head."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv          # [s, hd / 2]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(w, u, m):
    """u [b, s, d] (already times ``attention_in_multiplier``) -> [b, s, d]."""
    b, s, _ = u.shape
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    km = 1.0 if _DEPARTURE == "key_multiplier_left_out" else float(m["key_multiplier"])
    theta = float(m["rope_theta"])
    q = _rotated((u @ _F32(w["wq"])).reshape(b, s, hq, hd), theta)
    k = _rotated((u @ _F32(w["wk"])).reshape(b, s, hkv, hd) * km, theta)
    v = (u @ _F32(w["wv"])).reshape(b, s, hkv, hd)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    sc = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return o.reshape(b, s, hq * hd) @ _F32(w["wo"])


def _mlp(w, u, m):
    gate_m, down_m = (float(v) for v in m["mlp_multipliers"])
    y = (u @ _F32(w["w_up"])) * jax.nn.silu((u @ _F32(w["w_gate"])) * gate_m)
    return (y @ _F32(w["w_down"])) * down_m


def block(x, n1, n2, pw, fw, m: dict):
    """One block on x [b, s, d] float32: the two mixers' sum, then the SwiGLU."""
    eps = m["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = _rms(x, n1, eps)
        y = float(m["ssm_out_multiplier"]) * _mamba(pw["mamba"], u * float(m["ssm_in_multiplier"]), m)
        if _DEPARTURE != "attention_dropped":
            y = y + float(m["attention_out_multiplier"]) * _attention(
                pw["gqa"], u * float(m["attention_in_multiplier"]), m)
        x = x + y
        return x + _mlp(fw, _rms(x, n2, eps), m)


def _embedded(params, tokens, m):
    return _F32(params["embed"]["embedding"])[tokens] * float(m["embedding_multiplier"])


def _block_weights(params, l: int):
    layers = params["layers"]
    return (layers["attn_norm"]["scale"][l], layers["mlp_norm"]["scale"][l],
            layers["par"][l], layers["mlp"][l])


def hidden_states(params, tokens, m: dict):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32."""
    x = _embedded(params, tokens, m)
    for l in range(m["num_hidden_layers"]):
        x = block(x, *_block_weights(params, l), m)
    return _rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])


def _head(h, kernel, m):
    with jax.default_matmul_precision("highest"):
        return (h @ _F32(kernel)) * float(m["lm_head_multiplier"])


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32."""
    return _head(hidden_states(params, tokens, m), params["lm_head"]["kernel"], m)


def logits_in_blocks(params, tokens, m: dict, rows, cols: int = 32768):
    """``logits(params, tokens, m)[0, rows]`` [len(rows), vocab] as a numpy array,
    computed a block at a time on the device: ONE jitted program a layer (its
    matrices cast up as it runs and dropped with it) and one a column block of
    the head, so that the float32 copies never stand side by side (the head's
    alone is 4.98 GiB at 261 120 x 5120).  Inside ``departure`` the programs are
    traced with it open; inside ``weights_rounded_to`` each block's matrices are
    rounded before its program reads them (``_as_read``): a control's reference."""
    import numpy as np

    embed, one_block, final, head = _programs(json.dumps(m, sort_keys=True), _DEPARTURE)
    x = embed(_as_read(params["embed"]["embedding"][tokens]))
    for l in range(m["num_hidden_layers"]):
        x = one_block(x, *_as_read(_block_weights(params, l)))
    h = final(x, params["final_norm"]["scale"], jnp.asarray(rows, jnp.int32))
    del x
    kernel = params["lm_head"]["kernel"]
    out = [np.asarray(head(h, _as_read(kernel[:, at:at + cols])))
           for at in range(0, kernel.shape[1], cols)]
    return np.concatenate(out, axis=1)


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
    """``logits_in_blocks``' four jitted programs for one configuration (its JSON),
    traced with the departure ``dep`` open as it was when it was called (a jitted
    body is traced at its first call, which may come after the context closed)."""
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

    return (traced(lambda rows: _F32(rows) * float(m["embedding_multiplier"])),
            traced(lambda x, n1, n2, pw, fw: block(x, n1, n2, pw, fw, m)),
            traced(lambda x, w, r: _rms(x, w, m["rms_norm_eps"])[0][r]),
            traced(lambda h, k: _head(h, k, m)))


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
    """Parameters a token's forward pass multiplies by HERE: both mixers'
    projections and the SwiGLU of every block, the head's rows."""
    d = m["hidden_size"]
    h, p, g, n, _ = _sizes(m)
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    a_block = d * (2 * h * p + 2 * g * n + h) + h * p * d \
        + d * hd * (2 * hq + 2 * hkv) + 3 * d * m["intermediate_size"]
    return int(d * m["vocab_size"] + m["num_hidden_layers"] * a_block)


def mixer_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token outside the matmuls by parameters, a block:
    the state's update and read-out (3 H P N multiply-adds), the convolution,
    and ``ctx`` keys at 4 Hq hd."""
    h, p, g, n, k = _sizes(m)
    ssm = 6.0 * h * p * n + 2.0 * k * (h * p + 2 * g * n)
    attn = 4.0 * m["num_attention_heads"] * m["head_dim"] * ctx
    return m["num_hidden_layers"] * (ssm + attn)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's mixers at
    the mean context (seq+1)/2.  (No training cell runs this architecture.)"""
    return 6.0 * matmul_params(m) + 3.0 * mixer_flops_per_token(m, (seq + 1) / 2)
