"""``model_type`` "evabyte": what the harness needs from this architecture (the
five callables ``models/__init__.py`` lists), and ``probe`` for the serving
driver's check of the summaries a closed window is left KEEPING.

The plain reference is the EvaByte language model in straightforward
``jax.numpy``: float32, every matmul at ``highest``, no cache, no pages, no
batching: a window of query rows at a time against its own keys and every
summary before it, so that a sequence of 32 768 fits.  It reads the program's
parameter tree (``models/latent.py``: ``layers/attn_norm``, ``layers/mlp_norm``,
``layers/eva`` and ``layers/mlp``, one tree a layer), so both sides run on the
same weights.

``d`` = ``hidden_size`` 4096, eps = ``rms_norm_eps`` 1e-5, no bias, untied head.
Block ``l``, input ``x`` [T, d], absolute positions ``i = 0 .. T - 1``:

- ``h = x + EVA_l(N1(x))``, ``y = h + W_d (silu(N2(h) W_g) * (N2(h) W_u))``
  (``intermediate_size`` 11008); ``N(x) = x / rms(x; eps) * (1 + w)``
  (``norm_add_unit_offset``).  The residual sums are float32 here; the program's
  stream is bfloat16 and its sum is the float32 sum rounded to it
  (``fp32_skip_add``: what a bfloat16 add computes).
- ``q, k, v = N1(x) W_q, W_k, W_v``, ``num_attention_heads`` 32 heads of 128 each
  (``num_key_value_heads`` 32: no grouping); rotary (rotate-half) over the WHOLE
  head, theta ``rope_theta`` 100 000, on q and k, by the POSITION.
- Windows ``w(i) = i // window_size`` (2048, aligned, not overlapping), chunks
  ``c(j) = j // chunk_size`` (16; ``num_chunks`` null: the chunk size decides).
  For every chunk ``c`` and head ``h``, with learned ``phi_h, mu_h`` in R^128:
  ``a_m = softmax over the chunk's 16 positions m of (phi_h . k_m)`` (k rotated;
  no scale and no ``-|k|^2 / 2`` inside), ``k~_c = sum_m a_m k_m + mu_h``,
  ``v~_c = sum_m a_m v_m``.
- For query ``i``: exact set ``S_i = {j : w(j) = w(i), j <= i}``, summary set
  ``C_i = {c : the chunk's window < w(i)}`` (a window's own chunks are never
  summaries to its own queries);
  ``o_i = [sum_S e^(q_i . k_j / sqrt 128) v_j + sum_C e^(q_i . k~_c / sqrt 128) v~_c]
  / [the same sums without v]``: ONE softmax over both sets, float32
  (``mixedp_attn``); ``EVA = o W_o``.
- Embedding ``vocab_size`` 320 x d; final norm; head ``W_h``: d x
  (``num_pred_heads`` 8 x 320), untied; the NEXT byte's logits are head 0's 320
  columns (columns 0-319), float32 (``fp32_logits``).  The other seven heads'
  columns are held and not read: multi-byte decoding is left out.

The estimator (a joint softmax over the own window's keys and one
control-variate summary per chunk outside it) is EVA's published one (Zheng et
al., ICLR 2023); ``window_size`` and ``chunk_size`` are the config's.  What the
config does not spell and this file READS is under the configuration file's
``assumed`` (``pooling``, ``windows``, ``summaries_of``, ``head_layout``), and each
reading has a control below (``DEPARTURES``) that the serving driver must see
refused.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

_WEIGHTS_AS = None  # the control's precision, while ``weights_rounded_to`` is open
# a control of the MATHEMATICS, while ``departure`` is open: the reference
# computes something else in one place and has to come out NOT correct
_DEPARTURE = None
DEPARTURES = ("mean_pooling", "no_key_offset", "own_window_summaries", "summaries_unroped",
              "window_edge_off_by_one_chunk", "row_for_position", "bf16_softmax")
# (``bf16_softmax``: bfloat16 SCORES change an output by less than its own
# rounding to bfloat16 and no comparison of outputs can see them; what a
# softmax in bfloat16 can break is its SUMS over thousands of rows, so the
# control carries those in bfloat16: ``_softmax_bf16_sums``)


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
    """Inside (at TRACE time), the reference departs from the mathematics in
    ``name``'s place (``DEPARTURES``): the serving driver's controls of what
    ``correct`` can see."""
    global _DEPARTURE
    if name not in DEPARTURES:
        raise ValueError(f"no departure {name!r}; there are {DEPARTURES}")
    _DEPARTURE = name
    try:
        yield
    finally:
        _DEPARTURE = None


def transformer_config(model: dict, **overrides):
    """The configuration file's published keys -> the program's
    ``TransformerConfig`` with its ``latent`` description of the blocks."""
    from deepspeed_tpu.models.latent import Eva, LatentSpec
    from deepspeed_tpu.models.transformer import TransformerConfig

    heads = model["num_attention_heads"]
    if model["attention_class"] != "eva" or model["attention_bias"] \
            or model["tie_word_embeddings"] or model["hidden_act"] != "silu" \
            or not model["norm_add_unit_offset"] or model["rope_scaling"] is not None \
            or model["num_chunks"] is not None or model["num_key_value_heads"] != heads \
            or model["hidden_size"] % heads or model["window_size"] % model["chunk_size"]:
        raise ValueError("only the published evabyte block is mapped here")
    n = model["num_hidden_layers"]
    spec = LatentSpec(
        layer_kinds=("eva",) * n, full=None, sliding=None, index_heads=0, index_dim=0,
        index_topk=0, first_dense=n, n_routed=0, n_held=0, held_offset=0, experts_per_tok=0,
        moe_width=0, n_shared=0, unit_offset=True,
        eva=Eva(num_heads=heads, head_dim=model["hidden_size"] // heads,
                rope_theta=float(model["rope_theta"]), window=int(model["window_size"]),
                chunk=int(model["chunk_size"]), init_std=float(model["init_std"])),
        pred_heads=int(model["num_pred_heads"]), fp32_logits=bool(model["fp32_logits"]))
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=n, num_heads=heads,
        num_kv_heads=heads, head_dim=model["hidden_size"] // heads,
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=dtypes[model["torch_dtype"]], latent=spec)
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def rows(n: int, m: dict) -> int:
    """Rows a context of ``n`` positions keeps: one per chunk of every closed
    window, one per position of the open one."""
    w, c = m["window_size"], m["chunk_size"]
    return n // w * (w // c) + n % w


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + _F32(w))


def _rotary(x, at, theta: float):
    """x [b, s, h, hd]: rotate-half over the whole head at positions ``at`` [s]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = at.astype(jnp.float32)[None, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(k, v, k_raw, phi, mu, chunk: int):
    """k, v [b, s, h, hd] (k rotated; ``k_raw`` before rotation) -> one summary
    per WHOLE chunk: (k~, v~) [b, s // chunk, h, hd]."""
    b, s, h, hd = k.shape
    n = s // chunk
    cut = lambda a: a[:, :n * chunk].reshape(b, n, chunk, h, hd)
    src = cut(k_raw if _DEPARTURE == "summaries_unroped" else k)
    score = jnp.einsum("bnchd,hd->bnch", src, phi)
    if _DEPARTURE == "mean_pooling":
        score = jnp.zeros_like(score)
    a = jax.nn.softmax(score, axis=2)
    ks = jnp.einsum("bnch,bnchd->bnhd", a, src)
    if _DEPARTURE != "no_key_offset":
        ks = ks + mu
    return ks, jnp.einsum("bnch,bnchd->bnhd", a, cut(v))


def attention_core(q, k, v, ks, vs, m: dict):
    """The ONE softmax of EVA attention on given rows: q, k, v [b, s, h, hd] (q
    and k rotated), the summaries ks, vs [b, s // chunk, h, hd] -> [b, s, h,
    hd].  A window of query rows at a time against its own keys, causal, and the
    summaries of the windows before it (all of a long sequence's scores at once
    would not fit the chip)."""
    b, s, h, hd = q.shape
    window, chunk = m["window_size"], m["chunk_size"]
    n_sum, per = ks.shape[1], window // chunk

    def one_window(i):
        lo = i * window
        q_w, k_w, v_w = (jax.lax.dynamic_slice_in_dim(a, lo, window, axis=1) for a in (q, k, v))
        sc_own = jnp.einsum("bqhd,bkhd->bhqk", q_w, k_w) * hd ** -0.5
        sc_sum = jnp.einsum("bqhd,bnhd->bhqn", q_w, ks) * hd ** -0.5
        j = jnp.arange(window)
        own = (j[None, :] <= j[:, None]) & (lo + j[None, :] < s)
        edge = i * per
        if _DEPARTURE == "window_edge_off_by_one_chunk":
            edge = edge - 1  # the last chunk before the window is not seen
        seen = jnp.arange(n_sum)[None, :] < edge
        if _DEPARTURE == "own_window_summaries":
            # ... and the own window's chunks that closed before the query, too
            seen = jnp.arange(n_sum)[None, :] < (lo + j[:, None]) // chunk
        seen = jnp.broadcast_to(seen, (window, n_sum))
        sc = jnp.concatenate([jnp.where(own, sc_own, -jnp.inf),
                              jnp.where(seen, sc_sum, -jnp.inf)], axis=-1)
        vals = jnp.concatenate([v_w, vs], axis=1)
        if _DEPARTURE == "bf16_softmax":
            return _softmax_bf16_sums(sc, vals)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), vals)

    n_win = -(-s // window)
    pad = n_win * window - s
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    o = jax.lax.map(one_window, jnp.arange(n_win))               # [n_win, b, window, h, hd]
    return jnp.moveaxis(o, 0, 1).reshape(b, n_win * window, h, hd)[:, :s]


def _softmax_bf16_sums(sc, vals, block: int = 128):
    """The control ``bf16_softmax``: the same softmax with its running sums (the
    normaliser and the weighted values) CARRIED in bfloat16 from one block of
    ``block`` keys to the next, as a kernel whose accumulators are bfloat16
    would: scores and exponentials float32, each block's partial sums float32."""
    n = sc.shape[-1]
    pad = -n % block
    sc = jnp.pad(sc, ((0, 0),) * 3 + ((0, pad),), constant_values=-jnp.inf)
    vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0), (0, 0)))
    e = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))        # [b, h, q, K]
    blocks = (n + pad) // block
    e = jnp.moveaxis(e.reshape(*e.shape[:3], blocks, block), 3, 0)
    vb = jnp.moveaxis(vals.reshape(vals.shape[0], blocks, block, *vals.shape[2:]), 1, 0)
    # (``reduce_precision``: a cast to bfloat16 and back is no rounding XLA has to keep)
    low = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def step(carry, x):
        l, acc = carry
        e_b, v_b = x
        return (low(l + jnp.sum(e_b, -1)),
                low(acc + jnp.einsum("bhqk,bkhd->bhqd", e_b, v_b))), None

    b, h, q = sc.shape[:3]
    (l, acc), _ = jax.lax.scan(step, (jnp.zeros((b, h, q)), jnp.zeros((b, h, q, vals.shape[-1]))),
                               (e, vb))
    return jnp.moveaxis(acc / l[..., None], 1, 2)


def _attention(w, u, m, probe):
    b, s, _ = u.shape
    h, window, chunk = m["num_attention_heads"], m["window_size"], m["chunk_size"]
    hd = m["hidden_size"] // h
    split = lambda a: a.reshape(b, s, h, hd)
    q, k_raw, v = (split(u @ _F32(w[n])) for n in ("wq", "wk", "wv"))
    at = jnp.arange(s)
    if _DEPARTURE == "row_for_position":  # rotary fed the cache's row index
        at = at // window * (window // chunk) + at % window
    q, k = _rotary(q, at, float(m["rope_theta"])), _rotary(k_raw, at, float(m["rope_theta"]))
    ks, vs = summaries(k, v, k_raw, _F32(w["phi"]), _F32(w["mu"]), chunk)
    if probe is not None:
        probe.append({"eva_k": ks, "eva_v": vs})
    return attention_core(q, k, v, ks, vs, m).reshape(b, s, h * hd) @ _F32(w["wo"])


def attention_on(w, q, k, v, m: dict):
    """The reference's attention on the PROGRAM's own rows of one layer (q, k, v
    [b, s, h, hd] as its projections and rotary left them): the summaries of k
    and v under the layer's ``phi`` / ``mu``, then the one softmax.  What the
    program's attention output is held to apart from everything before it."""
    with jax.default_matmul_precision("highest"):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        ks, vs = summaries(k, v, k, _F32(w["phi"]), _F32(w["mu"]), m["chunk_size"])
        return attention_core(q, k, v, ks, vs, m)


def _swiglu(x, gt, up, dn):
    return (jax.nn.silu(x @ _F32(gt)) * (x @ _F32(up))) @ _F32(dn)


def hidden_states(params, tokens, m: dict, probe=None):
    """tokens [b, s] -> final-norm hidden states [b, s, d] float32.  ``probe``
    (a list) collects per layer the summaries ``k~, v~`` of every whole chunk
    ([b, s // chunk, h, hd]): what a closed window's page is left keeping."""
    eps, layers = m["rms_norm_eps"], params["layers"]
    with jax.default_matmul_precision("highest"):
        x = _F32(params["embed"]["embedding"])[tokens]
        for l in range(m["num_hidden_layers"]):
            u = _norm(x, layers["attn_norm"]["scale"][l], eps)
            x = x + _attention(layers["eva"][l], u, m, probe)
            u = _norm(x, layers["mlp_norm"]["scale"][l], eps)
            fw = layers["mlp"][l]
            x = x + _swiglu(u, fw["w_gate"], fw["w_up"], fw["w_down"])
        return _norm(x, params["final_norm"]["scale"], eps)


def _head(params, m: dict):
    """Head 0's columns of the head matrix: the next byte's."""
    return _F32(params["lm_head"]["kernel"])[:, :m["vocab_size"]]


def logits(params, tokens, m: dict):
    """[b, s, vocab] float32: the NEXT byte's logits (prediction head 0)."""
    h = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return h @ _head(params, m)


def probe(params, tokens, m: dict, at=0, rows=None):
    """(logits, the summaries layer by layer), for the serving driver; with
    ``rows`` (static) only the ``rows`` positions from ``at`` on get logits."""
    seen: list = []
    h = hidden_states(params, tokens, m, seen)
    if rows is not None:
        h = jax.lax.dynamic_slice_in_dim(h, at, rows, axis=1)
    with jax.default_matmul_precision("highest"):
        return h @ _head(params, m), seen


def make_loss_fn(m: dict):
    """``loss(params, batch, rng=None)``: token-mean next-token cross entropy
    of ``batch["input_ids"]`` [b, s+1] (no training cell runs this architecture)."""

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
    """Parameters a token's forward pass multiplies by HERE: each layer's four
    attention projections and its SwiGLU, and head 0's columns of the head (the
    other prediction heads' are held and not multiplied by)."""
    d = m["hidden_size"]
    return int(m["num_hidden_layers"] * (4 * d * d + 3 * d * m["intermediate_size"])
               + d * m["vocab_size"])


def mixer_flops_per_token(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token's attention at context ``ctx``: the rows it
    reads (``rows``: its window's exact keys, a summary per earlier chunk), at 4
    H hd each a layer."""
    d = m["hidden_size"]
    return 4.0 * d * (rows(int(ctx), m) + 1) * m["num_hidden_layers"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter and three times the forward's attention at
    the mean context.  (No training cell runs this architecture.)"""
    at = range(0, seq, max(seq // 64, 1))
    mean_rows = sum(rows(i, m) + 1 for i in at) / len(at)
    return 6.0 * matmul_params(m) + 3.0 * 4.0 * m["hidden_size"] * mean_rows \
        * m["num_hidden_layers"]

