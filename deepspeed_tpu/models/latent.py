"""Decoder whose layers are of several KINDS (``TransformerConfig.latent``):
latent attention (MLA) on every layer, either over the keys a learned indexer
selects (``full``), over a sliding window with its own ranks and head count
(``sliding``) or over EVERY cached row (``every``: DeepSeek-V2's layer, which
keeps latent pages and nothing else); a dense SwiGLU on the leading layers and
routed experts with a shared expert after them, of which this process may hold
a SHARE (``moe/layer.py:moe_block_held``).  What differs between the models of
this family is VALUES of the spec too: YaRN on the rope dims and the softmax
scale's factor that goes with it (``LatentAttn.rope_scaling``,
``scale_factor``), the headwise output gate there or not (``LatentAttn.gate``),
the routing sigmoid + bias or a softmax limited to a few groups of experts
(``routing``, ``n_group``, ``topk_group``).

Parameters are grouped per kind (``layers/full``, ``layers/sliding``,
``layers/every``, ``layers/mlp``, ``layers/moe``: a tuple with one tree per layer of the kind,
NOT one stacked array, because a slice of a stacked array handed to a Pallas
kernel is copied first: 6 GB a dispatch for the experts; the two norms are
stacked ``[L, d]``); ``layer_params`` picks layer ``l``'s trees.  The per-token halves of a layer (``attn_inputs``,
``indexer_inputs``, ``attn_output``, ``ffn``) are shared by the uncached
``forward`` here and by the serving runner (``inference/latent_runner.py``),
which differ only in where a layer's keys live.  There is no pipeline or
tensor-parallel path for these layers yet, and a backward only where the note
on training below says so.

A model may instead be made of blocks that are ONE norm and ONE mixer
(``SINGLE``: ``x <- x + mixer(norm(x))``): a Mamba-2 state-space mixer
(``mamba``, ``ops/ssm.py``), grouped-query attention without positions
(``gqa``), or the expert layer alone (``experts``), whose experts may be of two
matrices with ``relu(.)^2`` between and work in a latent (``expert_form``,
``moe_latent``).  Their trees are ``layers/norm`` (stacked) and one tuple per
kind; ``block_params`` picks block ``l``'s.

The families meet in a third (``HYBRID``): blocks of TWO norms whose mixer is a
recurrence with a matrix state (``gdn``: Gated DeltaNet, ``ops/gdn.py``) or
gated grouped-query attention (``GatedGqa``: q / k norms, rotary positions on
part of the head, a sigmoid output gate or none) of up to TWO kinds in one model, each
with its own head count and rotary table: ``gattn`` over every key (K / V
pages) and ``wattn`` over the last ``window`` keys (a K / V ring a slot); or EVA
attention (``eva``, ``ops/eva.py``: plain multi-head attention, rotary over the
whole head, over the exact keys of the query's own window of positions and one
learned summary row per chunk of every window before it: K / V pages that give
all but one back when a window closes).  What
differs between the models of this family is VALUES of the spec: the gate one
value a channel, one a head or absent (``GatedGqa.gate``), YaRN on the rotary table
(``GatedGqa.rope_scaling``), the feed-forward the expert layer in every block
or a dense SwiGLU on the ``first_dense`` leading ones, routed by a softmax over
all experts or by sigmoid scores with a selection bias and ``routed_scale``
(``routing``), the shared expert behind a gate of its own or not
(``shared_gate``) or absent (no ``n_shared``, no ``shared_width``), the norms'
weights zero-centred, ``x^ (1 + w)``, or plain
(``unit_offset``).  Trees: ``layers/attn_norm``, ``layers/mlp_norm`` (stacked)
and one tuple per kind (``gdn``, ``gattn``, ``wattn``, ``eva``, ``moe``, and ``mlp``
where ``first_dense`` > 0); ``hybrid_params`` picks block ``l``'s.  The head may
hold ``pred_heads`` heads' columns side by side, of which the next token reads
the first ``vocab_size``.

A two-norm block may instead hold TWO PARALLEL MIXERS (``par``, Falcon-H1's
block): a Mamba-2 recurrence (``LatentSpec.mamba``) AND grouped-query attention
(``LatentSpec.gqa``, rotated over the whole head) read ONE normed input and
their outputs are SUMMED into the residual, in EVERY block, so a sequence keeps
a recurrence's state and K / V pages side by side for every layer; a dense
SwiGLU follows.  Every projection carries a CONSTANT multiplier (muP's, as
configuration: ``Mamba.in_multiplier`` / ``multipliers`` / ``out_multiplier``,
``Gqa.in_multiplier`` / ``key_multiplier`` / ``out_multiplier``, ``LatentSpec``'s
for the embedding, the logits and the SwiGLU's gate and output), applied as
written: the product is scaled in float32 and rounded once (``_mm``,
``scaled``), since a multiplier is no bfloat16 number and folding it into
bfloat16 weights is not exact.  Trees: ``layers/par`` = a tuple of ``{"mamba":
.., "gqa": ..}`` a block, ``layers/mlp``; such a model's blocks are all of the kind.

A two-norm block's mixer may also be ONE of those two, chosen BY BLOCK
(``LatentSpec.two_norms`` with the kinds ``mamba`` / ``gqa``; Granite 4.0-H's
block): a Mamba-2 recurrence in most blocks and position-free GQA in the rest,
each followed by the expert layer (or a leading dense SwiGLU) as every two-norm
block is, so a sequence keeps a state for each recurrent block and K / V pages
for each attention block.  Both residual branches carry ONE constant
(``residual_multiplier``: ``x + c mixer(norm(x))``, ``x + c ffn(norm(x))``), the
softmax scale may be the configuration's own constant (``Gqa.scale``), and the
head may be TIED: with ``cfg.tie_embeddings`` the tree holds no ``lm_head`` and
the logits are the normed rows against the embedding's held rows.  Trees:
``layers/mamba`` and ``layers/gqa``, a tuple a kind, beside ``layers/moe``.

TRAINING (``CausalLM.loss_fn`` -> ``forward`` under ``jax.grad``, through the
train engine): the blocks whose mixer is attention over K / V (``gattn``,
``wattn``, ``gqa``) and whose feed-forward is a SwiGLU or the held experts.
They attend through the flash dispatcher (``ops/pallas/flash_attention.py``: a
window is a band of blocks; off the chip the band-masked XLA body), each block
under ``jax.checkpoint`` as ``cfg.remat`` says, the routers' scores handed out
for the balance term (``LatentSpec.router_aux_loss_coef``), the blocks' counts
noted for the step's metrics (``telemetry.count_in_step``).  A kind with no
backward yet (key selection, the chunked scans, the delta rule, the latent
bodies, the chunk summaries) refuses by its mechanism when its backward is traced (``_forward_only``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

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
    window: int = 0  # 0: the layer's kind says which keys; n: the last n positions
    rope_scaling: Optional["Yarn"] = None
    scale_factor: float = 1.0  # the softmax scale times this (YaRN's mscale^2)
    gate: bool = True          # a sigmoid scalar a head on the output (``w_g``)

    @property
    def row(self) -> int:  # what the cache keeps per key
        return self.kv_rank + self.rope_dim

    @property
    def scale(self) -> float:
        return float(self.nope_dim + self.rope_dim) ** -0.5 * self.scale_factor


SINGLE = ("mamba", "gqa", "experts")  # kinds of a block that is one norm, one mixer


@dataclass(frozen=True)
class Mamba:
    """A Mamba-2 mixer: ``num_heads`` heads of ``head_dim`` channels, a state
    ``state`` wide per channel, ``B`` and ``C`` shared by the heads of each of
    ``n_groups`` groups, a causal depthwise convolution over ``conv`` tokens."""

    num_heads: int
    head_dim: int
    n_groups: int
    state: int
    conv: int
    chunk: int  # tokens a chunk of the uncached forward's scan (serving: a page)
    # constant multipliers: on the normed input before ``W_in``, on the five segments
    # z | x | B | C | dt of ``W_in``'s output (empty: none), on ``W_out``'s output
    in_multiplier: float = 1.0
    multipliers: Tuple[float, ...] = ()
    out_multiplier: float = 1.0

    @property
    def d_in(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_width(self) -> int:  # x, B and C go through the convolution
        return self.d_in + 2 * self.n_groups * self.state

    @property
    def in_scale(self) -> Optional[np.ndarray]:
        """``multipliers`` laid over ``W_in``'s ``in_width`` outputs, float32."""
        if not self.multipliers:
            return None
        gn = self.n_groups * self.state
        return np.repeat(np.asarray(self.multipliers, np.float32),
                         [self.d_in, self.d_in, gn, gn, self.num_heads])

    @property
    def in_width(self) -> int:  # [z | xBC | dt]
        return self.d_in + self.conv_width + self.num_heads

    @property
    def state_shape(self) -> Tuple[int, int, int]:  # what a sequence keeps, float32
        return self.num_heads, self.head_dim, self.state


@dataclass(frozen=True)
class Gqa:
    """Grouped-query attention: no positional embedding (``rope_theta`` 0), or
    rotary positions (rotate-half) over the whole head at ``rope_theta``.
    Constant multipliers on the normed input, on the keys BEFORE their rotation
    and on ``W_o``'s output; ``scale``: the softmax scale where it is the
    configuration's own constant (None: ``head_dim ** -0.5``)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 0.0
    in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    out_multiplier: float = 1.0
    scale: Optional[float] = None


# mixers of a two-norm block: a recurrence, gated attention over every key, ... over a
# window, attention over a window's exact keys and the summaries of the windows before it
# ... and ``par``: TWO mixers side by side on one normed input, summed
HYBRID = ("gdn", "gattn", "wattn", "eva", "par")
# the mixers a ``par`` block holds, ONE of each; with ``LatentSpec.two_norms`` also a
# two-norm block's kinds: ONE of the two a block
PAR_MIXERS = ("mamba", "gqa")


@dataclass(frozen=True)
class Gdn:
    """A Gated DeltaNet mixer: ``num_v_heads`` value heads of ``v_dim`` over
    ``num_k_heads`` key heads of ``k_dim`` (value head ``j`` reads key head
    ``j // (num_v_heads / num_k_heads)``), a matrix state ``k_dim x v_dim`` per
    value head, a causal depthwise convolution over ``conv`` tokens."""

    num_k_heads: int
    k_dim: int
    num_v_heads: int
    v_dim: int
    conv: int
    chunk: int  # tokens a chunk of the uncached forward's scan (serving: a page)

    @property
    def key_width(self) -> int:
        return self.num_k_heads * self.k_dim

    @property
    def d_in(self) -> int:
        return self.num_v_heads * self.v_dim

    @property
    def conv_width(self) -> int:  # q, k and v go through the convolution
        return 2 * self.key_width + self.d_in

    @property
    def state_shape(self) -> Tuple[int, int, int]:  # what a sequence keeps, float32
        return self.num_v_heads, self.k_dim, self.v_dim


@dataclass(frozen=True)
class Yarn:
    """YaRN's scaling of a rotary table: frequencies that turn fewer than
    ``beta_slow`` times over ``original_max`` positions are divided by
    ``factor``, those that turn more than ``beta_fast`` times are left, a
    linear ramp between; cos and sin times ``attention_factor``."""

    factor: float
    original_max: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


@dataclass(frozen=True)
class GatedGqa:
    """Grouped-query attention with RMSNorm on each head's q and k, rotary
    positions (rotate-half) on the first ``rope_dim`` of ``head_dim`` and a
    sigmoid gate on the output: one value a CHANNEL, projected beside q, one a
    HEAD, from a projection of its own (``w_g``), or NONE."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_dim: int
    rope_theta: float
    window: int = 0              # 0: every key; n: the last n positions, its own included
    gate: str = "channel"        # 'channel' | 'head' | 'none'
    rope_scaling: Optional[Yarn] = None


@dataclass(frozen=True)
class Eva:
    """EVA attention: ``num_heads`` heads of ``head_dim`` with keys and values of
    their own (no grouping), rotary positions (rotate-half) over the whole head,
    a learned pooling vector ``phi`` and key offset ``mu`` a head.  A query
    attends the exact keys of its own aligned window of ``window`` positions and
    one summary per ``chunk`` positions of every window before it
    (``ops/eva.py``)."""

    num_heads: int
    head_dim: int
    rope_theta: float
    window: int
    chunk: int
    init_std: float = 0.02  # of ``phi`` and ``mu``

    @property
    def rows_a_closed_window(self) -> int:
        return self.window // self.chunk


@dataclass(frozen=True)
class LatentSpec:
    layer_kinds: Tuple[str, ...]  # 'full' | 'sliding' | 'every', or SINGLE's, or HYBRID's: one per layer held
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
    mamba: Optional[Mamba] = None
    gqa: Optional[Gqa] = None
    expert_form: str = "swiglu"  # 'swiglu': gate, up, down | 'relu2': up, relu(.)^2, down
    moe_latent: int = 0          # > 0: the routed experts work in a latent this wide
    shared_width: int = 0        # the shared expert's width, if not moe_width * n_shared
    gdn: Optional[Gdn] = None
    gattn: Optional[GatedGqa] = None
    # 'sigmoid': + bias, the picked normalised | 'softmax': over all, the picked renormalised
    # | 'group_limited': softmax over all, the picked from the ``topk_group`` groups (of
    # ``n_group``) of largest maximum, NOT renormalised, times ``routed_scale``
    routing: str = "sigmoid"
    shared_gate: bool = False    # the shared expert's output times sigmoid(x . w_sg)
    unit_offset: bool = False    # RMSNorm's weights are zero-centred: x^ (1 + w)
    wattn: Optional[GatedGqa] = None  # a second kind of gated attention, over a window
    every: Optional[LatentAttn] = None  # latent attention over every cached row
    n_group: int = 0
    topk_group: int = 0
    # > 0: the training loss adds this times the routers' balance term, the mean over
    # the expert layers of n_routed x sum_e (share of the pairs on e) x (mean score of e)
    router_aux_loss_coef: float = 0.0
    eva: Optional[Eva] = None
    # the head holds this many prediction heads' ``vocab_size`` columns side by side;
    # the next token's logits are the first head's
    pred_heads: int = 1
    fp32_logits: bool = False  # the head's product accumulates AND leaves in float32
    # constants that belong to no mixer (``Mamba`` and ``Gqa`` carry their own): on the
    # embedding's rows, on the logits, on a SwiGLU's gate INSIDE its SiLU and on its output
    embedding_multiplier: float = 1.0
    logits_multiplier: float = 1.0
    mlp_gate_multiplier: float = 1.0
    mlp_down_multiplier: float = 1.0
    # the kinds ``mamba`` / ``gqa`` name a block of ONE norm and that mixer (False:
    # ``SINGLE``) or of TWO norms, that mixer and a feed-forward behind it (True);
    # every other kind is of one family by its name
    two_norms: bool = False
    residual_multiplier: float = 1.0  # on BOTH branches of a two-norm block, before the sum

    @property
    def par(self) -> bool:
        """Blocks of two parallel mixers (``mamba`` + ``gqa``)."""
        return "par" in self.layer_kinds

    @property
    def single(self) -> bool:
        """Blocks of one norm and one mixer each (``SINGLE``)."""
        return bool(self.layer_kinds) and not self.two_norms \
            and all(k in SINGLE for k in self.layer_kinds)

    @property
    def hybrid(self) -> bool:
        """Blocks of two norms: a mixer (``HYBRID``'s, or with ``two_norms`` one of
        ``PAR_MIXERS`` a block), then a dense SwiGLU or the expert layer."""
        kinds = HYBRID + (PAR_MIXERS if self.two_norms else ())
        return bool(self.layer_kinds) and all(k in kinds for k in self.layer_kinds)

    @property
    def stateful(self) -> bool:
        """What a slot keeps is a recurrence's state of fixed size and K / V
        pages (``single`` or ``hybrid``), not latent pages and window rings."""
        return self.single or self.hybrid

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        """The layers that hold experts, in order."""
        if self.single:
            return tuple(l for l, k in enumerate(self.layer_kinds) if k == "experts")
        return tuple(range(self.first_dense, len(self.layer_kinds)))

    def _held(self, kinds):
        """(kind, mixer) of the one of ``kinds`` that some block holds, (None, None)
        where none does: what a slot keeps follows the KINDS PRESENT."""
        kind = next((k for k in kinds if self.count(k)), None)
        return kind, kind and self.mixer(kind)

    @property
    def recurrence(self):
        """(kind, mixer) of a ``stateful`` model's recurrence (None, None for a
        model of attention alone)."""
        return self._held(tuple(RECURRENCES))

    def mixer(self, kind: str):
        """The spec of a ``stateful`` model's mixer of ``kind`` (SINGLE's, HYBRID's)."""
        return getattr(self, kind)

    @property
    def attention(self):
        """(kind, mixer) of a ``stateful`` model's attention over K / V PAGES."""
        return self._held(("gqa", "gattn"))

    @property
    def ringed(self) -> bool:
        """Some layer keeps a ring of its window's rows per slot."""
        return any(k in ("sliding", "wattn") for k in self.layer_kinds)

    @property
    def indexed(self) -> bool:
        """Some layer keeps an index key a position beside its latent row."""
        return "full" in self.layer_kinds

    def attn(self, kind: str) -> LatentAttn:
        return getattr(self, kind)  # 'full' | 'sliding' | 'every'

    def count(self, kind: str) -> int:
        """Layers that hold a mixer of ``kind`` (a block of parallel mixers holds
        one of each of its two)."""
        return sum(k == kind or (k == "par" and kind in PAR_MIXERS) for k in self.layer_kinds)

    @property
    def index_scale(self) -> float:
        return float(self.index_heads * self.index_dim) ** -0.5


def _attn_shapes(d: int, a: LatentAttn) -> Dict[str, tuple]:
    h = a.num_heads
    out = {
        "w_dq": (d, a.q_rank), "w_uq": (a.q_rank, h * (a.nope_dim + a.rope_dim)),
        "w_dkv": (d, a.row), "w_uk": (a.kv_rank, h * a.nope_dim),
        "w_uv": (a.kv_rank, h * a.v_dim), "w_g": (d, h), "wo": (h * a.v_dim, d),
    }
    if not a.gate:
        del out["w_g"]
    return out


def _index_shapes(d: int, s: LatentSpec) -> Dict[str, tuple]:
    return {"w_iq": (s.full.q_rank, s.index_heads * s.index_dim),
            "w_ik": (d, s.index_dim), "w_iw": (d, s.index_heads)}


def _single_shapes(d: int, s: LatentSpec, kind: str) -> Dict[str, tuple]:
    """A single-mixer block's parameters by name (its one norm apart)."""
    if kind == "mamba":
        mb = s.mamba
        return {"w_in": (d, mb.in_width), "conv_w": (mb.conv, mb.conv_width),
                "conv_b": (mb.conv_width,), "dt_bias": (mb.num_heads,),
                "a_log": (mb.num_heads,), "d_skip": (mb.num_heads,),
                "norm": (mb.d_in,), "w_out": (mb.d_in, d)}
    if kind == "gqa":
        g = s.gqa
        return {"wq": (d, g.num_heads * g.head_dim), "wk": (d, g.num_kv_heads * g.head_dim),
                "wv": (d, g.num_kv_heads * g.head_dim), "wo": (g.num_heads * g.head_dim, d)}
    r, fm = s.moe_latent or d, s.moe_width
    fs = s.shared_width or s.moe_width * s.n_shared
    gated = s.expert_form == "swiglu"
    out = {"router": (d, s.n_routed), "bias": (s.n_routed,),
           "w_up": (s.n_held, r, fm), "w_down": (s.n_held, fm, r),
           "s_up": (d, fs), "s_down": (fs, d)}
    if gated:
        out.update(w_gate=(s.n_held, r, fm), s_gate=(d, fs))
    if not fs:  # no shared expert
        for name in ("s_up", "s_down", "s_gate"):
            out.pop(name, None)
    if s.moe_latent:
        out.update(w_lat_down=(d, r), w_lat_up=(r, d))
    if s.routing == "softmax":
        del out["bias"]  # a softmax router selects by its scores alone
    if s.shared_gate:
        out["w_sg"] = (d, 1)
    return out


def _hybrid_shapes(d: int, s: LatentSpec, kind: str) -> Dict[str, tuple]:
    """A two-norm block's mixer parameters by name (``HYBRID``); its expert
    layer's are ``_single_shapes(d, s, "experts")``."""
    if kind == "gdn":
        gd = s.gdn
        return {"w_qkvz": (d, gd.conv_width + gd.d_in), "w_ba": (d, 2 * gd.num_v_heads),
                "conv_w": (gd.conv, gd.conv_width), "dt_bias": (gd.num_v_heads,),
                "a_log": (gd.num_v_heads,), "norm": (gd.v_dim,), "w_out": (gd.d_in, d)}
    if kind in PAR_MIXERS:  # ONE of the two a block: the single-mixer block's tree
        return _single_shapes(d, s, kind)
    if kind == "par":  # both mixers' trees, by ``<mixer>/<name>``
        return {f"{mixer}/{name}": shape for mixer in PAR_MIXERS
                for name, shape in _single_shapes(d, s, mixer).items()}
    if kind == "eva":
        ev = s.eva
        width = ev.num_heads * ev.head_dim
        return {"wq": (d, width), "wk": (d, width), "wv": (d, width), "wo": (width, d),
                "phi": (ev.num_heads, ev.head_dim), "mu": (ev.num_heads, ev.head_dim)}
    ga = s.mixer(kind)
    per_channel = ga.gate == "channel"  # each head's projection is then [q | gate]
    out = {"wq": (d, (1 + per_channel) * ga.num_heads * ga.head_dim),
           "wk": (d, ga.num_kv_heads * ga.head_dim),
           "wv": (d, ga.num_kv_heads * ga.head_dim), "q_norm": (ga.head_dim,),
           "k_norm": (ga.head_dim,), "wo": (ga.num_heads * ga.head_dim, d)}
    if ga.gate == "head":
        out["w_g"] = (d, ga.num_heads)
    return out


def param_count(cfg) -> int:
    """Parameters HELD here (a share of the experts and of the vocabulary
    where the configuration says so)."""
    s, d = cfg.latent, cfg.hidden_size
    size = lambda shapes: sum(int(np.prod(v)) for v in shapes.values())
    n = (1 + (0 if cfg.tie_embeddings else s.pred_heads)) * cfg.vocab_size * d + d
    if s.single:
        return n + sum(d + size(_single_shapes(d, s, kind)) for kind in s.layer_kinds)
    if s.hybrid:
        ffn_of = lambda l: 3 * d * cfg.intermediate_size if l < s.first_dense \
            else size(_single_shapes(d, s, "experts"))
        return n + sum(2 * d + size(_hybrid_shapes(d, s, kind)) + ffn_of(l)
                       for l, kind in enumerate(s.layer_kinds))
    for l, kind in enumerate(s.layer_kinds):
        a = s.attn(kind)
        n += 2 * d + size(_attn_shapes(d, a)) + a.q_rank + a.kv_rank
        if kind == "full":
            n += size(_index_shapes(d, s)) + 2 * s.index_dim
        if l < s.first_dense:
            n += 3 * d * cfg.intermediate_size
        else:
            n += d * s.n_routed + (s.n_routed if s.routing == "sigmoid" else 0)
            n += 3 * d * s.moe_width * (s.n_held + s.n_shared)
    return n


def flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward FLOPs a token of a causal sequence of ``seq_len``
    requires of what is HELD here (``CausalLM.flops_per_token``): 6 for each
    parameter of a matmul the token passes (attention's and a dense layer's
    matrices, the router, the head's slice, and of the routed experts the
    ``experts_per_tok x n_held / n_routed`` it meets here in the mean, the
    shared expert), and attention's (query, key) pairs under each layer's mask
    at 12 x heads x head_dim a pair.  For the kinds that train (``forward``)."""
    s, d = cfg.latent, cfg.hidden_size
    if not s.stateful or any(k in ("mamba", "gdn", "eva", "par") for k in s.layer_kinds):
        refuse("CausalLM.flops_per_token", "only blocks of attention over K / V, SwiGLU and "
               "held experts are counted (they are the kinds that train)")
    size = lambda shapes, names: sum(int(np.prod(shapes[n])) for n in names if n in shapes)
    ex = _single_shapes(d, s, "experts")
    per_tok = s.experts_per_tok / s.n_routed  # of the held experts' parameters
    experts = size(ex, ("router", "s_up", "s_gate", "s_down", "w_lat_down", "w_lat_up", "w_sg")) \
        + per_tok * size(ex, ("w_up", "w_gate", "w_down"))
    params, pairs = d * cfg.vocab_size, 0.0
    for l, kind in enumerate(s.layer_kinds):
        if kind == "experts":
            params += experts
            continue
        mixer = s.mixer(kind)
        shapes = _hybrid_shapes(d, s, kind) if s.hybrid else _single_shapes(d, s, kind)
        params += size(shapes, ("wq", "wk", "wv", "wo", "w_g"))
        pairs += mixer.num_heads * mixer.head_dim \
            * allowed_pairs(seq_len, getattr(mixer, "window", 0)) / seq_len
        if s.hybrid:
            params += 3 * d * cfg.intermediate_size if l < s.first_dense else experts
    return 6.0 * params + 12.0 * pairs


def init_params(rng, cfg, dtype=jnp.float32) -> Params:
    s, d, L = cfg.latent, cfg.hidden_size, cfg.num_layers
    if len(s.layer_kinds) != L:
        raise ValueError(f"{len(s.layer_kinds)} layer kinds for {L} layers")
    keys = iter(jax.random.split(rng, 32 * (L + 1)))

    def dense(shape, fan_in):
        w = jax.random.normal(next(keys), shape, jnp.float32) / np.sqrt(fan_in)
        return w.astype(dtype)

    def single(kind):
        """A single-mixer block, or a two-norm block's mixer: matrices N(0, 1 /
        fan-in); the router's bias as below; a recurrence's ``dt_bias`` so that
        softplus lands log-uniform in [0.001, 0.1], ``A = -exp(a_log)`` in
        -U(1, 16), ``D`` ones, and those float32 as the recurrence reads them."""
        if kind == "par":
            return {mixer: single(mixer) for mixer in PAR_MIXERS}
        shapes = _hybrid_shapes(d, s, kind) if kind in HYBRID else _single_shapes(d, s, kind)
        w = {name: dense(sh, sh[-2]) for name, sh in shapes.items()
             if len(sh) >= 2 and name not in ("phi", "mu")}
        if kind == "eva":  # the pooling vectors and key offsets: N(0, init_std^2), float32
            for name in ("phi", "mu"):
                w[name] = s.eva.init_std * jax.random.normal(next(keys), shapes[name],
                                                             jnp.float32)
        if "bias" in shapes:
            w["bias"] = (0.02 * jax.random.normal(next(keys), (s.n_routed,))
                         ).astype(jnp.float32)
        if kind in ("mamba", "gdn"):
            heads = s.mamba.num_heads if kind == "mamba" else s.gdn.num_v_heads
            u = lambda lo, hi: jax.random.uniform(next(keys), (heads,), jnp.float32, lo, hi)
            dt = jnp.exp(u(np.log(1e-3), np.log(1e-1)))
            if kind == "mamba":
                w["conv_b"] = dense((s.mamba.conv_width,), s.mamba.conv)
            w.update(dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
                     a_log=jnp.log(u(1.0, 16.0)))
        if kind == "mamba":
            w.update(d_skip=jnp.ones((s.mamba.num_heads,), jnp.float32),
                     norm=jnp.ones((s.mamba.d_in,), dtype))
        if kind == "gdn":
            w["norm"] = jnp.ones((s.gdn.v_dim,), jnp.float32)
        if kind in ("gattn", "wattn"):
            hd = s.mixer(kind).head_dim
            w.update(q_norm=norm_weight((hd,)), k_norm=norm_weight((hd,)))
        return w

    def centred(shape):
        """A zero-centred norm's weight (``x^ (1 + w)``), float32: N(0, 0.1^2),
        so that the offset is not the whole of it."""
        return 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    def norm_weight(shape):
        """A two-norm block's norm by the spec's ``unit_offset``: plain weights are 1."""
        return centred(shape) if s.unit_offset else jnp.ones(shape, dtype)

    if cfg.tie_embeddings and s.pred_heads > 1:
        raise ValueError("a tied head is the embedding's rows: one prediction head")
    # (a tied head IS the embedding's held rows: the tree holds no ``lm_head``)
    head = lambda layers, norm: {
        "embed": {"embedding": dense((cfg.vocab_size, d), d)},
        "layers": layers,
        "final_norm": {"scale": norm},
        **({} if cfg.tie_embeddings else
           {"lm_head": {"kernel": dense((d, s.pred_heads * cfg.vocab_size), d)}}),
    }
    if s.single:
        layers = {"norm": {"scale": jnp.ones((L, d), dtype)}}
        for kind in SINGLE:
            layers[kind] = tuple(single(kind) for _ in range(s.count(kind)))
        return head(layers, jnp.ones((d,), dtype))
    if s.hybrid:
        if 0 < s.count("par") < L:
            raise ValueError("a model's blocks are all of two parallel mixers or none is")
        layers = {"attn_norm": {"scale": norm_weight((L, d))},
                  "mlp_norm": {"scale": norm_weight((L, d))},
                  "moe": tuple(single("experts") for _ in range(L - s.first_dense))}
        for kind in HYBRID + PAR_MIXERS:
            # (the older kinds' trees are there, empty or not)
            if kind not in ("eva", "par") + PAR_MIXERS or kind in s.layer_kinds:
                layers[kind] = tuple(single(kind) for _ in range(s.layer_kinds.count(kind)))
        if s.first_dense:
            f = cfg.intermediate_size
            layers["mlp"] = tuple(
                {"w_gate": dense((d, f), d), "w_up": dense((d, f), d), "w_down": dense((f, d), f)}
                for _ in range(s.first_dense))
        return head(layers, norm_weight((d,)))
    if any(k in SINGLE + HYBRID for k in s.layer_kinds):
        raise ValueError("single-mixer blocks, two-norm blocks of a recurrence or gated "
                         "attention, and latent-attention layers are three families: a "
                         "model's layers are all of one")

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
        # the selection bias: small and non-zero, so that it decides some
        # selections and no expert's score drowns in it (a softmax router has none)
        # (drawn AFTER the router's matrix: the order of the keys is the seed's weights)
        bias = lambda: {"bias": (0.02 * jax.random.normal(next(keys), (s.n_routed,))
                                 ).astype(jnp.float32)} if s.routing == "sigmoid" else {}
        return {
            "router": dense((d, s.n_routed), d), **bias(),
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
    for kind in ("full", "sliding", "every"):
        layers[kind] = tuple(attn(kind) for _ in range(s.count(kind)))
    layers["mlp"] = tuple(
        {"w_gate": dense((d, f), d), "w_up": dense((d, f), d), "w_down": dense((f, d), f)}
        for _ in range(nd))
    layers["moe"] = tuple(experts() for _ in range(nm))
    return head(layers, jnp.ones((d,), dtype))


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


def block_params(layers: Params, l: int, s: LatentSpec):
    """(kind, the norm's scale, the mixer's weights) of single-mixer block ``l``."""
    kind = s.layer_kinds[l]
    return kind, layers["norm"]["scale"][l], layers[kind][s.layer_kinds[:l].count(kind)]


def hybrid_params(layers: Params, l: int, s: LatentSpec):
    """(kind, the two norms' weights, the mixer's weights, the feed-forward's,
    whether that is the expert layer) of two-norm block ``l`` (``HYBRID``)."""
    kind = s.layer_kinds[l]
    norms = (layers["attn_norm"]["scale"][l], layers["mlp_norm"]["scale"][l])
    mw = layers[kind][s.layer_kinds[:l].count(kind)]
    if l < s.first_dense:
        return kind, norms, mw, layers["mlp"][l], False
    return kind, norms, mw, layers["moe"][l - s.first_dense], True


# ---------------------------------------------------------------------------
# the per-token halves of a layer: rows are tokens, [T, ...]
# ---------------------------------------------------------------------------
def rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf.astype(x.dtype) * scale


def rms_centred(x, w, eps):
    """RMSNorm with a zero-centred weight, ``x^ (1 + w)``, scaled in float32."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def norm(x, scale, cfg):
    """The model's RMSNorm by its spec (``unit_offset``)."""
    return (rms_centred if cfg.latent.unit_offset else rms)(x, scale, cfg.norm_eps)


def scaled(x, m):
    """``x`` times the constant ``m`` (a float, or float32 values along the last
    axis): the product in float32, rounded ONCE to ``x``'s dtype.  A multiplier
    is no bfloat16 number (0.3536 rounds by 0.1%), so it is not cast to one."""
    if np.ndim(m) == 0 and m == 1:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def residual(x, y, m: float = 1.0):
    """``x + m y``: a block's branch ``y`` times the constant on it (``scaled``: the
    product in float32, rounded once), added into the stream ``x``."""
    return x + scaled(y, m).astype(x.dtype)


def _mm(h, w, m=1.0):
    """``(h @ w) m``: with a constant multiplier the product leaves the matmul in
    float32, is scaled there and rounded once (an epilogue of the matmul's
    fusion); without one, ``h @ w`` as every other projection."""
    if m is None or (np.ndim(m) == 0 and m == 1):
        return h @ w
    return (jnp.dot(h, w, preferred_element_type=jnp.float32) * m).astype(h.dtype)


def embedded(params: Params, tokens, cfg):
    """The tokens' embedding rows in the model's dtype, times their multiplier."""
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    return scaled(x, cfg.latent.embedding_multiplier)


def yarn_ramp(r: int, theta: float, y: Yarn) -> np.ndarray:
    """The share of each of a rotary table's ``r / 2`` frequencies that YaRN
    divides by ``factor`` (0: left as it is, 1: wholly): a linear ramp from
    the dim that turns ``beta_fast`` times over ``original_max`` positions to
    the one that turns ``beta_slow`` times."""
    dim_of = lambda turns: r * np.log(y.original_max / (turns * 2 * np.pi)) / (2 * np.log(theta))
    lo = max(np.floor(dim_of(y.beta_fast)), 0)
    hi = min(np.ceil(dim_of(y.beta_slow)), r - 1)
    return np.clip((np.arange(r // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0).astype(np.float32)


def _rope(x, pos, theta: float, scaling: Optional[Yarn] = None):
    """x [T, h, r] rotated in the half-split layout at ``pos`` [T]."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    if scaling is not None:
        ramp = yarn_ramp(r, theta, scaling)
        inv = inv / scaling.factor * ramp + inv * (1.0 - ramp)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        cos, sin = cos * scaling.attention_factor, sin * scaling.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def absorbed_queries(w_uk, q, q_rope, a: LatentAttn):
    """The heads' queries folded through ``W_uk``: q [T, H, nope + ..] (its
    first ``nope`` dims count), q_rope [T, H, rope] (rotated) -> ``[q_nope W_uk
    ; q_rope]`` [T, H, row], what scores a cache row as it lies."""
    w_uk = w_uk.reshape(a.kv_rank, a.num_heads, a.nope_dim)
    return jnp.concatenate(
        [jnp.einsum("thn,rhn->thr", q[..., :a.nope_dim], w_uk), q_rope], axis=-1)


def latent_values(w_uv, o_lat, a: LatentAttn):
    """Attention over latent rows [T, H, r_kv] folded through ``W_uv``: the
    heads' values [T, H, v]."""
    w_uv = w_uv.reshape(a.kv_rank, a.num_heads, a.v_dim)
    return jnp.einsum("thr,rhv->thv", o_lat, w_uv)


def attn_inputs(aw, h, pos, a: LatentAttn, cfg, absorbed: bool = True):
    """Normed input ``h`` [T, d] -> (c_q [T, r_q], absorbed queries [T, H,
    row], the key's cache row [T, row], gate [T, H] or None where the kind has
    none).  ``absorbed=False`` (the kind over EVERY row, whose pack chooses the
    form by the length of a run: ``latent_runner._attend_every``) hands the
    queries BEFORE ``W_uk`` instead, ``[q_nope ; q_rope]`` [T, H, nope + rope]."""
    t, d, eps = h.shape[0], cfg.hidden_size, cfg.norm_eps
    up = lambda r: (d / r) ** 0.5 if cfg.latent.rescale_lora else 1.0
    c_q = rms(h @ aw["w_dq"], aw["q_norm"], eps) * jnp.asarray(up(a.q_rank), h.dtype)
    q = (c_q @ aw["w_uq"]).reshape(t, a.num_heads, a.nope_dim + a.rope_dim)
    q_r = _rope(q[..., a.nope_dim:], pos, a.rope_theta, a.rope_scaling)
    kv = h @ aw["w_dkv"]
    c_kv = rms(kv[:, :a.kv_rank], aw["kv_norm"], eps) * jnp.asarray(up(a.kv_rank), h.dtype)
    k_r = _rope(kv[:, None, a.kv_rank:], pos, a.rope_theta, a.rope_scaling)[:, 0]
    if absorbed:
        q = absorbed_queries(aw["w_uk"], q, q_r, a)
    else:
        q = jnp.concatenate([q[..., :a.nope_dim], q_r], axis=-1)
    gate = jax.nn.sigmoid((h @ aw["w_g"]).astype(jnp.float32)) if a.gate else None
    return c_q, q, jnp.concatenate([c_kv, k_r], axis=-1), gate


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


def attn_output(aw, o_lat, gate, a: LatentAttn, values: bool = False):
    """Attention over latent rows [T, H, r_kv] -> the sublayer's output [T, d]:
    through ``W_uv`` per head, the headwise gate where the kind has one, ``W_o``.
    ``values=True``: ``o_lat`` is the heads' values [T, H, v] already (the kind
    over every row folds through ``W_uv`` where its form needs it)."""
    o = o_lat if values else latent_values(aw["w_uv"], o_lat, a)
    if gate is not None:
        o = o * gate[..., None].astype(o_lat.dtype)
    return o.reshape(o.shape[0], -1) @ aw["wo"]


def mamba_chunks(mw, h, valid, cont, conv_prev, state_prev, mb: Mamba, eps: float,
                 probe=None):
    """A state-space mixer over CHUNKS of tokens (``ops/ssm.py``: the chunked
    scan).  h [G, L, d] the normed input; valid [G, L] (padding rows leave the
    state as it was); cont [G]: the chunk continues the chunk before it, which
    is then whole; ``conv_prev`` [G, K-1, C] and ``state_prev`` [G, H, P, N] what
    each other chunk starts from (zeros for a sequence's first).  Returns
    (out [G, L, d], the state after each chunk float32, the convolution's tail
    after each chunk's last valid row [G, K-1, C]).  ``probe`` (a list) is
    handed what the recurrence consumed, a row a token (``_probed``)."""
    from ..ops import ssm

    g, l, d = h.shape
    z, xbc, dt = _mamba_inputs(mw, h.reshape(g * l, d), mb)
    xbc = xbc.reshape(g, l, -1)
    dt = jnp.where(valid[..., None], dt.reshape(g, l, -1), 0.0)
    k1 = mb.conv - 1
    before = jnp.roll(xbc[:, l - k1:], 1, axis=0)  # the chunk before's last rows
    prev = jnp.where(cont[:, None, None], before, conv_prev.astype(xbc.dtype))
    conv, ext = ssm.conv_chunks(prev, xbc, mw["conv_w"], mw["conv_b"])
    x, b, c = _mamba_split(conv, mb)
    y, states = ssm.ssm_scan(x, dt, -jnp.exp(mw["a_log"]), b, c, state_prev, cont)
    _probed(probe, g * l, x, b, c, dt)
    at = jnp.sum(valid, axis=1, dtype=jnp.int32)[:, None] + jnp.arange(k1)[None, :]
    tails = jnp.take_along_axis(ext, at[..., None], axis=1)
    out = _mamba_output(mw, y.reshape(g * l, *y.shape[2:]), x.reshape(g * l, *x.shape[2:]),
                        z, mb, eps, h.dtype)
    return out.reshape(g, l, d), states, tails


def mamba_step(mw, h, active, conv_tail, state, mb: Mamba, eps: float, probe=None):
    """The same mixer on ONE token a sequence (the recurrence itself).  h
    [B, d]; ``conv_tail`` [B, K-1, C] and ``state`` [B, H, P, N] are carried on
    where ``active`` and handed back bit-identical elsewhere.  Returns (out
    [B, d], state, conv tail)."""
    from ..ops import ssm

    z, xbc, dt = _mamba_inputs(mw, h, mb)
    conv, tail = ssm.conv_step(conv_tail, xbc, mw["conv_w"], mw["conv_b"])
    x, b, c = _mamba_split(conv, mb)
    y, state = ssm.ssm_step(state, x, dt, -jnp.exp(mw["a_log"]), b, c, active)
    _probed(probe, h.shape[0], x, b, c, dt)
    tail = jnp.where(active[:, None, None], tail, conv_tail)
    return _mamba_output(mw, y, x, z, mb, eps, h.dtype), state, tail


def _probed(probe, t: int, x, b, c, dt) -> None:
    """What a state-space block's recurrence consumed, for a benchmark's check
    of the state it leaves KEPT: x [t, H, P], B and C [t, R, N], the step
    sizes [t, H] (0 at padding), float32, a row a token."""
    if probe is not None:
        rows = lambda a: a.reshape(t, *a.shape[a.ndim - 2:]).astype(jnp.float32)
        probe.append({"ssm_x": rows(x), "ssm_b": rows(b), "ssm_c": rows(c),
                      "ssm_dt": dt.reshape(t, -1).astype(jnp.float32)})


def _mamba_inputs(mw, h, mb: Mamba):
    """h [T, d] -> (gate z [T, d_in], xBC [T, C] before the convolution, the
    step sizes softplus(dt + dt_bias) [T, H] float32)."""
    zxd = _mm(scaled(h, mb.in_multiplier), mw["w_in"], mb.in_scale)
    z, xbc, dt = jnp.split(zxd, [mb.d_in, mb.d_in + mb.conv_width], axis=-1)
    return z, xbc, jax.nn.softplus(dt.astype(jnp.float32) + mw["dt_bias"])


def _mamba_split(conv, mb: Mamba):
    """The convolution's output [..., C] -> (x [..., H, P], B and C [..., R, N])."""
    x, b, c = jnp.split(conv, [mb.d_in, mb.d_in + mb.n_groups * mb.state], axis=-1)
    lead = conv.shape[:-1]
    return (x.reshape(*lead, mb.num_heads, mb.head_dim),
            b.reshape(*lead, mb.n_groups, mb.state), c.reshape(*lead, mb.n_groups, mb.state))


def _mamba_output(mw, y, x, z, mb: Mamba, eps: float, dtype):
    """y, x [T, H, P] float32, z [T, d_in]: the skip ``D x``, the gate
    ``silu(z)`` BEFORE the norm, RMSNorm over each group's channels, ``W_out``."""
    t = y.shape[0]
    y = (y + mw["d_skip"][:, None] * x).reshape(t, mb.d_in) * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(t, mb.n_groups, -1)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return _mm(yg.reshape(t, mb.d_in).astype(dtype) * mw["norm"], mw["w_out"], mb.out_multiplier)


def gqa_inputs(aw, h, g: Gqa, pos=None):
    """h [T, d] -> (q [T, Hq, hd], k, v [T, Hkv, hd]): no positions, or q and k
    rotated over the whole head at ``pos`` [T] where the spec has a
    ``rope_theta`` (the keys times their multiplier BEFORE the rotation).  The
    barrier keeps the head split out of the dots (``model_runner._qkv``)."""
    h = scaled(h, g.in_multiplier)
    q, k, v = jax.lax.optimization_barrier(
        (h @ aw["wq"], _mm(h, aw["wk"], g.key_multiplier), h @ aw["wv"]))
    heads = lambda a, n: a.reshape(a.shape[0], n, g.head_dim)
    q, k, v = heads(q, g.num_heads), heads(k, g.num_kv_heads), heads(v, g.num_kv_heads)
    if g.rope_theta:
        if pos is None:
            raise ValueError("grouped-query attention with rotary positions needs the rows' "
                             "positions (single-mixer blocks hand none)")
        q, k = _rope(q, pos, g.rope_theta), _rope(k, pos, g.rope_theta)
    return q, k, v


def gqa_output(aw, o, g: Gqa):
    """Attention's output o [T, Hq, hd] through ``W_o`` (times its multiplier)."""
    return _mm(o.reshape(o.shape[0], -1), aw["wo"], g.out_multiplier)


def gdn_chunks(gw, h, valid, cont, conv_prev, state_prev, gd: Gdn, eps: float, probe=None):
    """A Gated DeltaNet mixer over CHUNKS of tokens (``ops/gdn.py``: the chunked
    delta rule), with ``mamba_chunks``' arguments and results: h [G, L, d]; valid
    [G, L] (padding rows leave the state as it was); cont [G]; ``conv_prev``
    [G, K-1, C] and ``state_prev`` [G, Hv, Dk, Dv] what each other chunk starts
    from.  Returns (out [G, L, d], the state after each chunk float32, the
    convolution's tail after each chunk's last valid row [G, K-1, C])."""
    from ..ops import gdn, ssm

    n, l, d = h.shape
    qkv, z, g, beta = _gdn_inputs(gw, h.reshape(n * l, d), gd)
    qkv = qkv.reshape(n, l, -1)
    g, beta = (jnp.where(valid[..., None], a.reshape(n, l, -1), 0.0) for a in (g, beta))
    k1 = gd.conv - 1
    before = jnp.roll(qkv[:, l - k1:], 1, axis=0)  # the chunk before's last rows
    prev = jnp.where(cont[:, None, None], before, conv_prev.astype(qkv.dtype))
    with jax.named_scope("gdn_conv"):
        conv, ext = ssm.conv_chunks(prev, qkv, gw["conv_w"], _no_bias(gd))
    q, k, v = _gdn_split(conv, gd)
    o, states = gdn.gdn_scan(q, k, v, g, beta, state_prev, cont)
    _gdn_probed(probe, n * l, q, k, v, g, beta)
    at = jnp.sum(valid, axis=1, dtype=jnp.int32)[:, None] + jnp.arange(k1)[None, :]
    tails = jnp.take_along_axis(ext, at[..., None], axis=1)
    out = _gdn_output(gw, o.reshape(n * l, *o.shape[2:]), z, gd, eps, h.dtype)
    return out.reshape(n, l, d), states, tails


def gdn_step(gw, h, active, conv_tail, state, gd: Gdn, eps: float, probe=None):
    """The same mixer on ONE token a sequence (the recurrence itself), as
    ``mamba_step``: h [B, d]; ``conv_tail`` [B, K-1, C] and ``state`` [B, Hv, Dk,
    Dv] are carried on where ``active`` and handed back bit-identical elsewhere.
    Returns (out [B, d], state, conv tail)."""
    from ..ops import gdn, ssm

    qkv, z, g, beta = _gdn_inputs(gw, h, gd)
    with la.scope("gdn_conv"):  # (``gdn_conv_step`` where a pack carries the step)
        conv, tail = ssm.conv_step(conv_tail, qkv, gw["conv_w"], _no_bias(gd))
    q, k, v = _gdn_split(conv, gd)
    o, state = gdn.gdn_step(state, q, k, v, g, beta, active)
    _gdn_probed(probe, h.shape[0], q, k, v, g, beta)
    tail = jnp.where(active[:, None, None], tail, conv_tail)
    return _gdn_output(gw, o, z, gd, eps, h.dtype), state, tail


RECURRENCES = {"mamba": (mamba_chunks, mamba_step), "gdn": (gdn_chunks, gdn_step)}  # (chunks, step)


def _no_bias(gd: Gdn):
    return jnp.zeros((gd.conv_width,), jnp.float32)  # this convolution has none


def _gdn_probed(probe, t: int, q, k, v, g, beta) -> None:
    """What a Gated DeltaNet block's recurrence consumed, for a benchmark's
    check of the state it leaves KEPT: q, k [t, Hk, Dk] as normalised, v [t, Hv,
    Dv], the log decays and the betas [t, Hv] (0 at padding), float32."""
    if probe is not None:
        rows = lambda a, nd: a.reshape(t, *a.shape[a.ndim - nd:]).astype(jnp.float32)
        probe.append({"gdn_q": rows(q, 2), "gdn_k": rows(k, 2), "gdn_v": rows(v, 2),
                      "gdn_g": rows(g, 1), "gdn_beta": rows(beta, 1)})


def _gdn_inputs(gw, h, gd: Gdn):
    """h [T, d] -> ([q | k | v] [T, C] before the convolution, the output gate
    z [T, d_in], the log decay ``-exp(a_log) softplus(a + dt_bias)`` and
    ``beta = sigmoid(b)`` [T, Hv] float32)."""
    qkv, z = jnp.split(h @ gw["w_qkvz"], [gd.conv_width], axis=-1)
    b, a = jnp.split((h @ gw["w_ba"]).astype(jnp.float32), 2, axis=-1)
    g = -jnp.exp(gw["a_log"]) * jax.nn.softplus(a + gw["dt_bias"])
    return qkv, z, g, jax.nn.sigmoid(b)


def _gdn_split(conv, gd: Gdn):
    """The convolution's output [..., C] float32 -> (q, k [..., Hk, Dk], v [...,
    Hv, Dv]) as the recurrence consumes them: k of unit length, q of length
    ``Dk^-1/2``."""
    q, k, v = jnp.split(conv, [gd.key_width, 2 * gd.key_width], axis=-1)
    lead = conv.shape[:-1]
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = (unit(a.reshape(*lead, gd.num_k_heads, gd.k_dim)) for a in (q, k))
    return q * gd.k_dim ** -0.5, k, v.reshape(*lead, gd.num_v_heads, gd.v_dim)


def _gdn_output(gw, o, z, gd: Gdn, eps: float, dtype):
    """o [T, Hv, Dv] float32, z [T, d_in]: RMSNorm over each head's channels
    (a plain weight) BEFORE the gate ``silu(z)``, then ``W_out``."""
    t = o.shape[0]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * gw["norm"]
    y = o.reshape(t, gd.d_in) * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(dtype) @ gw["w_out"]


def gattn_inputs(aw, h, pos, ga: GatedGqa, eps: float, unit_offset: bool = True):
    """h [T, d] at positions ``pos`` [T] -> (q [T, Hq, hd], k, v [T, Hkv, hd],
    the output gate float32: [T, Hq * hd] where each query head's projection is
    [q | gate], [T, Hq, 1] where the gate is one value a head, ``h @ w_g``, None
    where the kind has none); q
    and k normed over the head (``unit_offset``: zero-centred weights) and
    rotated on their first ``rope_dim`` dims.  The barrier as in ``gqa_inputs``."""
    t = h.shape[0]
    qg, k, v = jax.lax.optimization_barrier((h @ aw["wq"], h @ aw["wk"], h @ aw["wv"]))
    per_channel = ga.gate == "channel"
    if per_channel:
        q, gate = jnp.split(qg.reshape(t, ga.num_heads, 2 * ga.head_dim), 2, axis=-1)
    else:
        q = qg.reshape(t, ga.num_heads, ga.head_dim)
        gate = (h @ aw["w_g"])[..., None] if ga.gate == "head" else None
    heads = lambda a: a.reshape(t, ga.num_kv_heads, ga.head_dim)
    if ga.rope_dim == ga.head_dim:
        rot = lambda a: _rope(a, pos, ga.rope_theta, ga.rope_scaling)
    else:
        rot = lambda a: jnp.concatenate(
            [_rope(a[..., :ga.rope_dim], pos, ga.rope_theta, ga.rope_scaling),
             a[..., ga.rope_dim:]], axis=-1)
    normed = rms_centred if unit_offset else rms
    q = rot(normed(q, aw["q_norm"], eps))
    k, v = rot(normed(heads(k), aw["k_norm"], eps)), heads(v)
    if per_channel:
        gate = gate.reshape(t, -1)
    return q, k, v, None if gate is None else jax.nn.sigmoid(gate.astype(jnp.float32))


def gattn_output(aw, o, gate):
    """Attention's output o [T, Hq, hd] times the gate (a channel's [T, Hq *
    hd], a head's [T, Hq, 1] or None), through ``W_o``."""
    if gate is None:
        return o.reshape(o.shape[0], -1) @ aw["wo"]
    if gate.ndim == 3:
        y = (o.astype(jnp.float32) * gate).reshape(o.shape[0], -1)
    else:
        y = o.reshape(o.shape[0], -1).astype(jnp.float32) * gate
    return y.astype(o.dtype) @ aw["wo"]


def eva_inputs(aw, h, pos, ev: Eva):
    """h [T, d] at positions ``pos`` [T] -> (q, k, v [T, H, hd]); q and k rotated
    over the whole head.  The barrier as in ``gqa_inputs``."""
    q, k, v = jax.lax.optimization_barrier((h @ aw["wq"], h @ aw["wk"], h @ aw["wv"]))
    heads = lambda a: a.reshape(a.shape[0], ev.num_heads, ev.head_dim)
    rot = lambda a: _rope(heads(a), pos, ev.rope_theta)
    return rot(q), rot(k), heads(v)


def head_logits(x, params: Params, cfg):
    """Normed hidden rows x [..., d] through the head: the next token's logits
    [..., vocab] (the first of ``pred_heads`` heads' columns; float32 out of the
    product where the spec says ``fp32_logits``).  A TIED head
    (``cfg.tie_embeddings``) is the embedding's held rows, contracted over their
    width as they lie: no second array and no transposed copy."""
    s = cfg.latent
    m = s.logits_multiplier
    if cfg.tie_embeddings:
        rows = params["embed"]["embedding"]  # [vocab, d]
        wide = s.fp32_logits or m != 1.0  # (a constant scales the product in float32, as ``_mm``)
        out = jax.lax.dot_general(x, rows, (((x.ndim - 1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32 if wide else None)
        out = out if m == 1.0 else out * m
        return out if s.fp32_logits else out.astype(x.dtype)
    kernel = params["lm_head"]["kernel"]
    if s.pred_heads > 1:
        kernel = kernel[:, :cfg.vocab_size]
    if s.fp32_logits:
        out = jnp.dot(x, kernel, preferred_element_type=jnp.float32)
        return out if m == 1.0 else out * m
    return _mm(x, kernel, m)


def ffn(fw, h, is_moe: bool, cfg, valid=None, tile=None):
    """(output [T, d], and of an expert layer (routing stats, experts picked
    [T, k], every expert's score [T, n_routed]), else None).  ``tile``: the
    expert layer's row tile where the caller chooses it (``moe_block_held``)."""
    if not is_moe:
        s = cfg.latent  # (multipliers of 1.0: the products as they were)
        gate = _mm(h, fw["w_gate"], s.mlp_gate_multiplier)
        return _mm(jax.nn.silu(gate) * (h @ fw["w_up"]), fw["w_down"], s.mlp_down_multiplier), None
    from ..moe.layer import moe_block_held

    return moe_block_held(fw, h, cfg.latent, valid, tile)


# ---------------------------------------------------------------------------
# the uncached forward: every sequence is its own keys
# ---------------------------------------------------------------------------
def forward(params: Params, tokens, cfg, *, return_hidden: bool = False):
    """tokens [b, s] -> (logits [b, s, v] | hidden, the experts each expert
    layer's router picked (two-norm blocks: a tuple of [b * s, k], one a layer;
    None otherwise), the expert layers' balance terms summed: 0.0 unless the
    spec has a ``router_aux_loss_coef``)."""
    from ..telemetry import note_step_fact

    s_, (b, n) = cfg.latent, tokens.shape
    if s_.router_aux_loss_coef and not s_.hybrid:
        refuse("LatentSpec.router_aux_loss_coef", "only the two-norm blocks hand out "
               "their routers' scores")
    note_step_fact("tokens", b * n)
    note_step_fact("layers_with_experts", len(s_.expert_layers))
    pos = jnp.tile(jnp.arange(n), b)
    x = embedded(params, tokens.reshape(-1), cfg)
    grouped = lambda a: a.reshape(b, n, *a.shape[1:])
    if s_.single:
        return _head(params, _single_blocks(params["layers"], x, b, n, cfg), b, n, cfg,
                     return_hidden)
    if s_.hybrid:
        x, aux, picks = _hybrid_blocks(params["layers"], x, b, n, cfg)
        out, _, aux = _head(params, x, b, n, cfg, return_hidden, aux)
        return out, picks, aux
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
            o = _forward_only(o, "attention over the keys an indexer SELECTS")
        else:
            # 'every' is a window no shorter than the sequence: every key s <= t
            o = la.window_attention(q_abs, q_pos, rows, q_pos, a.window or n, a.kv_rank,
                                    a.scale)
            o = _forward_only(o, "the latent-attention bodies (ops/latent_attention.py)")
        x = x + attn_output(aw, o.reshape(b * n, *o.shape[2:]), gate, a).astype(x.dtype)
        h = rms(x, n2["scale"], cfg.norm_eps)
        x = x + ffn(fw, h, is_moe, cfg)[0].astype(x.dtype)
    return _head(params, x, b, n, cfg, return_hidden)


def _head(params: Params, x, b: int, n: int, cfg, return_hidden: bool, aux=0.0):
    """The final norm and the head on token rows x [b * n, d]."""
    x = norm(x, params["final_norm"]["scale"], cfg).reshape(b, n, -1)
    aux = jnp.asarray(aux, jnp.float32)
    if return_hidden:
        return x, None, aux
    return head_logits(x, params, cfg), None, aux


def _single_blocks(layers: Params, x, b: int, n: int, cfg):
    """The uncached forward's blocks of one mixer each; x [b * n, d]."""
    s_ = cfg.latent
    pos = jnp.tile(jnp.arange(n), b)
    for l in range(cfg.num_layers):
        kind, scale, w = block_params(layers, l, s_)
        h = rms(x, scale, cfg.norm_eps)
        y = ffn(w, h, True, cfg)[0] if kind == "experts" else _mixer(kind, w, h, b, n, pos, cfg)
        x = x + y.astype(x.dtype)
    return x


def _mixer(kind: str, w, h, b: int, n: int, pos, cfg):
    """The uncached forward's Mamba-2 recurrence or grouped-query attention
    (``PAR_MIXERS``), whichever block holds it, on normed rows h [b * n, d]."""
    s_ = cfg.latent
    if kind == "mamba":
        y = _chunked_uncached(mamba_chunks, w, h.reshape(b, n, -1), s_.mamba, cfg.norm_eps)
        return _forward_only(y.reshape(b * n, -1), "the chunked state-space scan (ops/ssm.py)")
    q, k, v = gqa_inputs(w, h, s_.gqa, pos)
    return gqa_output(w, _attend(q, k, v, b, n, 0, cfg, s_.gqa.scale), s_.gqa)


def _attend(q, k, v, b: int, n: int, window: int, cfg, scale=None):
    """Causal attention of ``b`` sequences of ``n`` rows each through the body
    ``cfg.attn_impl`` names (the flash dispatcher: the Pallas kernels on the
    chip where their gate takes the shape, the band-masked XLA body elsewhere):
    q [b * n, Hq, hd], k and v [b * n, Hkv, hd] -> [b * n, Hq, hd]; with
    ``window``, over the last ``window`` keys, a row's own included; ``scale``:
    the softmax scale where it is not ``hd ** -0.5``.  Inputs
    and output are ``remat='selective'``'s save points."""
    from ..ops.attention import get_attention_impl
    from .transformer import _ckpt_name

    q, k, v = (_ckpt_name(a.reshape(b, n, *a.shape[1:]), name)
               for a, name in ((q, "save_q"), (k, "save_k"), (v, "save_v")))
    with jax.named_scope("attn_window" if window else "attn_full"):
        o = get_attention_impl(cfg.attn_impl)(
            q, k, v, causal=True, **({"window": window} if window else {}),
            **({} if scale is None else {"scale": scale}))
    return _ckpt_name(o, "save_attn").reshape(b * n, *o.shape[2:])


def allowed_pairs(n: int, window: int = 0) -> int:
    """(query, key) pairs a causal sequence of ``n`` rows attends: every key at
    or before a row, the last ``window`` of them where there is a window."""
    w = min(window, n) if window else n
    return w * (w + 1) // 2 + (n - w) * w


def _hybrid_blocks(layers: Params, x, b: int, n: int, cfg):
    """The uncached forward's two-norm blocks (``HYBRID``); x [b * n, d] ->
    (x, the expert layers' balance terms summed, their routers' picks, a
    layer each).  Each block runs under
    ``jax.checkpoint`` as ``cfg.remat`` says and hands its counts out, which
    are noted for the step's metrics here, outside it."""
    from ..moe.layer import held_rows_laid_out
    from ..telemetry import count_in_step
    from .transformer import checkpointed

    s_, eps = cfg.latent, cfg.norm_eps
    pos = jnp.tile(jnp.arange(n), b)
    balance = s_.router_aux_loss_coef > 0

    def block(x, n1, n2, mw, fw, *, kind: str, is_moe: bool):
        h = norm(x, n1, cfg)
        if kind == "gdn":
            y = _chunked_uncached(gdn_chunks, mw, h.reshape(b, n, -1), s_.gdn, eps)
            y = _forward_only(y.reshape(b * n, -1), "the chunked delta rule (ops/gdn.py)")
        elif kind == "par":  # the SUM of the recurrence's side and attention's, one input
            y = _mixer("mamba", mw["mamba"], h, b, n, pos, cfg) \
                + _mixer("gqa", mw["gqa"], h, b, n, pos, cfg)
        elif kind in PAR_MIXERS:  # ONE of the two, chosen by block
            y = _mixer(kind, mw, h, b, n, pos, cfg)
        elif kind == "eva":
            from ..ops import eva

            ev = s_.eva
            q, k, v = (a.reshape(b, n, *a.shape[1:]) for a in eva_inputs(mw, h, pos, ev))
            with jax.named_scope("eva_attend"):
                o = eva.attend_uncached(q, k, v, mw["phi"], mw["mu"], ev.window, ev.chunk)
            o = _forward_only(o, "the chunk summaries of EVA attention (ops/eva.py)")
            y = o.reshape(b * n, -1) @ mw["wo"]
        else:
            ga = s_.mixer(kind)
            q, k, v, gate = gattn_inputs(mw, h, pos, ga, eps, s_.unit_offset)
            y = gattn_output(mw, _attend(q, k, v, b, n, ga.window, cfg), gate)
        x = residual(x, y, s_.residual_multiplier)
        y, routing = ffn(fw, norm(x, n2, cfg), is_moe, cfg)
        stats, picks, scores = routing if is_moe else (None, None, None)
        term = None
        if balance and is_moe:
            # n_routed x sum_e (e's share of the (token, pick) pairs: no gradient)
            #                  x (the mean over the tokens of e's score)
            with jax.named_scope("router"):
                share = jnp.mean(jnp.any(picks[..., None] == jnp.arange(s_.n_routed), axis=1),
                                 axis=0, dtype=jnp.float32) / s_.experts_per_tok
                term = s_.n_routed * jnp.sum(share * jnp.mean(scores, axis=0))
        return residual(x, y, s_.residual_multiplier), stats, term, picks

    aux, handed = jnp.zeros((), jnp.float32), []
    for l in range(cfg.num_layers):
        kind, (n1, n2), mw, fw, is_moe = hybrid_params(layers, l, s_)
        run = checkpointed(functools.partial(block, kind=kind, is_moe=is_moe), cfg.remat,
                           prevent_cse=True)
        x, stats, term, picks = run(x, n1, n2, mw, fw)
        if picks is not None:
            handed.append(picks)
        if term is not None:
            aux = aux + term
        if stats is not None:
            for name, value in zip(("expert_pairs_routed", "expert_pairs_held",
                                    "expert_rows_max", "expert_rows_min"), stats):
                count_in_step(name, value)
            rows, bounded = held_rows_laid_out(b * n, s_, stats[1])
            count_in_step("expert_rows_laid_out", rows)
            count_in_step("expert_layers_bounded", bounded)
        if kind not in ("gdn", "eva", "mamba"):
            count_in_step("causal_keys", jnp.float32(b * allowed_pairs(n)))
            window = 0 if kind in ("par", "gqa") else s_.mixer(kind).window  # (0: every key)
            count_in_step("window_keys_attended", jnp.float32(b * allowed_pairs(n, window)))
    return x, aux, tuple(handed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _forward_only(y, mechanism: str):
    """``y``, through a mixer that has no backward yet: differentiating it
    refuses by ``mechanism`` instead of handing back a gradient nobody checked."""
    return y


def _forward_only_fwd(y, mechanism):
    return y, None


def _forward_only_bwd(mechanism, _, g):
    refuse("training (a gradient through this layer)",
           f"{mechanism} has a forward and a serving path and no checked backward yet")


_forward_only.defvjp(_forward_only_fwd, _forward_only_bwd)


def _chunked_uncached(chunks, w, h, mixer, eps: float):
    """A recurrence's ``chunks`` (``mamba_chunks`` | ``gdn_chunks``) on h [b, n,
    d], every sequence from a zero state in chunks of ``mixer.chunk``."""
    b, n, d = h.shape
    size, c = mixer.chunk, -(-n // mixer.chunk)
    h = jnp.pad(h, ((0, 0), (0, c * size - n), (0, 0))).reshape(b * c, size, d)
    valid = jnp.tile(jnp.arange(c * size) < n, b).reshape(b * c, size)
    cont = jnp.tile(jnp.arange(c) > 0, b)
    out, _, _ = chunks(
        w, h, valid, cont, jnp.zeros((b * c, mixer.conv - 1, mixer.conv_width), h.dtype),
        jnp.zeros((b * c, *mixer.state_shape), jnp.float32), mixer, eps)
    return out.reshape(b, c * size, d)[:, :n]


def refuse(option: str, why: str):
    raise NotImplementedError(
        f"{option} is not supported for a model with layers of several kinds "
        f"(TransformerConfig.latent: latent attention, single-mixer blocks, or two-norm "
        f"blocks of a recurrence / gated attention): {why}")
