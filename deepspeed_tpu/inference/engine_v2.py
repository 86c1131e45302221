"""Continuous-batching inference engine (the FastGen-core analogue).

Port of the reference's ``InferenceEngineV2`` serving surface
(``inference/v2/engine_v2.py``): ``put(uids, tokens)`` admits/steps work
(:107), ``query``/``can_schedule`` do KV-block admission control
(:158/:184), ``flush`` releases sequences.  The execution model is
TPU-shaped: static-shape compiled functions — bucketed prefill (prompt
padded to the next bucket) + one batched decode kernel over the fixed slot
array — with host-side block bookkeeping (ragged.py) driving them, the
Dynamic-SplitFuse-style fixed token budget replaced by one-prefill-per-put
+ batched decode ticks.

The engine chooses its RUNNER once, at construction (``self.runner``:
``model_runner.DenseRunner``, or ``latent_runner.LatentRunner`` for
``cfg.latent``), and asks it for everything that depends on the kind of
layer state: the cache, the pack / tick / verify entries its jitted programs
call, whether a cold pack has a program of its own, whether its packs take
the tick's decode step beside their tokens (``packs_carry_step``: one program
a tick where a pack and a step went out as two), and the kind's host
accounting (extra ``stats`` counters, a dispatch's span arguments, what a
released slot gives back, what ``close()`` audits).
"""
from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import TransformerConfig
from ..utils.logging import log_dist
from . import model_runner
from .paged import kv_pool_pspec
from .ragged import StateManager
from .sampling import SamplingParams, finite_guard, sample


# burst-accumulator pad written by rows already deactivated on device:
# distinct from the -1 finite_guard poison sentinel (which is a real
# emission — always a row's LAST — that the host must see to quarantine)
_BURST_PAD = -2
# a ``tick_collect`` longer than this is logged with its ``ready`` split: the
# stalls of seconds name their phase in every run's output, traced or not
STALL_LOG_S = 1.0


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds max bucket {buckets[-1]}")


# A prefill pack hands its program ONE flat int32 buffer.  ``_pack_sizes`` IS
# the layout, for both sides: on the host ``unpack_pack`` returns numpy VIEWS
# of a buffer from ``new_pack`` (the build fills them in place), in a traced
# program the same static slices of the uploaded array.
def _pack_sizes(t_pad: int, block_size: int, max_seqs: int, max_pages: int,
                ctx: bool, step: bool = False) -> List[int]:
    """Lengths of tokens, seg, pos, pack_pages, last_idx (a cold pack), the
    slots' block tables (a context pack's segments and a carried step's rows:
    a slot is one or the other), ctx_lens (a context pack) and the tick's
    ``[4, slots]`` rows as ``decode_impl`` takes them (``step``: a pack that
    carries the tick's step), in the order they lie."""
    sizes = [t_pad, t_pad, t_pad, t_pad // block_size, max_seqs]
    if ctx or step:
        sizes.append(max_seqs * max_pages)
    if ctx:
        sizes.append(max_seqs)
    return sizes + [4 * max_seqs] if step else sizes


def unpack_pack(buf, block_size: int, max_seqs: int, max_pages: int, ctx: bool,
                step: bool = False):
    """(tokens, seg, pos, pack_pages, last_idx) of a cold pack's buffer, for
    a context pack also (ctx_tables, ctx_lens); with ``step`` the tables are
    there for a cold pack too and the step's rows come last."""
    per_slot = sum(_pack_sizes(0, block_size, max_seqs, max_pages, ctx, step))
    t_pad = (buf.shape[0] - per_slot) * block_size // (3 * block_size + 1)
    sizes = _pack_sizes(t_pad, block_size, max_seqs, max_pages, ctx, step)
    if sum(sizes) != buf.shape[0]:
        raise ValueError(f"no pack of {buf.shape[0]} int32 at block_size "
                         f"{block_size}, {max_seqs} slots, {max_pages} pages")
    at = np.cumsum([0] + sizes)
    parts = [buf[a:b] for a, b in zip(at[:-1], at[1:])]
    if ctx or step:
        parts[5] = parts[5].reshape(max_seqs, max_pages)
    if step:
        parts[-1] = parts[-1].reshape(4, max_seqs)
    return tuple(parts)


def new_pack(t_pad: int, block_size: int, max_seqs: int, max_pages: int,
             ctx: bool, step: bool = False):
    """An empty pack of ``t_pad`` tokens: (buffer, its views by
    ``unpack_pack``): no token, no page, no row to sample, no context, no
    live slot."""
    buf = np.zeros(sum(_pack_sizes(
        t_pad, block_size, max_seqs, max_pages, ctx, step)), np.int32)
    views = unpack_pack(buf, block_size, max_seqs, max_pages, ctx, step)
    for v in views[3:6]:  # pack_pages, last_idx, the tables: -1 = none
        v.fill(-1)
    return buf, views


class Enqueued:
    """One program of a tick, enqueued and not collected yet: what its
    collect needs.  ``rows``: a pack's [(seq, start, end)] or a step's
    sequences; ``finishing``: the sequences whose prompt a pack completes;
    ``step``: the sequences of the tick's step a pack carried (none: the pack
    alone); ``sampled``: the device's ``[slots]`` tokens while nobody has
    fetched them (a split dispatch), ``tokens`` once somebody has; ``split``:
    the fetch is not inside the dispatch span; ``by``: the dispatch span
    itself (``NULL_SPAN`` with telemetry off), whose id its collect names."""

    __slots__ = ("span", "rows", "finishing", "step", "split", "sampled", "tokens", "by")

    def __init__(self, span: str, rows: List, finishing: Optional[List],
                 split: bool, by, step: Sequence = ()):
        self.span, self.rows, self.finishing = span, rows, finishing
        self.step, self.split, self.by = list(step), split, by
        self.sampled = self.tokens = None


class InferenceEngineV2:
    """Paged-KV continuous-batching engine for one model replica."""

    def __init__(
        self,
        params,
        cfg: TransformerConfig,
        max_seqs: int = 64,
        num_blocks: int = 2048,
        block_size: int = 32,
        max_seq_len: Optional[int] = None,
        prefill_buckets: Sequence[int] = (64, 128, 256, 512, 1024, 2048, 4096),
        prefill_budget: Optional[int] = None,
        seed: int = 0,
        offload_weights: bool = False,
        grid=None,
        quantize_weights: Optional[str] = None,
        enable_prefix_caching: bool = False,
        prefill_chunk: Optional[int] = None,
        kv_watermark: float = 0.0625,
        enable_speculation: bool = False,
        spec_max_draft: int = 4,
        spec_min_match: int = 2,
        spec_lookup_window: int = 1024,
        telemetry=None,
        serve=None,
        faults=None,
        serve_replicas: int = 1,
        seq_shards: int = 1,
        quant_comm: Optional[str] = None,
        comm_tiles: Optional[int] = None,
    ):
        from ..utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        self.cfg = cfg
        # Families the paged v2 path cannot serve yet must refuse loudly
        # instead of decoding silently wrong tokens: ALiBi needs a
        # positional-bias operand in the paged decode kernel, and the
        # parallel-block layout (falcon/gptj/phi) shares one LN across both
        # branches while the runner assumes attn_norm/mlp_norm.  Per-family
        # biases (qkv/o/mlp/head) and bloom's embedding LN ARE applied
        # (model_runner._attn_out/_ffn/_lm_logits/_embed).
        if cfg.position == "alibi":
            raise NotImplementedError(
                "InferenceEngineV2 cannot serve position='alibi' models: the "
                "paged decode kernel has no additive positional-bias operand "
                "yet — use init_inference (the dense v1 engine) instead"
            )
        if cfg.parallel_block:
            raise NotImplementedError(
                "InferenceEngineV2 cannot serve parallel_block models "
                "(falcon/gptj/phi layout): the runner wires sequential "
                "attn_norm/mlp_norm blocks — use init_inference instead"
            )
        # The ONE place that chooses the runner: everything below asks
        # ``self.runner`` (the device entries its programs call, its cache,
        # its host accounting), never ``cfg.latent``.
        if cfg.latent is not None:
            # layers of several kinds (models/latent.py) keep two kinds of
            # state (latent pages, a window ring per slot): what would serve
            # them wrongly is refused by the name of its option
            from ..models.latent import refuse
            from .latent_runner import LatentRunner

            # a slot's state is a recurrence's (state-space or delta rule), not a ring
            states = cfg.latent.stateful and not cfg.latent.ringed
            # latent PAGES alone (no ring, no recurrence, no index keys beside
            # them) are shared as a dense model's K / V pages are: a pack's
            # chunk that starts at a position > 0 reads the pages under it
            pages_alone = not (cfg.latent.ringed or cfg.latent.stateful or cfg.latent.indexed)
            # pages that are given back while the sequence lives (EVA attention)
            compacted = cfg.latent.eva is not None
            for option, on, why in (
                ("grid (a tensor-parallel / replica / seq-shard serve mesh)",
                 grid is not None or int(serve_replicas) > 1 or int(seq_shards) > 1,
                 "its weights and caches have no sharding rules yet"),
                ("enable_speculation", enable_speculation,
                 "a rejected draft's share cannot be rolled back out of a chunk's summary, "
                 "nor a window that closed under it be opened again" if compacted else
                 "a rejected draft's state-space state cannot be rolled back" if states
                 else "a rejected draft's rows cannot be rolled back out of a ring"),
                ("quantize_weights (and int8 / fp8 KV)", quantize_weights is not None,
                 "its projections, states and pages have no quantized form yet" if states
                 else "its projections and latent rows have no quantized form yet"),
                ("enable_prefix_caching", enable_prefix_caching and not pages_alone,
                 "a block's key says which positions it holds, and a compacted table's "
                 "page holds a closed window's summaries or an open window's rows by "
                 "where its sequence stands: summary pages could be shared a window at "
                 "a time, exact pages not" if compacted else
                 "a cached prefix would have to bring a state snapshot with its pages"
                 if states else
                 "a cached prefix would have to bring a window's ring with its pages"),
                ("offload_weights", offload_weights, "untried"),
            ):
                if on:
                    refuse(option, why)
            self.runner = LatentRunner(cfg)
        else:
            self.runner = model_runner.DenseRunner(cfg)
        # 2-D batch x model serve mesh: ``serve_replicas`` > 1 partitions
        # slots and KV blocks into per-replica groups laid out over the
        # mesh's batch (data) axis — explicit opt-in, because leftover mesh
        # capacity also lands on the data axis and plain-TP callers expect
        # replicated behavior there.
        tp = grid.spec.model if grid is not None else 1
        dp = int(serve_replicas)
        sq = int(seq_shards)
        if dp > 1:
            if grid is None or grid.spec.data != dp:
                raise ValueError(
                    f"serve_replicas={dp} needs a grid whose batch (data) "
                    f"axis is exactly {dp} — build it with "
                    f"initialize_mesh(batch={dp}, model=...)"
                )
            if max_seqs % dp or num_blocks % dp:
                raise ValueError(
                    f"max_seqs ({max_seqs}) and num_blocks ({num_blocks}) "
                    f"must divide into {dp} serve replicas"
                )
        # 3-D batch x seq x model mesh: ``seq_shards`` > 1 additionally
        # slices each replica's block pool over the mesh's seq axis.  A
        # sequence's pages round-robin across the slices (StateManager
        # striping), each seq shard computes a flash-style PARTIAL over its
        # local pages, and a log-sum-exp ring pass (S-1 collective_permute
        # hops of the [B, hq, hd+2] accumulator) merges the partials — so a
        # context bigger than one slice's pool serves fine as long as the
        # AGGREGATE pool fits it.
        if sq > 1:
            if grid is None or grid.spec.seq != sq:
                raise ValueError(
                    f"seq_shards={sq} needs a grid whose seq axis is "
                    f"exactly {sq} — build it with "
                    f"initialize_mesh(seq={sq}, model=..., batch=...)"
                )
            if num_blocks % (dp * sq):
                raise ValueError(
                    f"num_blocks ({num_blocks}) must divide into "
                    f"{dp} replicas x {sq} seq shards"
                )
            # Prefix caching, chunked prefill and speculation are
            # REPLICA-AFFINE at dp > 1 (nothing is gated any more):
            # admission routes a prompt to the replica holding its deepest
            # cached prefix (per-replica content-hash namespaces — keys
            # chain on block ids, which are replica-partitioned, so the
            # hash map partitions for free), ctx/verify packs are built as
            # dp per-replica chunks, and their attention runs under
            # shard_map with the same global→local block-id translation
            # paged_attention_decode performs — no pack ever reads the
            # pool across the batch axis.
        self.serve_replicas = dp
        self.seq_shards = sq
        # Quantized-weight serving (reference csrc/fp_quantizer + FP6 blog
        # 1.69-2.65x claim): big matmul kernels stored int8/fp8 with per-
        # output-channel scales; serving_mm applies the scale post-matmul so
        # weight HBM traffic halves and no bf16 copy is ever materialized.
        self.quantize_weights = quantize_weights
        if quantize_weights is not None:
            # Quantize BEFORE TP sharding: the AutoTP walk then shards the
            # compressed payloads (q/packed classify like their kernel —
            # same path and trailing dims; per-output-channel scales shard
            # with their column-parallel out dims).  FP6 row-parallel
            # kernels pack per K-chunk so the byte planes shard cleanly on
            # in-features (ServingQuantFP6.row_shards).  int8 TP serving is
            # the multi-chip 70B capacity combo (reference: FP6 + TP in
            # inference v2).
            from ..ops.quantizer import quantize_serving_params, tree_nbytes

            before = tree_nbytes(params)
            params = jax.jit(
                lambda p: quantize_serving_params(
                    p, quantize_weights, row_parallel_shards=tp
                )
            )(params)
            log_dist(
                f"quantized-weight serving ({quantize_weights}): params "
                f"{before / 2**20:.1f} MiB -> {tree_nbytes(params) / 2**20:.1f} MiB"
            )
        # ZeRO-Inference (reference docs/_posts/2022-09-10-zero-inference.md,
        # inference/config.py weight offload): weights live in host memory;
        # on TPU the jit streams them through HBM layer-by-layer, bounding
        # device memory to one layer's working set
        self._offload_weights = offload_weights
        self._offload_mode: Optional[str] = None
        # Tensor-parallel serving (reference inference/v2/engine_v2.py:93
        # _initialize_tp_group + model_implementations/sharding/): params go
        # into AutoTP shardings, the KV pool shards on kv heads, and the
        # paged-attention kernel runs per-shard under shard_map.  A 70B-class
        # model that trains under zero.Init serves the same way: sharded.
        self.grid = grid
        self._mesh = None
        if grid is not None and (tp > 1 or dp > 1 or sq > 1):
            if offload_weights:
                raise ValueError(
                    "offload_weights and tensor-parallel serving are "
                    "exclusive: ZeRO-Inference streams host-resident weights, "
                    "TP shards them in HBM — pick one capacity strategy"
                )
            if cfg.num_heads % tp != 0:
                raise ValueError(
                    f"num_heads {cfg.num_heads} must be divisible by the "
                    f"model axis ({tp}) for TP serving"
                )
            import jax.tree_util as jtu
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.auto_tp import infer_tp_rules
            from ..runtime.zero import match_rules, path_str

            self._mesh = grid.mesh
            # head-divisibility hints: attention kernels shard at HEAD
            # granularity only (GQA with hkv < tp replicates wk/wv,
            # matching the replicated KV pool the paged TP path uses there)
            rules = infer_tp_rules(
                params, tp, vocab_size=cfg.vocab_size,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            )
            self._param_shardings = jtu.tree_map_with_path(
                lambda kp, leaf: NamedSharding(
                    grid.mesh, match_rules(path_str(kp), tuple(leaf.shape), rules)
                ),
                params,
            )
            # from_hf streams the checkpoint straight into these shardings;
            # leaf-wise skip keeps that a no-op (a blanket device_put of an
            # already-sharded 70B tree would silently reshard any leaf where
            # the plan and the raw rule mapping ever diverge)
            params = jtu.tree_map(
                lambda x, sh: x if getattr(x, "sharding", None) == sh
                else jax.device_put(x, sh),
                params, self._param_shardings,
            )
        if offload_weights:
            params = self._to_host(params)
        self.params = params
        self.block_size = block_size
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        self.max_pages = -(-self.max_seq_len // block_size)
        # serving knobs (ServeScheduler reads these): ``enable_prefix_caching``
        # turns on refcounted block reuse across prompts sharing a prefix,
        # ``prefill_chunk`` bounds prompt tokens per scheduler tick (Dynamic
        # SplitFuse), ``kv_watermark`` is the pool fraction admission keeps
        # free so decode growth cannot deadlock against a full pool
        self.enable_prefix_caching = enable_prefix_caching
        self.prefill_chunk = prefill_chunk
        self.kv_watermark = kv_watermark
        # speculative decoding (prompt-lookup drafting, inference/
        # speculative.py): ``spec_max_draft`` candidate tokens per sequence
        # verify in ONE target forward, ``spec_min_match`` is the n-gram
        # that must recur in the sequence's own history to draft at all
        if enable_speculation and spec_max_draft < 1:
            raise ValueError("spec_max_draft must be >= 1 when speculating")
        if enable_speculation and spec_min_match < 1:
            raise ValueError("spec_min_match must be >= 1 when speculating")
        self.enable_speculation = enable_speculation
        self.spec_max_draft = spec_max_draft
        self.spec_min_match = spec_min_match
        self.spec_lookup_window = spec_lookup_window
        # fault-tolerant-serving knobs (config.ServeConfig or dict): request
        # deadlines, bounded retries, shed-mode thresholds — consumed by the
        # ServeScheduler this engine lazily builds
        from ..config.config import ServeConfig, _coerce

        self.serve = serve if isinstance(serve, ServeConfig) \
            else _coerce(ServeConfig, serve)
        # quantized-collective transport for the row-parallel TP psums
        # (comm/qcomm.py): ctor arg wins, else the serve config block.
        # 'none' keeps decode token-identical to pre-qcomm serving; the
        # typed qcomm format check rejects anything else loudly.
        from ..comm import qcomm as _qcomm

        self.quant_comm = (quant_comm if quant_comm is not None
                           else self.serve.quant_comm)
        _qcomm._check_fmt(self.quant_comm)
        self.comm_tiles = max(int(comm_tiles if comm_tiles is not None
                                  else self.serve.comm_tiles), 1)
        from ..ops.quantizer import ServingContext
        from ..parallel.topology import MODEL_AXIS

        self.serving_ctx = ServingContext(
            mesh=self._mesh if tp > 1 else None,
            axis=MODEL_AXIS,
            size=tp,
            kv_cols=(cfg.num_kv_heads % tp == 0),
            comm_fmt=self.quant_comm if tp > 1 else "none",
            comm_tiles=self.comm_tiles,
        )
        # chaos harness (inference/faults.py): a seeded FaultInjector whose
        # scoped points fire inside this engine's dispatch sites and the
        # allocator's growth path; None = every check compiles to a no-op
        self.faults = faults
        self.mgr = StateManager(num_blocks, block_size, max_seqs,
                                enable_prefix_caching=enable_prefix_caching,
                                replicas=dp, seq_shards=sq)
        self.mgr.faults = faults
        # a runner whose tables give pages back (``ragged.WindowCompaction``)
        self.mgr.compaction = getattr(self.runner, "compaction", None)
        # per-replica speculation totals [drafted, accepted] — the
        # spec-accept half of the serve/replicaN/* gauge group (drafts and
        # their accept-rate EMAs live on per-replica slots already; this
        # only aggregates them by owner replica for the telemetry surface)
        self._spec_by_replica = [[0, 0] for _ in range(dp)]
        self._scheduler = None
        # telemetry (telemetry/): ``stats`` is now a read-through view over
        # registry counters — same keys, same read semantics, and the
        # counters keep counting with telemetry disabled (the view is part
        # of the engine's correctness surface).  Histograms/spans/traces are
        # shared no-ops unless a ``telemetry`` config/True is passed.
        from ..telemetry import StatsView, Telemetry

        self.telemetry = Telemetry.ensure(telemetry)
        if self.telemetry.enabled:
            # serve-only processes have no train-engine atexit drain; this
            # writes a configured chrome_trace_path/jsonl_path at exit
            self.telemetry.register_exit_close()
        # a SECOND engine sharing one Telemetry gets "serve2/" etc. so its
        # stats view never aliases the first engine's counters.  The sched
        # namespace is claimed HERE, not at first scheduler access — lazy
        # claiming would pair serve2/ with sched/ if engine 2's scheduler
        # happened to be touched first.  All three namespaces are claimed
        # as ONE atomic group (shared suffix) — sequential claim_prefix
        # calls let two engines constructed concurrently on a shared
        # Telemetry interleave into serve2/sched3 (the mispairing
        # schedviz's namespace scenario replays deterministically)
        self._ns, self._sched_ns, self._comm_ns = \
            self.telemetry.claim_prefixes(("serve", "sched", "comm"))
        self._c = self.telemetry.counters(self._ns, (
            "prefill_tokens_dispatched",  # real prompt tokens run (not pad)
            "prefill_dispatches",
            "table_uploads",  # H2D copies of the block-table mirror
            "sampling_uploads",  # H2D copies of the per-slot sampling rows
            "dispatch_uploads",  # host arrays the dispatch bodies handed over
            # (the two above among them): one a decode tick, one a pack
            "decode_ticks",
            "decode_emitted",  # tokens emitted by plain decode dispatches
            "mixed_dispatches",  # packs that carried a step's live rows: ONE
            # program where a pack and a step went out as two (each also
            # counts as a prefill dispatch and as a decode tick)
            "decode_bursts",  # device-resident bursts (ONE host sync each)
            "burst_ticks",  # decode dispatches fused inside bursts
            "burst_emitted",  # tokens committed out of burst fetches
            "spec_ticks",  # verify dispatches (each scores k+1 positions)
            "spec_seq_forwards",  # sequence-participations in verify ticks
            "spec_drafted",  # draft tokens proposed
            "spec_accepted",  # draft tokens accepted
            "spec_emitted",  # tokens emitted by verify ticks (acc + 1 each)
            "spec_drafts_shed",  # draft sets dropped by _spec_tick's own
            # capacity pre-pass (direct put()/step(); scheduler sheds are
            # counted in its drafts_shed stat)
            # fault-tolerance transitions (incremented by the paired
            # ServeScheduler — registry counters are memoized by name, so
            # the scheduler's handles are these same objects):
            "failed",  # requests reaching FAILED (isolation / NaN sentinel)
            "timed_out",  # deadline expirations (TTFT or e2e)
            "cancelled",  # cancel(uid) calls that landed
            "retries",  # transient-dispatch retries (bounded backoff loop)
            "nan_failures",  # FAILED specifically via the -1 logits sentinel
            "isolation_probes",  # solo re-dispatches after a batch failure
            "shed_transitions",  # shed-mode flips (both directions)
            "shed_rejections",  # try_submit calls rejected RETRY_LATER
            "watchdog_trips",  # tick-duration watchdog firings
            # a tick dispatched one ahead (the dispatch bodies count the
            # first, the paired scheduler the second, the collects the third):
            "dispatched_ahead",  # programs enqueued while the execution
            # before them was not yet fetched
            "ahead_drains",  # ticks that collected first and ran in the
            # back-to-back order (the reason: the ``sched.drain`` span's)
            "ahead_rows_dropped",  # rows whose result was thrown away: their
            # request ended (a stop token, a failure) while they were enqueued
        ))
        self.stats = StatsView(self._c)
        reg = self.telemetry.registry
        self._h = {
            k: reg.histogram(f"{self._ns}/{k}")
            for k in ("prefill_pack_ms", "decode_tick_ms", "spec_tick_ms",
                      "collect_wait_ms")
        }
        # eagerly register this engine's request-latency group so the
        # namespace's histograms exist (empty) before any request arrives
        self.telemetry.request_hists(self._ns)
        # comm/* telemetry: wire-byte accounting for this engine's TP
        # collectives (analytic — payload bytes the transport puts on the
        # wire per dispatch, from qcomm.wire_bytes; 0 without a TP mesh).
        # ``tests/test_qcomm.py`` diffs these across passthrough/int8 twins.
        self._comm_c = self.telemetry.counters(self._comm_ns, (
            "bytes_on_wire",  # transport payload + scale bytes per device
            # format-INDEPENDENT wire GSPMD inserts around the sharded
            # embedding/head and residual stream (comm/budget.py overhead
            # group) — kept separate so the quant-comm A/B delta on
            # bytes_on_wire stays a pure transport comparison
            "bytes_on_wire_overhead",
            "collectives",  # row-parallel reduce count (tiles included)
        ))
        self.prefill_buckets = [b for b in prefill_buckets if b <= self.max_seq_len] or [self.max_seq_len]
        # SplitFuse-style token budget: multiple prompts share one prefill
        # dispatch as long as their total length fits the budget (clamped to
        # the largest bucket — a pack must fit one compiled dispatch)
        self.prefill_budget = min(
            prefill_budget or self.prefill_buckets[-1], self.prefill_buckets[-1]
        )
        runner = self.runner
        # A pack CARRIES THE TICK'S STEP where the runner's packs take the
        # step's rows and a program may queue (the scheduler then plans a pack
        # and a step together): both pack programs are built in that form, so
        # the set-up compiles what it always did, and a pack with no live slot
        # is the pack alone.  A serve mesh lays a pack out in replica chunks
        # and offloaded weights keep today's order: their packs stay as they are.
        self.packs_carry_step = runner.packs_carry_step and self.programs_may_queue
        self.kv = runner.init_cache(
            num_blocks, block_size, max_seqs, self.prefill_buckets[-1])
        self.mgr.release_hook = runner.released
        self._c.update(self.telemetry.counters(self._ns, runner.counters))
        self._kv_shardings = None
        if self._mesh is not None:
            from jax.sharding import NamedSharding

            kv_sh = NamedSharding(
                self._mesh, kv_pool_pspec(cfg.num_kv_heads, tp, dp, sq)
            )
            self._kv_shardings = (kv_sh, kv_sh)
            self.kv = jax.device_put(self.kv, self._kv_shardings)
        # The key LIVES ON THE DEVICE: every program takes it, splits it inside
        # and hands the carried key back; the host only keeps the reference.
        self._rng = jax.random.PRNGKey(seed)
        # ...and so does the chain (``packed_impl`` below): the newest token
        # of every slot, handed from program to program
        self._chain = jnp.zeros((max_seqs,), jnp.int32)
        self._burst_cap = 64  # step_n accumulator rows (doubles on demand)
        # host-side block-table mirror: rows update as pure numpy writes and
        # upload ONCE per tick — per-sequence device .at[].set calls cost one
        # dispatch each, which dominated decode latency.  Dirty tracking on
        # top: ticks where no sequence grew or swapped a page reuse the
        # cached device copy and skip the H2D transfer entirely.
        self._tables_np = np.full((max_seqs, self.max_pages), -1, np.int32)
        self._tables_dev = None
        self._tables_dirty = True
        # per-slot sampling rows (temperature, top_p) for the verify
        # dispatch, dirty-tracked like the block tables: steady-state ticks
        # where no sequence changed its sampling skip the H2D copy
        self._samp_np = np.full((max_seqs, 2), np.nan, np.float32)
        self._samp_dev = None
        # lazily-built paged-KV handoff dispatches (extract/inject_kv_blocks)
        self._kv_gather_jit = None
        self._kv_scatter_jit = None

        # params are explicit jit arguments — closing over them would inline
        # every weight into the HLO as a constant (huge programs, no donation)
        cfg_ = self.cfg
        # serving-matmul policy closure: TP mesh + fused-kernel gate for the
        # shard_map'd quant-matmul regions inside the compiled dispatches
        ctx_ = self.serving_ctx
        dp_ = self.serve_replicas
        sq_ = self.seq_shards
        mesh_ = self._mesh

        B_, mp_, bs_ = max_seqs, self.max_pages, block_size

        def sampled_pack(logits, rng, sampling_triple):
            """Sampling fused into the dispatch: the decode loop never makes a
            second device round trip per tick.  finite_guard folds NaN/inf
            detection into the same fetch: a poisoned row samples -1 and
            the host fails THAT request instead of trusting garbage.  The
            key is split HERE and carried on as a result: the host never
            holds, splits or uploads one."""
            t, k, p = sampling_triple
            rng, sub = jax.random.split(rng)
            sampled = sample(logits, SamplingParams(t, k, p), sub)
            return finite_guard(logits, sampled), rng

        # only the device-relevant sampling triple is static — hashing the
        # whole SamplingParams would recompile on max_new_tokens/stop_token
        # THE CHAIN: every program of a tick hands on one int32 ``[slots]``
        # array, the newest token each slot sampled (a pack writes the slots
        # whose prompt it completed, a step its live slots, every other slot
        # keeps what it held).  It IS the result the host fetches, and the
        # next step reads a slot's input token from it wherever the host has
        # not seen that token yet (a tick dispatched one ahead): like the
        # key, it goes from program to program and is uploaded by nobody.
        carries_ = self.packs_carry_step

        def step_inputs(rows, chain):
            """A step's input tokens and live mask from its ``[4, slots]``
            rows (``decode_impl``) and the chain it is handed."""
            return (jnp.where(rows[3] != 0, jnp.maximum(chain, 0), rows[0]),
                    rows[2] != 0)

        def pack_program(entry, pack, is_ctx, params, kv, rng, chain,
                         sampling_triple, **kw):
            """What the two pack programs share.  Where a pack carries the
            tick's step its buffer ends in the step's rows: they go through
            ``entry`` beside the pack's tokens (ONE stream of the weights) and
            the program then does what the pack's and the step's did in turn:
            the pack samples and writes the slots it completed into the chain,
            the step samples off the NEXT split and writes its live slots
            (disjoint from the pack's: the step reads its input tokens from the
            chain it was handed).  With no slot live the key is split once, as
            by the pack alone."""
            views = unpack_pack(pack, bs_, B_, mp_, is_ctx, carries_)
            if not carries_:
                logits, kv = entry(params, cfg_, *views, kv, ctx=ctx_,
                                   mesh=mesh_, **kw)
                sampled, rng = sampled_pack(logits, rng, sampling_triple)
                return jnp.where(views[4] >= 0, sampled, chain), kv, rng
            *views, rows = views
            tokens, active = step_inputs(rows, chain)
            (logits, step_logits), kv = entry(
                params, cfg_, *(views if is_ctx else views[:5]), kv, ctx=ctx_,
                mesh=mesh_, step=(tokens, rows[1], views[5], active), **kw)
            sampled, rng = sampled_pack(logits, rng, sampling_triple)
            chain = jnp.where(views[4] >= 0, sampled, chain)
            sampled, step_rng = sampled_pack(step_logits, rng, sampling_triple)
            return (jnp.where(active, sampled, chain), kv,
                    jnp.where(active.any(), step_rng, rng))

        def packed_impl(params, pack, kv, rng, chain, sampling_triple):
            """A cold pack: ``pack`` is the ONE int32 buffer of
            ``new_pack(..., ctx=False)``."""
            return pack_program(runner.prefill_packed, pack, False, params, kv,
                                rng, chain, sampling_triple)

        def packed_ctx_impl(params, pack, kv, rng, chain, sampling_triple):
            """Context-aware variant: suffix tokens attend over each
            sequence's cached KV pages (prefix-cache hits, chunked-prefill
            continuation chunks).  Cold packs stay on ``packed_impl``."""
            return pack_program(runner.prefill_packed_ctx, pack, True, params,
                                kv, rng, chain, sampling_triple, dp=dp_,
                                seq_shards=sq_)

        def cow_impl(kv, src, dst):
            """Copy-on-write page clone: dst pages get src's contents in
            every layer pool (donated, so the pool updates in place)."""
            ck, cv = kv
            ck = tuple(c.at[dst].set(c[src]) for c in ck)
            cv = tuple(c.at[dst].set(c[src]) for c in cv)
            return ck, cv

        def decode_sample(params, tokens, seq_lens, block_tables, active, kv,
                          rng, sampling_triple):
            """The forward and the guarded sample a decode tick and a burst
            tick share: (sampled, rng carried on, kv)."""
            logits, kv = runner.decode_step(
                params, cfg_, tokens, seq_lens, block_tables, active, kv,
                ctx=ctx_, mesh=mesh_, dp=dp_, seq_shards=sq_,
            )
            t, k, p = sampling_triple
            rng, sub = jax.random.split(rng)
            sampled = finite_guard(
                logits, sample(logits, SamplingParams(t, k, p), sub)
            )
            return sampled, rng, kv

        def decode_impl(params, rows, block_tables, kv, rng, chain,
                        sampling_triple):
            """One decode tick.  ``rows`` is the tick's ONE upload, int32
            ``[4, max_seqs]``: the slots' input tokens, their KV positions,
            0 / 1 for a live slot, and 0 / 1 for a slot whose input token is
            the chain's (the host enqueued this step before it fetched the
            program that sampled it); the key and the chain arrive from the
            last dispatch's results and are carried on to the next.  A
            ``-1`` read from the chain (the finite guard's sentinel) is
            clamped: that row's request has failed and its result is thrown
            away at collect."""
            tokens, active = step_inputs(rows, chain)
            sampled, rng, kv = decode_sample(
                params, tokens, rows[1], block_tables, active, kv, rng,
                sampling_triple)
            return jnp.where(active, sampled, chain), rng, kv

        def decode_burst_impl(params, tokens, seq_lens, block_tables, active,
                              kv, rng, burst, tick, emitted, stop_rows,
                              max_emit, sampling_triple):
            """decode_impl + ON-DEVICE burst accumulation AND termination:
            each tick writes its sampled row into the donated ``burst``
            buffer and updates the per-slot ``active`` mask IN the graph —
            a row hitting its stop token, its emission cap, or the
            finite_guard sentinel deactivates immediately, so later ticks
            neither sample it nor write its KV (early-exit masking: the
            mask gates ``write_decode_kv`` inside ``decode_step``).  The
            single end-of-burst fetch therefore yields exactly the tokens
            per-tick ``step()`` would have — no decode-past-stop.

            Carries: ``active`` [B] bool (monotone-decreasing), ``emitted``
            [B] int32 token counts (mirrored into ``burst`` row 0 so ONE
            fetch returns counts + tokens), ``stop_rows`` [B] int32 per-slot
            stop ids (-1 = none; NOT a static arg — per-request stop tokens
            must not recompile), ``max_emit`` [B] int32 per-slot emission
            caps (remaining budget AND max_seq_len headroom).  ``burst`` is
            [cap+1, B]: row 0 = counts, row 1+t = tick t's emissions
            (``_BURST_PAD`` where the row was already inactive; the -1
            poison sentinel can only ever be a row's LAST emission).  The
            host keeps references ONLY to the latest outputs, so earlier
            ticks' token arrays free as soon as their consumer ran."""
            sampled, rng, kv = decode_sample(
                params, tokens, seq_lens, block_tables, active, kv, rng,
                sampling_triple)
            act_i = active.astype(jnp.int32)
            emit = jnp.where(active, sampled, jnp.int32(_BURST_PAD))
            burst = jax.lax.dynamic_update_index_in_dim(
                burst, emit, tick + 1, axis=0
            )
            emitted = emitted + act_i
            burst = burst.at[0].set(emitted)
            # termination checks AFTER this tick's emission: the stop token
            # itself is emitted (step() appends it before finishing), the
            # poison sentinel is emitted (the host commits the healthy
            # prefix and quarantines), and a row emits exactly max_emit
            poisoned = sampled < 0
            hit_stop = (stop_rows >= 0) & (sampled == stop_rows)
            active = active & ~poisoned & ~hit_stop & (emitted < max_emit)
            # lengths advance only for rows that emitted this tick — a
            # finished row's seq_lens freezes, so its attention window and
            # block-table reads never run past its reserved pages
            seq_lens = seq_lens + act_i
            # next tick's input token (clamped: the -1 sentinel must not
            # index the embedding; the row is inactive anyway)
            tokens = jnp.where(active, jnp.maximum(sampled, 0), tokens)
            return (tokens, seq_lens, rng, kv, burst, tick + 1, active,
                    emitted)

        def spec_impl(params, tokens, seg, pos, dst_pages, dst_offs,
                      ctx_tables, ctx_lens, draft, n_draft, samp_rows, kv,
                      rng, top_k, all_greedy):
            """One speculative verify tick: score every slot's
            [last committed token | draft prefix] in a single forward, then
            accept/resample on device (sampling.spec_verify_sample).  The
            KV pool is donated — draft KV lands in place; rejected tails
            are rolled back host-side by the allocator's truncate path."""
            from .sampling import spec_verify_sample

            logits, kv = runner.verify_packed_ctx(
                params, cfg_, tokens, seg, pos, dst_pages, dst_offs,
                ctx_tables, ctx_lens, kv, ctx=ctx_, mesh=mesh_, dp=dp_,
                seq_shards=sq_,
            )
            k1 = draft.shape[1] + 1
            logits = logits.reshape(draft.shape[0], k1, -1)
            rng, sub = jax.random.split(rng)
            out, n_out = spec_verify_sample(
                logits, draft, n_draft, samp_rows[:, 0], samp_rows[:, 1],
                top_k, sub, all_greedy=all_greedy,
            )
            # one non-finite logit anywhere in a row's k+1 verify positions
            # poisons the whole row (-1 sentinel): accepting drafts scored
            # by a garbage forward is not partially trustworthy
            return finite_guard(logits, out), n_out, kv, rng

        # The six programs, built ONCE from one table: (attribute, impl, its
        # jax.jit options, the KV pool's place among n results, and among the
        # arguments after ``params``: None for a program that takes no
        # weights).  stop_rows / max_emit of the burst are NOT donated: the
        # same device arrays feed every tick.  The key and the chain are
        # donated by none: a dispatch that raises must leave them alive, and
        # the chain is also what the host fetches.
        table = (
            ("_packed_prefill_jit", packed_impl,
             dict(donate_argnums=(2,), static_argnums=(5,)), 1, 3, 1),
            ("_packed_prefill_ctx_jit", packed_ctx_impl,
             dict(donate_argnums=(2,), static_argnums=(5,)), 1, 3, 1),
            ("_cow_jit", cow_impl, dict(donate_argnums=(0,)), 0, 1, None),
            ("_decode_jit", decode_impl,
             dict(donate_argnums=(3,), static_argnums=(6,)), 2, 3, 2),
            ("_decode_burst_jit", decode_burst_impl,
             dict(donate_argnums=(2, 4, 5, 7, 8, 9), static_argnums=(12,)),
             3, 8, 4),
            ("_spec_jit", spec_impl,
             dict(donate_argnums=(11,), static_argnums=(13, 14)), 2, 4, 10),
        )
        if self._mesh is not None:
            # pin the result shardings so the KV pool STAYS sharded across
            # ticks (donation then reuses the buffers in place) and sampled
            # tokens come back replicated for the host loop
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self._mesh, P())
            # per-tick inputs (a tick's rows, the key, the burst's donated
            # buffers) must be COMMITTED to the replicated sharding the pinned
            # outputs carry: left uncommitted, GSPMD may choose a
            # batch-sharded input layout (it propagates the 2-D mesh
            # attention specs) and the donor/output aliasing then fails on
            # the size mismatch; the key would also compile every program
            # twice (its first call uncommitted, every later one not)
            self._rep_sharding = rep
            self._rng = jax.device_put(self._rng, rep)
            self._chain = jax.device_put(self._chain, rep)
        for name, impl, opts, kv_out, n_out, kv_rest_idx in table:
            if self._mesh is not None:
                outs = (rep,) * kv_out + (self._kv_shardings,) \
                    + (rep,) * (n_out - kv_out - 1)
                opts["out_shardings"] = outs if n_out > 1 else outs[0]
            jitted = jax.jit(impl, **opts)
            if kv_rest_idx is not None:
                # (no weights are host-resident under a mesh: a no-op there)
                jitted = self._wrap_offload(jitted, kv_rest_idx)
            setattr(self, name, jitted)

        self._tracked = {}
        if runner.scoped_programs:
            # the device trace is attributed to these programs' named scopes
            # (telemetry.program_scopes): keep the shapes they compiled for
            from ..telemetry import programs

            self._tracked = {
                name: programs.track(getattr(self, name))
                for name in ("_packed_prefill_ctx_jit", "_decode_jit")}

        def _cow(src: int, dst: int) -> None:
            self.kv = self._cow_jit(self.kv, jnp.int32(src), jnp.int32(dst))

        self.mgr.cow_hook = _cow

    # -- ZeRO-Inference helpers ---------------------------------------------
    @staticmethod
    def _to_host(params):
        import jax as _jax

        try:
            sharding = _jax.sharding.SingleDeviceSharding(
                _jax.devices()[0], memory_kind="pinned_host"
            )
            return _jax.device_put(params, sharding)
        except Exception:
            return params  # backend has no host memory space

    def _wrap_offload(self, jitted, kv_rest_idx: int):
        """With offload_weights: feed host-resident params straight into jit
        (XLA streams them); backends that reject host operands fall back to
        staging a transient device copy per dispatch (same capability-probe
        pattern as the training engine's _wrap_offload_step).

        ``kv_rest_idx``: position of the donated KV pool within ``rest``.
        While host-operand support is still unknown, the KV arg is defensively
        copied before the host-mode attempt — the jit donates it, and a
        rejection that surfaces at execution time (after donation) would
        otherwise leave the staged retry dereferencing a deleted buffer."""
        if not self._offload_weights:
            return jitted

        def call(params, *rest):
            if self._offload_mode in (None, "host"):
                probing = self._offload_mode is None
                if probing:
                    rest = list(rest)
                    kv_live = rest[kv_rest_idx]
                    rest[kv_rest_idx] = jax.tree_util.tree_map(
                        jnp.copy, kv_live
                    )
                try:
                    out = jitted(params, *rest)
                    self._offload_mode = "host"
                    return out
                except Exception as e:
                    msg = str(e).lower()
                    if not probing or not any(
                        k in msg for k in ("memory kind", "memory_kind",
                                           "pinned_host", "memory space",
                                           "memory_space", "host memory")
                    ):
                        raise
                    log_dist(
                        "zero-inference: host-memory jit unsupported here; "
                        "staging weights per dispatch"
                    )
                    self._offload_mode = "staged"
                    rest[kv_rest_idx] = kv_live  # copy may be donated; restore
            # cross-memory-kind device_put is rejected on some backends:
            # stage through host RAM (the weights are host-resident anyway)
            dev = jax.tree_util.tree_map(
                lambda x: jnp.asarray(np.asarray(x)), params
            )
            return jitted(dev, *rest)

        return call

    # -- scheduling queries (reference engine_v2.py:158/:184) --------------
    def query(self, uid: int) -> Tuple[int, int]:
        """(max admissible new tokens, allocatable blocks) — admission info.
        Counts evictable cached blocks: the prefix cache retires pages to an
        LRU instead of the free list, and allocation reclaims them.

        Under ``serve_replicas > 1`` a request lives entirely inside ONE
        replica's block range, so this reports the BEST single replica's
        availability — the aggregate view would advertise capacity no
        single request can actually use (the same replica-unaware
        arithmetic admission itself no longer does)."""
        free = max(a.available_blocks for a in self.mgr.allocators)
        return free * self.block_size, free

    @classmethod
    def from_hf(cls, model_dir: str, dtype=None, **kw) -> "InferenceEngineV2":
        """Build from an HF safetensors checkpoint directory — the analogue
        of the reference's ``build_hf_engine`` (inference/v2/engine_factory.py:69).

        With ``grid=`` (model axis > 1) the checkpoint is streamed
        shard-by-shard straight into its TP shardings, so a 70B-class model
        never materializes unsharded on any host or device — the serving
        counterpart of zero.Init's sharded construction."""
        grid = kw.get("grid")
        if grid is not None and grid.spec.model > 1:
            import functools
            import json
            import os

            from ..checkpoint.hf_import import (
                _LazyStore,
                config_from_hf,
                load_hf_checkpoint_sharded,
            )
            from ..config.config import ZeroConfig
            from ..models.transformer import init_params
            from ..parallel.auto_tp import infer_tp_rules
            from ..runtime.zero import plan_sharding

            with open(os.path.join(model_dir, "config.json")) as fh:
                cfg = config_from_hf(json.load(fh))
            if dtype is not None:
                cfg = cfg.replace(dtype=dtype)
            # same tie fallback the loader applies — pre-checked here (with a
            # shared store, scanned once) so the plan's shapes match the tree
            store = _LazyStore(model_dir)
            if not cfg.tie_embeddings and "lm_head.weight" not in store:
                cfg = cfg.replace(tie_embeddings=True)
            shapes = jax.eval_shape(
                functools.partial(init_params, cfg=cfg, dtype=cfg.dtype),
                jax.random.PRNGKey(0),
            )
            rules = infer_tp_rules(
                shapes, grid.spec.model, vocab_size=cfg.vocab_size,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            )
            plan = plan_sharding(shapes, ZeroConfig(stage=0), grid.spec, tp_rules=rules)
            params, cfg = load_hf_checkpoint_sharded(
                model_dir, plan, grid.mesh, cfg=cfg, dtype=cfg.dtype, store=store
            )
            return cls(params, cfg, **kw)

        from ..checkpoint.hf_import import load_hf_checkpoint

        params, cfg = load_hf_checkpoint(model_dir)
        if dtype is not None:
            cfg = cfg.replace(dtype=dtype)
        # serve in the compute dtype (cfg.dtype defaults to bf16, matching
        # the KV cache) — the reference's build_hf_engine casts the same way
        params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, cfg.dtype), params
        )
        return cls(params, cfg, **kw)

    def can_schedule(self, prompt_lens: Sequence[int],
                     token_lists=None) -> bool:
        # replica-aware: aggregate block counts would accept a batch that
        # fits the SUM of the per-replica pools but no single replica —
        # the simulation mirrors admit's sequential placement exactly.
        # ``token_lists`` (optional) lets the simulation credit prefix-
        # cached blocks the way admit(match_prefix=True) actually will.
        return self.mgr.can_admit_all(prompt_lens, token_lists=token_lists)

    # -- serving API -------------------------------------------------------
    def put(
        self,
        uids: Sequence[int],
        token_lists: Sequence[Sequence[int]],
        sampling: SamplingParams = SamplingParams(),
    ) -> Dict[int, int]:
        """Admit new sequences and prefill them, returning {uid: first_token}.

        Prompts are packed into shared dispatches under ``prefill_budget``
        tokens (SplitFuse-style; reference ragged_wrapper atoms) — N short
        prompts cost one forward pass, not N.

        Compat wrapper: this is the all-or-nothing admission path and raises
        ``RuntimeError`` when KV blocks or slots run out.  Load that may
        exceed capacity belongs on ``self.scheduler`` (``submit()`` queues
        instead of throwing, chunks long prompts, preempts under pressure).
        With ``enable_prefix_caching`` the admit matches cached prefix
        blocks and only the suffix is dispatched."""
        token_lists = [list(map(int, toks)) for toks in token_lists]
        # validate the WHOLE request before admitting anything: a mid-loop
        # failure must not leave earlier prompts admitted with never-written
        # KV pages
        for uid, toks in zip(uids, token_lists):
            if len(toks) > self.prefill_buckets[-1]:
                raise ValueError(
                    f"prompt length {len(toks)} exceeds max bucket "
                    f"{self.prefill_buckets[-1]}"
                )
        if not self.can_schedule([len(t) for t in token_lists],
                                 token_lists=token_lists):
            raise RuntimeError(
                f"cannot admit {len(token_lists)} sequences "
                f"({sum(len(t) for t in token_lists)} tokens): "
                "out of KV blocks/slots"
            )
        entries = []
        admitted: List[int] = []
        snap = self.mgr.hit_stats_snapshot()
        try:
            for uid, toks in zip(uids, token_lists):
                seq = self.mgr.admit(uid, toks)
                admitted.append(uid)
                self.mgr.ensure_capacity(seq, 0)
                entries.append((seq, seq.seen_tokens, len(seq.tokens)))
        except RuntimeError:
            # keep the all-or-nothing contract even if a replica's pool
            # defeats the pre-check (e.g. racing chaos injection): nothing
            # stays admitted with never-written KV pages
            for u in admitted:
                self.mgr.release(u)
            self.mgr.hit_stats_restore(snap)
            raise
        return self.prefill_entries(entries, sampling)

    def prefill_entries(self, entries, sampling: SamplingParams) -> Dict[int, int]:
        """Prefill ``entries`` = [(seq, start, end)] token ranges, splitting
        into packs under ``prefill_budget``; returns {uid: first_token} for
        every entry whose range completes its prompt (``end == len(tokens)``
        — mid-prompt chunks write KV but sample nothing).  ``start`` must be
        page-aligned: it is either a prefix-cache hit length or a prior
        chunk boundary, both block-granular by construction.

        Under ``serve_replicas > 1`` a pack is ``dp`` per-replica CHUNKS
        (``_run_packed_prefill`` lays them out), so the budget is accounted
        per replica at ``prefill_budget // dp`` tokens per chunk — the
        whole dispatch then stays at the budget's compute size, and ctx
        packs stay replica-local by construction.  An entry that overflows
        ITS replica's chunk defers to the next pack alone (other replicas'
        accumulating chunks are not flushed with it — each sequence
        appears at most once per call, so deferral cannot reorder a
        sequence's own chunks)."""
        out: Dict[int, int] = {}
        for pack in self._packs_of(entries):
            self._run_packed_prefill(pack, sampling, out)
        return out

    def prefill_dispatch(self, entries, sampling: SamplingParams,
                         ahead: bool = False, step=()) -> List["Enqueued"]:
        """``prefill_entries``' packs ENQUEUED and nothing fetched: one
        handle a pack, for ``pack_collect``, in dispatch order.  ``step``:
        the sequences of the tick's decode step, which the LAST pack carries
        (``packs_carry_step``): the key is then split pack by pack and by the
        step last, as by the programs in turn."""
        packs = list(self._packs_of(entries))
        return [self.pack_dispatch(pack, sampling, split=True, ahead=ahead,
                                   step=step if pack is packs[-1] else ())
                for pack in packs]

    def _packs_of(self, entries):
        """``entries`` cut into packs under ``prefill_budget`` (a generator:
        a pack is laid out once the one before it has been handed on)."""
        bs = self.block_size
        dp = self.serve_replicas
        per_budget = self.mgr.per_replica_token_budget(self.prefill_budget)
        # (a compacted table's window: a pack's page attends ONE window)
        edge = self.mgr.compaction.window if self.mgr.compaction is not None else 0
        for seq, start, _end in entries:
            if start % bs:
                raise ValueError(
                    f"prefill start {start} not page-aligned (bs {bs})"
                )
            if edge and _end > start and start // edge != (_end - 1) // edge:
                raise ValueError(
                    f"prefill chunk [{start}, {_end}) crosses a window's edge "
                    f"(every {edge} positions): a pack's page attends ONE window; "
                    f"the scheduler cuts chunks there"
                )
        pending: List = list(entries)
        while pending:
            pack: List = []
            pack_len = [0] * dp
            deferred: List = []
            for entry in pending:
                seq, start, end = entry
                n = -(-(end - start) // bs) * bs
                r = self.mgr.replica_of(seq) if dp > 1 else 0
                # an oversized single entry (> per_budget) rides an empty
                # chunk — pack_dispatch buckets the pack up to fit
                if pack_len[r] and pack_len[r] + n > per_budget:
                    deferred.append(entry)
                    continue
                pack.append(entry)
                pack_len[r] += n
            yield pack
            pending = deferred

    def _run_packed_prefill(self, entries, sampling, out: Dict[int, int]) -> None:
        """One packed-prefill dispatch for ``entries`` = [(seq, start, end)],
        fetched and emitted at once: ``pack_dispatch`` and ``pack_collect``
        back to back."""
        self.pack_collect(self.pack_dispatch(entries, sampling), out)

    def pack_dispatch(self, entries, sampling, split: bool = False,
                      ahead: bool = False, step=()) -> "Enqueued":
        """Build, upload and ENQUEUE one packed prefill for ``entries`` =
        [(seq, start, end)]; ``pack_collect`` fetches and emits it.  With
        ``split`` the span closes at the enqueue and the fetch is the
        collect's (``ahead``: the execution before this one is not fetched
        yet; the span says so); without, the fetch lies inside the span as it
        always did.

        ``step`` (``packs_carry_step`` only): the sequences of the tick's
        decode step, none of them in ``entries``.  Their rows ride the pack's
        buffer (``decode_dispatch``'s four, and their block tables in the rows
        a context pack keeps free for them) and its ONE program: still one
        upload, one ``prefill_pack`` span, one handle, one fetch, and a
        dispatch counted as a pack AND as a tick (``mixed_dispatches``).

        Each suffix starts at a PAGE boundary of the pack buffer (segment-0
        gap padding between prompts): KV then writes as one page-granular
        scatter per layer instead of a per-token scatter, which the TPU
        serializes (~100 ms/2048-token pack measured).  Cold packs (all
        starts 0) take the flash-kernel fast path; any non-zero start
        switches the pack to the context-aware dispatch that attends over
        cached pages.

        Layout: the pack is ``serve_replicas`` equal chunks of one bucketed
        size — replica ``r``'s entries fill [r*C, (r+1)*C) — and every row
        group (segment ids, ctx tables/lens, last_idx, sampled logits) is
        indexed by SLOT.  Slots and blocks partition contiguously per
        replica, so a ctx pack's shard_map region resolves its chunk
        entirely inside its local pool slice (paged.py translates the ids).
        At ``serve_replicas == 1`` this degenerates to the classic single-
        chunk layout byte-for-byte (one chunk, same bucket)."""
        self._maybe_fault("runner_exception", [s.uid for s, _, _ in entries])
        tel, ns = self.telemetry, self._ns
        with tel.span("engine.pack_build", track=ns) as bsp:
            bs = self.block_size
            dp = self.serve_replicas
            compaction = self.mgr.compaction
            groups: List[List] = [[] for _ in range(dp)]
            for e in entries:
                groups[self.mgr.replica_of(e[0]) if dp > 1 else 0].append(e)
            chunk_tokens = max(
                sum(-(-(end - start) // bs) * bs for _, start, end in g)
                for g in groups
            )
            C = _bucket(max(chunk_tokens, bs), self.prefill_buckets)
            if C % bs:
                raise ValueError(
                    f"prefill bucket {C} must be a multiple of block_size {bs}"
                )
            t_pad = C * dp
            use_ctx = any(start > 0 for _, start, _ in entries) \
                or self.runner.packs_are_one_program
            carries = self.packs_carry_step
            if step and (not carries or {id(s) for s in step}
                         & {id(e[0]) for e in entries}):
                raise ValueError("a pack carries a step only where "
                                 "packs_carry_step, and no sequence twice")
            # the pack's ONE buffer, filled through its views
            pack, views = new_pack(t_pad, bs, self.mgr.max_seqs,
                                   self.max_pages, use_ctx, carries)
            tokens, seg, pos, pack_pages, last_idx = views[:5]
            ctx_tables = views[5] if use_ctx or carries else None
            ctx_lens = views[6] if use_ctx else None
            ctx_pages = 0  # live context pages the ctx kernel walks: its time over this
            for r, group in enumerate(groups):
                cur = r * C
                for s, start, end in group:
                    n = end - start
                    tokens[cur : cur + n] = s.tokens[start:end]
                    seg[cur : cur + n] = s.slot + 1
                    pos[cur : cur + n] = np.arange(start, end)
                    n_pages = -(-n // bs)
                    first_page = start // bs
                    if compaction is not None:  # the column of the chunk's first page
                        first_page = compaction.column(start, bs)
                    pack_pages[cur // bs : cur // bs + n_pages] = np.asarray(
                        s.blocks[first_page : first_page + n_pages]
                    )
                    if end == len(s.tokens):  # completes the prompt -> sample
                        last_idx[s.slot] = cur + n - 1
                    if use_ctx:
                        ctx_tables[s.slot, : len(s.blocks)] = s.blocks
                        ctx_lens[s.slot] = start
                        ctx_pages += first_page
                    cur += n_pages * bs  # next prompt starts page-aligned
            step_ctx = self._fill_step(views[-1], step) if step else 0
            for s in step:  # (its row of the mirror is current: ``_fill_step``)
                ctx_tables[s.slot] = self._tables_np[s.slot]
            bsp.mark("rows")  # what is left of the span: the runner's counts
            triple = (sampling.temperature, sampling.top_k, sampling.top_p)
            n_real = sum(end - start for _, start, end in entries)
            n_slots = self.mgr.max_seqs  # logits rows a pack dispatch scores
            extra = self.runner.dispatched(
                self._c, ((s.slot, start, end) for s, start, end in entries), pack=True,
                tokens=t_pad, carried=n_slots if carries else 0)
            if carries:
                extra.update(step_rows=len(step), ctx_tokens=step_ctx)
            if step:
                # the step's rows as ``decode_dispatch`` counts them (the runner's
                # host mirrors follow the rows; the program's rows are counted
                # above, once), their span arguments apart from the pack's own
                rode = self.runner.dispatched(
                    self._c, ((s.slot, s.cur_len - 1, s.cur_len) for s in step))
                extra.update({f"step_{k}": v for k, v in rode.items()})
        finishing = [s for s, _, end in entries if end == len(s.tokens)]
        with tel.span(
            "prefill_pack", track=ns, hist=self._h["prefill_pack_ms"],
            tokens=n_real, ctx_pages=ctx_pages,
            uids=[s.uid for s, _, _ in entries], ahead=int(ahead), **extra,
        ) as sp:
            args = (self.params, self._upload(pack, held=False), self.kv,
                    self._rng, self._chain, triple)
            sp.mark("upload")  # the one argument handed over; next: enqueue
            name = "_packed_prefill_ctx_jit" if use_ctx else "_packed_prefill_jit"
            sampled, self.kv, self._rng = getattr(self, name)(*args)
            self._chain = sampled
            if name in self._tracked:
                self._tracked[name].note(args)
            sp.dispatched()
            self._c["prefill_tokens_dispatched"].inc(n_real)
            self._c["prefill_dispatches"].inc()
            self._c["dispatched_ahead"].inc(int(ahead))
            if step:
                self._c["mixed_dispatches"].inc()
                self._c["decode_ticks"].inc()
                self._c["decode_emitted"].inc(len(step))
            self._account_comm(t_pad, sample_rows=n_slots, ring=use_ctx)
            if compaction is not None:
                # (a step's row that fills its window gives the pages back as a tick's)
                self._close_windows([(s, end) for s, _, end in entries]
                                    + [(s, s.cur_len) for s in step])
            done = Enqueued("prefill_pack", list(entries), finishing, split, sp, step)
            fetched = finishing or step  # (intermediate chunks alone: nothing is)
            if fetched and not split:
                # host-complete: this fetch syncs the pack
                done.tokens = np.asarray(sampled)
            else:
                # nothing is fetched HERE (intermediate chunks only: nothing
                # ever is), so on an async backend the pack is still in
                # flight when the span closes.  It is exported unsynced; the
                # pack's device time is the trace's (one ``XLA Modules``
                # event per execution)
                sp.end(sync_obj=sampled)
                if fetched:
                    # the device -> host copy starts HERE, behind the program:
                    # the collect waits for the program, not for a transfer
                    # started late
                    sampled.copy_to_host_async()
                    done.sampled = sampled
        if split:
            # what the plan of the NEXT execution reads before this one is
            # collected: the chunk's KV is written (in device order), its
            # full blocks hold prompt tokens the host knows, and a prompt
            # completed here has one token on the way
            for s, _start, end in entries:
                s.seen_tokens = end
                self.mgr.update_hashes(s)
            for s in finishing:
                s.pending += 1
        for s in step:
            s.pending += 1  # (as ``decode_dispatch``'s rows)
        return done

    def _fetched(self, done: "Enqueued"):
        """The tokens of ``done``, waiting for its program where they were
        not fetched inside its dispatch span (``tick_collect``: the wait and
        the fetch, named after what it collects, ``of`` the dispatch span
        that enqueued it; its ``ready`` mark is where the runtime called the
        program's result defined, what follows is the copy landing and this
        thread taking it)."""
        if done.sampled is not None:
            of = {} if done.by.id is None else {"of": done.by.id}
            with self.telemetry.span("tick_collect", track=self._ns,
                                     hist=self._h["collect_wait_ms"],
                                     what=done.span, **of) as sp:
                # the tick's ONE wait, split from its fetch by the mark
                done.sampled.block_until_ready()  # lint: allow(host-sync)
                ready = sp.mark("ready")
                done.tokens = np.asarray(done.sampled)
            done.sampled = None
            if (sp.duration_ms or 0.0) > 1e3 * STALL_LOG_S:
                log_dist(
                    f"tick_collect of {done.span} #{done.by.id}: "
                    f"{sp.duration_ms:.1f} ms, ready after "
                    f"{(ready - sp.t0) * 1e3:.1f} ms",
                    ranks=[-1], level=logging.WARNING)
        return done.tokens

    def _dropped(self, s, done: "Enqueued", dead) -> bool:
        """Whether ``s``'s row of ``done`` is dead: its request ended while
        the program was enqueued (the scheduler names it in ``dead``), or the
        sequence is released already.  Nothing of a dead row is appended,
        hashed or returned."""
        if not done.split or (
                s.uid not in dead and self.mgr.seqs.get(s.uid) is s):
            return False
        self._c["ahead_rows_dropped"].inc()
        return True

    def pack_collect(self, done: "Enqueued", out: Dict[int, int],
                     dead=()) -> Dict[int, int]:
        """Fetch and emit a pack ``pack_dispatch`` enqueued: first tokens
        into ``out`` ({uid: token}, -1 for a row the finite guard failed).
        Returns what the step it carried sampled, as ``decode_collect`` does
        (booked inside the pack's emit: one booking a dispatch); {} for a
        pack alone."""
        tel, ns = self.telemetry, self._ns
        entries, finishing = done.rows, done.finishing
        next_tokens = self._fetched(done)
        with tel.span("engine.pack_emit", track=ns):
            poison = self._poisoned([s.uid for s in finishing])
            completing = {id(s) for s in finishing}
            for s, start, end in entries:
                completes = id(s) in completing
                if completes and done.split:
                    s.pending -= 1
                if self._dropped(s, done, dead):
                    continue
                s.seen_tokens = max(s.seen_tokens, end)
                if completes:
                    tok = int(next_tokens[s.slot])
                    if s.uid in poison:
                        tok = -1
                    if tok < 0:
                        # finite_guard sentinel: the row's logits were non-finite.
                        # No token is committed; the -1 in ``out`` tells the
                        # scheduler to fail THIS request (others keep theirs).
                        # Every key the sequence itself published — including
                        # ones from EARLIER chunks of this prompt, whose KV the
                        # same poisoned forward chain wrote — is retracted so
                        # suspect pages stop serving prefix-cache hits.
                        s.error = "non-finite logits in prefill"
                        self.mgr.quarantine_written(s)
                        out[s.uid] = -1
                        continue
                    s.tokens.append(tok)
                    self._set_block_table(s)
                    out[s.uid] = tok
                self.mgr.update_hashes(s)
            if not done.step:
                return {}
            return self._emit_step(done, done.step, next_tokens, dead)

    def _close_windows(self, written) -> None:
        """``written``: (sequence, positions written so far) of the execution
        just ENQUEUED.  A sequence whose window that execution filled has the
        window's exact pages taken out of its table and given back to the pool
        now: the execution is the last to read them, and whatever is handed
        them next is enqueued after it."""
        window = self.mgr.compaction.window
        for s, n in written:
            if n and n % window == 0:
                self._c["eva_pages_returned"].inc(self.mgr.close_window(s, n))
                self._c["eva_windows_closed"].inc()
                self._set_block_table(s)

    def refresh_routing_stats(self) -> None:
        """Fetch the runner's device-side counts (a ``cfg.latent`` model's
        selectors and routers) into ``stats``: small device->host copies;
        call it outside a timed window's hot loop."""
        if self.kv is not None:
            self.runner.refresh_stats(self._c, self.kv)

    def _set_block_table(self, seq) -> None:
        row = self._tables_np[seq.slot]
        new = np.full(self.max_pages, -1, np.int32)
        new[: len(seq.blocks)] = seq.blocks
        if not np.array_equal(row, new):
            row[:] = new
            self._tables_dirty = True

    def _tables_device(self):
        """Device copy of the block-table mirror, re-uploaded only on ticks
        where some sequence grew or swapped a page (dirty tracking) — the
        [max_seqs, max_blocks] H2D copy every tick was pure waste on
        steady-state decode.  Safe to cache: no decode jit donates the
        tables argument, and the transfer is handed a COPY (the numpy mirror
        mutates in place, and a transfer may read its source late)."""
        if self._tables_dirty or self._tables_dev is None:
            self._tables_dev = self._upload(self._tables_np.copy())
            self._tables_dirty = False
            self._c["table_uploads"].inc()
        return self._tables_dev

    def _sampling_device(self, active_seqs, sampling: SamplingParams):
        """Device copy of the per-slot (temperature, top_p) rows, re-uploaded
        only when some active sequence's values changed — the sampling-params
        analogue of the dirty-tracked block tables (steady-state serving has
        one sampling config for the whole run, so the [max_seqs, 2] H2D copy
        per tick was pure waste).  Inactive slots keep their last rows (they
        are masked out of every dispatch that reads this)."""
        dirty = False
        for s in active_seqs:
            row = self._samp_np[s.slot]
            # rows init to NaN, so a slot's first touch (or reuse by a new
            # sequence) always compares unequal and re-uploads
            if row[0] != sampling.temperature or row[1] != sampling.top_p:
                row[0] = sampling.temperature
                row[1] = sampling.top_p
                dirty = True
        if dirty or self._samp_dev is None:
            self._samp_dev = self._upload(self._samp_np.copy())
            self._c["sampling_uploads"].inc()
        return self._samp_dev

    @property
    def programs_may_queue(self) -> bool:
        """Whether a program may be enqueued while the one before it is not
        fetched yet (a scheduler's tick dispatched one ahead).  Over a serve
        mesh and with offloaded weights it is untried (their commit and
        donation rules were written for one program at a time): there the
        scheduler keeps dispatch and fetch back to back."""
        return self._mesh is None and not self._offload_weights

    def _upload(self, x: np.ndarray, held: bool = True):
        """Hand ONE host array to the device.  Every host array of a dispatch
        body goes through here, so ``dispatch_uploads`` counts them: each
        costs an allocation and a transfer of its own whatever its size
        (0.24 ms of host for 192 or 9096 integers: my chip run, PR 40).
        Over a serve mesh the array is committed replicated (the programs'
        pinned results are, and a donated input must be committed to its
        result's layout).  ``held=False``: the array feeds ONE call and is
        kept by nobody, so without a mesh the jitted call takes the numpy
        array itself (its own transfer costs 0.10 ms where a transfer ahead
        of the call costs 0.25: same run)."""
        self._c["dispatch_uploads"].inc()
        if self._mesh is not None:
            return jax.device_put(x, self._rep_sharding)
        return jax.device_put(x) if held else x

    def _account_comm(self, n_tokens: int, reps: int = 1,
                      sample_rows: Optional[int] = None,
                      ring: bool = True) -> None:
        """Wire-byte accounting for ONE dispatch's TP collectives into the
        ``comm/*`` counters, from the shared :mod:`comm.budget` plan (the
        same enumeration the Graft Auditor checks against the compiled
        HLO, so this accounting cannot silently drift from what XLA
        emits).  ``bytes_on_wire`` counts the row-parallel transports at
        this engine's format (``tests/test_qcomm.py`` diffs it across
        passthrough/int8 twins); ``bytes_on_wire_overhead`` counts the
        format-independent GSPMD wire (embedding combine, block-input and
        head-input gathers).  ``reps``: identical dispatches to account at
        once (a step_n burst is ``n`` decode ticks); ``sample_rows``:
        rows the dispatch scores logits for (defaults to ``n_tokens`` —
        packed prefill passes its slot count).  ``ring``: whether the
        dispatch reads the paged pool — the seq-shard log-sum-exp ring only
        runs in pool-reading dispatches (decode/ctx/verify; a COLD prefill
        pack attends densely and hops nothing).  No-op without a TP mesh
        and without seq shards."""
        ctx = self.serving_ctx
        if self._mesh is None or (ctx.size <= 1 and self.seq_shards <= 1):
            return
        from ..comm import budget

        plan = budget.serving_tick_plan(
            self.cfg, n_tokens, ctx.size, ctx.comm_fmt,
            tiles=max(ctx.comm_tiles, 1),
            sample_rows=n_tokens if sample_rows is None else sample_rows,
            seq_shards=self.seq_shards if ring else 1,
            replicas=self.serve_replicas,
        )
        self._comm_c["bytes_on_wire"].inc(
            reps * budget.plan_bytes(plan, overhead=False))
        self._comm_c["bytes_on_wire_overhead"].inc(
            reps * budget.plan_bytes(plan, overhead=True))
        # wire-op count: the plan's row group is already per-tile
        n_ops = sum(p.count for p in plan if p.label == "row_psum")
        self._comm_c["collectives"].inc(reps * n_ops)

    # -- fault hooks ---------------------------------------------------------
    def _maybe_fault(self, point: str, uids) -> None:
        """Chaos-harness check before a dispatch site.  Raised BEFORE the jit
        call, so the donated KV pool is never half-consumed by an aborted
        dispatch — a retry or per-request isolation probe re-dispatches
        against intact state."""
        if self.faults is not None:
            self.faults.maybe_raise(point, uids=uids)

    def _poisoned(self, uids) -> frozenset:
        """Uids whose rows the chaos harness poisons this tick — injected at
        the host boundary as the same ``-1`` sentinel ``finite_guard``
        produces for real non-finite logits, so the full quarantine path
        (no token committed, reservation rollback, typed failure) runs."""
        if self.faults is None:
            return frozenset()
        return frozenset(self.faults.select("nan_logits", uids))

    # -- speculative decoding ------------------------------------------------
    def plan_speculation(
        self, active_seqs, max_total_draft_tokens: Optional[int] = None,
        max_emit: Optional[Dict[int, int]] = None,
    ) -> Dict[int, List[int]]:
        """Prompt-lookup draft proposals for one verify tick: {uid: drafts}.

        Per-sequence draft length is throttled by the accept-rate EMA the
        verify tick maintains (sequences that reject everything fall to 0 =
        plain decode, re-probing with one token every few ticks), clamped so
        the sequence cannot outgrow ``max_seq_len``, and capped overall by
        ``max_total_draft_tokens`` — the scheduler passes its leftover
        prefill-chunk budget here so chunked prefill and speculation share
        one per-tick token headroom (drafted, not emitted, tokens count
        against it).  ``max_emit`` caps tokens a sequence may still emit
        (the scheduler passes each request's remaining ``max_new_tokens``):
        a tick emits at most n_drafts + 1, so drafts clamp to max_emit - 1
        HERE, before they debit the shared budget — a clamped-away draft
        must not starve another sequence's proposal.  Sequences with no
        proposal are absent from the dict.
        """
        from . import speculative

        out: Dict[int, List[int]] = {}
        if not self.enable_speculation:
            return out
        budget = (max_total_draft_tokens if max_total_draft_tokens is not None
                  else self.mgr.max_seqs * self.spec_max_draft)
        for s in active_seqs:
            cap = s.spec_draft_len if s.spec_draft_len >= 0 else self.spec_max_draft
            if cap == 0:
                # throttled to plain decode: re-probe with a single draft
                # token every few ticks so a sequence that BECOMES
                # compressible (e.g. falls into a repetition loop) recovers
                s.spec_cooldown -= 1
                if s.spec_cooldown > 0:
                    continue
                cap = 1
            cap = min(cap, self.spec_max_draft, budget,
                      self.max_seq_len - s.cur_len - 1)
            if max_emit is not None and s.uid in max_emit:
                cap = min(cap, max_emit[s.uid] - 1)
            if cap <= 0:
                continue
            drafts = speculative.propose(
                s.tokens, self.spec_min_match, cap, self.spec_lookup_window
            )
            if drafts:
                out[s.uid] = drafts
                budget -= len(drafts)
        return out

    def _spec_tick(
        self, active_seqs, sampling: SamplingParams,
        proposals: Optional[Dict[int, List[int]]] = None,
    ) -> Dict[int, List[int]]:
        """One speculative tick over ``active_seqs``: draft (prompt lookup)
        -> single-pass verify of k+1 positions per sequence -> accept ->
        rollback.  Returns {uid: emitted tokens} — each sequence emits
        between 1 (all drafts rejected, or none proposed: plain-decode
        equivalent) and k+1 (all accepted + bonus) tokens, appended to its
        descriptor.  Falls back to ``_decode_tick`` when nothing drafted
        (no k+1-wide dispatch for incompressible batches)."""
        if proposals is None:
            proposals = self.plan_speculation(active_seqs)
        # reserve pages for every position each pack would write
        # (L-1 .. L-1+n); under pool pressure a sequence sheds its drafts
        # and reserves only the plain-decode token, so speculation never
        # raises where enable_speculation=False would have fit (the
        # scheduler sheds pre-emptively; this guards direct step())
        bs = self.block_size
        for s in active_seqs:
            n = len(proposals.get(s.uid, []))
            L = s.cur_len
            try:
                self.mgr.ensure_capacity(s, n + 1)
                # the COW guard belongs to the same reservation: its
                # allocate(1) can fail a pool the capacity check fit, and it
                # must run BEFORE the block list is read into the destination
                # arrays (it may swap a shared page)
                for pg in range((L - 1) // bs, (L - 1 + n) // bs + 1):
                    self.mgr.ensure_writable(s, pg * bs)
            except RuntimeError:
                if not n:
                    raise
                proposals.pop(s.uid, None)
                self._c["spec_drafts_shed"].inc()
                # release the draft-tail reservation before retrying — those
                # blocks may be exactly what the plain-decode COW clone needs
                self.mgr.truncate_to_length(s)
                self.mgr.ensure_capacity(s, 1)
                self.mgr.ensure_writable(s, L - 1)
        if not proposals:
            return {u: [t] for u, t in
                    self._decode_tick(active_seqs, sampling).items()}
        self._maybe_fault("runner_exception", [s.uid for s in active_seqs])
        tel, ns = self.telemetry, self._ns
        with tel.span("engine.decode_build", track=ns) as bsp:
            B, K = self.mgr.max_seqs, self.spec_max_draft
            K1, bs = K + 1, self.block_size
            tokens = np.zeros(B * K1, np.int32)
            seg = np.zeros(B * K1, np.int32)
            pos = np.zeros(B * K1, np.int32)
            dst_pages = np.full(B * K1, -1, np.int32)
            dst_offs = np.zeros(B * K1, np.int32)
            draft = np.zeros((B, K), np.int32)
            n_draft = np.zeros(B, np.int32)
            ctx_lens = np.zeros(B, np.int32)
            for s in active_seqs:
                drafts = proposals.get(s.uid, [])
                n = len(drafts)
                L = s.cur_len
                self._set_block_table(s)  # COW swaps ran in the capacity pre-pass
                draft[s.slot, :n] = drafts
                n_draft[s.slot] = n
                ctx_lens[s.slot] = s.seen_tokens
                for i in range(n + 1):
                    p_tok = L - 1 + i
                    row = s.slot * K1 + i
                    tokens[row] = s.tokens[-1] if i == 0 else drafts[i - 1]
                    seg[row] = s.slot + 1
                    pos[row] = p_tok
                    dst_pages[row] = s.blocks[p_tok // bs]
                    dst_offs[row] = p_tok % bs
            bsp.mark("rows")
        # spec_tick_ms is uploads + dispatch + fetch: the argument uploads
        # belong inside the span
        with tel.span(
            "spec_tick", track=ns, hist=self._h["spec_tick_ms"],
            batch=len(active_seqs), drafted=int(n_draft.sum()),
            ctx_tokens=int(ctx_lens.sum()),
        ) as sp:
            up = self._upload
            args = (
                self.params, up(tokens), up(seg), up(pos), up(dst_pages),
                up(dst_offs), self._tables_device(), up(ctx_lens), up(draft),
                up(n_draft), self._sampling_device(active_seqs, sampling),
                self.kv, self._rng, sampling.top_k, sampling.temperature <= 0.0,
            )
            sp.mark("upload")
            out_dev, n_out_dev, self.kv, self._rng = self._spec_jit(*args)
            sp.dispatched()
            self._c["spec_ticks"].inc()
            self._c["spec_seq_forwards"].inc(len(active_seqs))
            self._account_comm(tokens.shape[0])
            # the tick's host sync
            out_np, n_out = np.asarray(out_dev), np.asarray(n_out_dev)
        with tel.span("engine.decode_emit", track=ns):
            poison = self._poisoned([s.uid for s in active_seqs])
            out: Dict[int, List[int]] = {}
            for s in active_seqs:
                n_emit = int(n_out[s.slot])
                emitted = [int(t) for t in out_np[s.slot, :n_emit]]
                if s.uid in poison or any(t < 0 for t in emitted):
                    # finite_guard poisoned the whole row (NaN anywhere in its
                    # k+1 verify positions): commit nothing, roll back the draft
                    # page reservations, retract its published keys, and
                    # surface the typed failure
                    s.error = "non-finite logits in verify"
                    self.mgr.quarantine_written(s)
                    if self.mgr.truncate_to_length(s):
                        self._set_block_table(s)
                    out[s.uid] = [-1]
                    continue
                n = int(n_draft[s.slot])
                n_acc = n_emit - 1
                s.tokens.extend(emitted)
                s.seen_tokens = s.cur_len - 1
                # rollback: free tail blocks the rejected drafts reserved (their
                # garbage KV rows inside KEPT blocks are masked by length and
                # overwritten as the sequence grows — the step_n rule)
                if self.mgr.truncate_to_length(s):
                    self._set_block_table(s)
                self.mgr.update_hashes(s)
                self._c["spec_drafted"].inc(n)
                self._c["spec_accepted"].inc(n_acc)
                self._c["spec_emitted"].inc(n_emit)
                s.spec_drafted += n
                s.spec_accepted += n_acc
                rep = self._spec_by_replica[self.mgr.replica_of(s)]
                rep[0] += n
                rep[1] += n_acc
                if n > 0:
                    self._spec_update_throttle(s, n, n_acc)
                out[s.uid] = emitted
        return out

    def _spec_update_throttle(self, s, n: int, n_acc: int) -> None:
        """Fold one verify tick's (drafted, accepted) into the sequence's
        accept-rate EMA and recompute its draft-length cap.  A sequence
        rejecting everything decays to 0 (= plain decode) within ~3
        consecutive full-rejection ticks and re-probes with a single draft
        token after the cooldown; acceptance grows the cap back toward
        ``spec_max_draft``."""
        s.spec_ema = 0.5 * s.spec_ema + 0.5 * (n_acc / n)
        s.spec_draft_len = int(round(s.spec_ema * self.spec_max_draft))
        if s.spec_draft_len == 0:
            s.spec_cooldown = 8

    def _decode_tick(self, active_seqs, sampling: SamplingParams) -> Dict[int, int]:
        """One batched decode dispatch over ``active_seqs`` only (other
        tracked sequences keep their KV untouched — the scheduler decodes
        its own running set without side-driving ``put()``-admitted ones),
        fetched and emitted at once: ``decode_dispatch`` and
        ``decode_collect`` back to back.  Appends the sampled token per
        sequence; stop/length handling is the caller's job."""
        return self.decode_collect(self.decode_dispatch(active_seqs, sampling))

    def _fill_step(self, rows, active_seqs) -> int:
        """A decode step's ``[4, slots]`` rows for ``active_seqs``, pages
        grown and the tables' mirror brought up to date; returns the context
        tokens the step attends."""
        tokens, seq_lens, active, chained = rows
        ctx_tokens = 0
        for s in active_seqs:
            # grow pages for the token being written this tick; the COW
            # guard clones the target page first if it is somehow shared
            self.mgr.ensure_capacity(s, 1)
            self.mgr.ensure_writable(s, s.cur_len - 1)
            self._set_block_table(s)
            if s.pending:
                chained[s.slot] = 1
            else:
                tokens[s.slot] = s.tokens[-1]
            seq_lens[s.slot] = s.cur_len - 1  # KV position of the new token
            active[s.slot] = 1
            ctx_tokens += s.cur_len
        return ctx_tokens

    def decode_dispatch(self, active_seqs, sampling: SamplingParams,
                        split: bool = False, ahead: bool = False) -> "Enqueued":
        """Build, upload and ENQUEUE one decode tick over ``active_seqs``;
        ``decode_collect`` fetches and emits it.  ``split`` / ``ahead`` as
        ``pack_dispatch``'s.  A sequence with a token on the way
        (``pending``) reads its input token from the chain on the device:
        the host knows its position and its pages, not its value."""
        tel, ns = self.telemetry, self._ns
        with tel.span("engine.decode_build", track=ns) as bsp:
            B = self.mgr.max_seqs
            # the tick's ONE upload: tokens, KV positions, 0 / 1 for a live
            # slot, 0 / 1 for an input token that is the chain's
            rows = np.zeros((4, B), np.int32)
            ctx_tokens = self._fill_step(rows, active_seqs)
            self._maybe_fault("runner_exception", [s.uid for s in active_seqs])
            bsp.mark("rows")  # what is left of the span: the runner's counts
            extra = self.runner.dispatched(
                self._c, ((s.slot, s.cur_len - 1, s.cur_len) for s in active_seqs), tokens=B)
        # decode_tick_ms is uploads + dispatch + fetch: the argument uploads
        # (the tick's rows, and the tables when a page moved) belong inside
        # the span
        with tel.span(
            "decode_tick", track=ns, hist=self._h["decode_tick_ms"],
            batch=len(active_seqs), ctx_tokens=ctx_tokens, ahead=int(ahead),
            **extra,
        ) as sp:
            args = (
                self.params, self._upload(rows, held=False),
                self._tables_device(), self.kv, self._rng, self._chain,
                (sampling.temperature, sampling.top_k, sampling.top_p),
            )
            sp.mark("upload")  # every argument handed over; next: enqueue
            sampled, self._rng, self.kv = self._decode_jit(*args)
            self._chain = sampled
            if self._tracked:
                self._tracked["_decode_jit"].note(args)
            sp.dispatched()
            self._c["decode_ticks"].inc()
            self._c["decode_emitted"].inc(len(active_seqs))
            self._c["dispatched_ahead"].inc(int(ahead))
            self._account_comm(B)
            done = Enqueued("decode_tick", list(active_seqs), None, split, sp)
            if split:
                # the fetch is the collect's: the span ends at the enqueue
                sp.end(sync_obj=sampled)
                sampled.copy_to_host_async()  # (as a split pack's)
                done.sampled = sampled
            else:
                done.tokens = np.asarray(sampled)  # the tick's host sync
        if self.mgr.compaction is not None:
            # (``cur_len - 1`` is the position this tick wrote)
            self._close_windows((s, s.cur_len) for s in active_seqs)
        for s in active_seqs:
            s.pending += 1  # a token on the way: lengths count it from here
        return done

    def decode_collect(self, done: "Enqueued", dead=()) -> Dict[int, int]:
        """Fetch and emit a tick ``decode_dispatch`` enqueued: {uid: token},
        -1 for a row the finite guard failed; a dead row (``dead``: its
        request ended while the tick was enqueued) is in it nowhere."""
        next_tokens = self._fetched(done)
        with self.telemetry.span("engine.decode_emit", track=self._ns):
            return self._emit_step(done, done.rows, next_tokens, dead)

    def _emit_step(self, done: "Enqueued", active_seqs, next_tokens,
                   dead) -> Dict[int, int]:
        """Book what a step sampled for ``active_seqs`` (a tick's rows, or
        the rows a pack carried), inside the caller's emit span."""
        poison = self._poisoned([s.uid for s in active_seqs])
        out = {}
        for s in active_seqs:
            s.pending -= 1
            if self._dropped(s, done, dead):
                continue
            tok = int(next_tokens[s.slot])
            if s.uid in poison:
                tok = -1
            if tok < 0:
                # finite_guard sentinel: fail this row only — no token is
                # committed, the growth block reserved for it above is
                # returned, and the keys it published are retracted (its
                # written KV is suspect) so nothing leaks or pollutes
                s.error = "non-finite logits in decode"
                self.mgr.quarantine_written(s)
                if self.mgr.truncate_to_length(s):
                    self._set_block_table(s)
                out[s.uid] = -1
                continue
            s.tokens.append(tok)
            s.seen_tokens = len(s.tokens) - 1
            self.mgr.update_hashes(s)
            out[s.uid] = tok
        return out

    def step(self, sampling: SamplingParams = SamplingParams()) -> Dict[int, int]:
        """One batched decode tick over all active sequences; returns the
        newest token per uid (sequences at their stop token are skipped).
        With ``enable_speculation`` a tick may emit SEVERAL tokens per
        sequence (drafts accepted by the verify pass) — all are appended to
        the descriptor, the newest is returned, and a stop token inside the
        emitted run truncates the sequence there."""
        self._settle()
        active_seqs = [s for s in self.mgr.active if not s.done]
        if not active_seqs:
            return {}
        if self.enable_speculation:
            runs = self._spec_tick(active_seqs, sampling)
        else:
            runs = {u: [t] for u, t in
                    self._decode_tick(active_seqs, sampling).items()}
        out = {}
        for s in active_seqs:
            run = runs[s.uid]
            if run and run[-1] < 0:
                # finite_guard sentinel (s.error carries the detail): the
                # sequence is done-with-error; healthy batchmates continue
                s.done = True
                out[s.uid] = -1
                continue
            if sampling.stop_token is not None and sampling.stop_token in run:
                cut = len(run) - run.index(sampling.stop_token) - 1
                if cut:  # drop speculated tokens past the stop
                    del s.tokens[-cut:]
                    run = run[:-cut]
                    s.seen_tokens = min(s.seen_tokens, s.cur_len - 1)
                s.done = True
            if s.cur_len >= self.max_seq_len:
                s.done = True
            out[s.uid] = run[-1]
        return out

    def _decode_burst(
        self, active_seqs, sampling: SamplingParams, n: int,
        max_emit: Optional[Dict[int, int]] = None,
        stop_tokens: Optional[Dict[int, Optional[int]]] = None,
    ) -> Dict[int, List[int]]:
        """Device-resident multi-tick decode core: up to ``n`` fused decode
        dispatches over ``active_seqs`` with ON-DEVICE termination and ONE
        end-of-burst fetch.  ``step_n`` and the scheduler's megastep both
        ride this.

        Per-slot stop tokens (``stop_tokens`` {uid: id}, default the shared
        ``sampling.stop_token``) and emission caps (``max_emit`` {uid: n},
        additionally clamped by ``max_seq_len`` headroom) ride device
        arrays into the burst jit, which deactivates each row the tick it
        stops — later ticks neither sample it nor write its KV, so the
        fetched runs are token-identical to per-tick ``step()`` decode
        (no decode-past-stop).

        One dispatch PER TICK (donation keeps the multi-GB KV pool updating
        in place — a fused lax.scan burst was measured 5x slower: the pool
        stops aliasing inside the loop carry), but only ONE host sync per
        burst AND zero per-tick uploads: tokens, seq_lens, the rng key, the
        active mask, the emission counts and the [cap+1, B] burst
        accumulator are all device arrays chained tick-to-tick.  The host
        must NOT retain per-tick outputs (holding every tick's token array
        alive was measured to stretch ticks from ~14 ms to 20-70 ms).

        Returns {uid: emitted run}.  A poisoned row quarantines AT its
        first bad tick on device (the mask drops it; later ticks never
        attend over its suspect KV): its run ends with the -1 sentinel,
        the healthy prefix before it is committed, and its published cache
        keys are retracted.  A chaos-injected ``nan_logits`` poison applies
        at burst granularity: nothing commits, run = [-1].  Rows given no
        emission headroom return an empty run untouched."""
        if self.mgr.compaction is not None:
            from ..models.latent import refuse

            refuse("serve.decode_megastep > 1 (a decode burst)", "a table that gives "
                   "pages back is compacted by the host between two ticks, and a burst's "
                   "ticks share one table")
        tel, ns = self.telemetry, self._ns
        with tel.span("engine.decode_build", track=ns) as bsp:
            B = self.mgr.max_seqs
            uids = [s.uid for s in active_seqs]
            base_lens = np.zeros(B, np.int32)
            tokens0 = np.zeros(B, np.int32)
            active = np.zeros(B, bool)
            stop_rows = np.full(B, -1, np.int32)
            emit_cap = np.zeros(B, np.int32)
            for s in active_seqs:
                cap_i = min(n, self.max_seq_len - s.cur_len)
                if max_emit is not None and s.uid in max_emit:
                    cap_i = min(cap_i, int(max_emit[s.uid]))
                if cap_i < 1:
                    continue  # no headroom: empty run, row never enters the batch
                # pre-reserve every page this row's burst can touch: the block
                # tables are then static for all its ticks (one upload); rows
                # stopping early hand the unused tail back after the fetch
                self.mgr.ensure_capacity(s, cap_i)
                self.mgr.ensure_writable(s, s.cur_len - 1)
                self._set_block_table(s)
                base_lens[s.slot] = s.cur_len - 1
                tokens0[s.slot] = s.tokens[-1]
                active[s.slot] = True
                emit_cap[s.slot] = cap_i
                st = sampling.stop_token if stop_tokens is None \
                    else stop_tokens.get(s.uid, sampling.stop_token)
                stop_rows[s.slot] = -1 if st is None else int(st)
            if not active.any():
                return {u: [] for u in uids}
            # no tick can emit once every row is past its cap — clamp the burst
            n = min(n, int(emit_cap.max()))
            self._maybe_fault("runner_exception", uids)
            bsp.mark("rows")  # this body commits its buffers inside the build
            tables = self._tables_device()
            tokens_dev = self._upload(tokens0)
            lens_dev = self._upload(base_lens)
            active_dev = self._upload(active)
            emitted_dev = self._upload(np.zeros(B, np.int32))
            stop_dev = self._upload(stop_rows)
            cap_dev = self._upload(emit_cap)
            triple = (sampling.temperature, sampling.top_k, sampling.top_p)
            # fixed burst capacity -> one compiled program for every n
            cap = self._burst_cap
            while cap < n:
                cap *= 2
            self._burst_cap = cap
            # [cap+1, B]: row 0 carries the per-slot emission counts, row 1+t
            # tick t's emissions — counts and tokens come back in ONE fetch
            buf = np.full((cap + 1, B), _BURST_PAD, np.int32)
            buf[0] = 0
            burst_dev = self._upload(buf)
            tick_dev = self._upload(np.zeros((), np.int32))
        # ONE span for the whole burst — per-tick spans would retain one
        # device array per tick, the exact host-reference leak this design
        # removes
        with tel.span(
            "decode_burst", track=ns, ticks=n, batch=len(active_seqs),
            ctx_tokens=int(base_lens.sum()) + int(active.sum()),
        ) as sp:
            sp.mark("upload")  # nothing left to upload: the names of a tick
            for _ in range(n):
                (tokens_dev, lens_dev, self._rng, self.kv, burst_dev,
                 tick_dev, active_dev, emitted_dev) = self._decode_burst_jit(
                    self.params, tokens_dev, lens_dev, tables, active_dev,
                    self.kv, self._rng, burst_dev, tick_dev, emitted_dev,
                    stop_dev, cap_dev, triple,
                )
            sp.dispatched()
            # a burst is n decode dispatches: account their TP wire bytes —
            # per-tick plan x n, ONE block-table upload (the same enumeration
            # the Graft Auditor checks against the burst jit's compiled HLO)
            self._account_comm(B, reps=n)
            self._c["decode_bursts"].inc()
            self._c["burst_ticks"].inc(n)
            burst = np.asarray(burst_dev)[: n + 1]  # the ONE host sync
        with tel.span("engine.decode_emit", track=ns):
            poison_inj = self._poisoned(uids)
            out: Dict[int, List[int]] = {}
            total = 0
            for s in active_seqs:
                if not active[s.slot]:
                    out[s.uid] = []
                    continue
                m = int(burst[0, s.slot])
                run = [int(t) for t in burst[1: 1 + m, s.slot]]
                if s.uid in poison_inj:
                    # chaos-injected poison: same contract as a tick-0 device
                    # sentinel — nothing committed, the row quarantined
                    run, committed = [-1], []
                elif run and run[-1] == -1:
                    committed = run[:-1]
                else:
                    committed = run
                s.tokens.extend(committed)
                s.seen_tokens = s.cur_len - 1
                if run and run[-1] < 0:
                    # the row deactivated at its first bad tick on device; its
                    # published keys are retracted (written KV is suspect)
                    s.error = "non-finite logits in decode burst"
                    self.mgr.quarantine_written(s)
                else:
                    self.mgr.update_hashes(s)
                # hand back the unused tail reservation (early-stopped rows) /
                # the poisoned tick's growth block in one truncate
                if self.mgr.truncate_to_length(s):
                    self._set_block_table(s)
                total += len(committed)
                out[s.uid] = run
        self._c["burst_emitted"].inc(total)
        return out

    def step_n(self, n: int, sampling: SamplingParams = SamplingParams()) -> Dict[int, int]:
        """``n`` pipelined decode ticks: sampled tokens stay ON DEVICE
        between ticks (each tick's output feeds the next tick's input
        directly), so the host round trip is paid ONCE per burst, not per
        token.

        Stop-EXACT: the burst jit checks each row's stop token and length
        cap on device and deactivates it the tick it finishes, so the
        fetched tokens are identical to ``n`` per-tick ``step()`` calls —
        the reference FastGen's async-scheduling caveat (decoding up to
        ``n-1`` tokens past a stop) is retired.  Returns
        {uid: last kept token} (-1 for a poisoned row, same as ``step()``).
        """
        self._settle()
        active_seqs = [s for s in self.mgr.active if not s.done]
        if not active_seqs or n <= 0:
            return {}
        # sequences already at the length cap finish; the rest keep decoding
        # (marking the whole batch done on one full sequence would silently
        # kill healthy requests)
        for s in active_seqs:
            if s.cur_len >= self.max_seq_len:
                s.done = True
        active_seqs = [s for s in active_seqs if not s.done]
        if not active_seqs:
            return {}
        # rows terminate at their own length caps on device, so the burst
        # length follows the LEAST constrained row (the old host clamp to
        # the shortest headroom starved healthy batchmates)
        n = min(n, self.max_seq_len - min(s.cur_len for s in active_seqs))
        runs = self._decode_burst(active_seqs, sampling, n)
        out: Dict[int, int] = {}
        for s in active_seqs:
            run = runs[s.uid]
            if not run:
                continue
            if run[-1] < 0:
                # poisoned rows report the sentinel, same contract as
                # step(): the caller must not mistake a stale committed
                # token for a fresh emission from a failed sequence
                s.done = True
                out[s.uid] = -1
                continue
            if sampling.stop_token is not None \
                    and run[-1] == sampling.stop_token:
                s.done = True
            if s.cur_len >= self.max_seq_len:
                s.done = True
            out[s.uid] = run[-1]
        return out

    def _settle(self) -> None:
        """A direct ``step()`` / ``step_n()`` drives EVERY tracked sequence:
        a scheduler that has a tick enqueued ahead collects it first (every
        token of its sequences is then the host's)."""
        if self._scheduler is not None:
            self._scheduler.settle("direct_step")

    def flush(self, uids: Sequence[int]) -> None:
        for uid in uids:
            self.mgr.release(uid)

    # -- paged-KV handoff (serving/handoff.py rides these) -------------------
    @staticmethod
    def _handoff_pad(n: int) -> int:
        """Page counts rounded up to the next power of two: the handoff
        gather/scatter jits then compile O(log pool) shapes total instead
        of one per distinct migrated-prompt length — a mid-migration XLA
        compile (the scatter donates the whole pool) stalls every worker's
        tick."""
        return 1 << (n - 1).bit_length() if n > 1 else n

    def extract_kv_blocks(self, blocks: Sequence[int]):
        """Device->host copy of a block range: per-layer ``(k, v)`` page
        arrays ``[n_blocks, bs, hkv, hd]`` for ``blocks`` (GLOBAL ids, any
        order).  One gather dispatch for the whole tree; the host copy is
        the prefill half of a prefill/decode disaggregation handoff —
        wire-format packing (optional int8 per-chunk-scale quantization) is
        the router's job (comm.qcomm payload codec), not the engine's."""
        if self._kv_gather_jit is None:
            self._kv_gather_jit = jax.jit(
                lambda kv, idx: jax.tree_util.tree_map(
                    lambda c: jnp.take(c, idx, axis=0), kv
                )
            )
        idx = [int(b) for b in blocks]
        n = len(idx)
        idx += [idx[-1]] * (self._handoff_pad(n) - n)
        pages = self._kv_gather_jit(self.kv, jnp.asarray(idx, jnp.int32))
        return jax.tree_util.tree_map(lambda c: np.asarray(c)[:n], pages)

    def inject_kv_blocks(self, blocks: Sequence[int], pages) -> None:
        """Scatter extracted pages into THIS engine's pool at ``blocks``
        (the decode half of the handoff).  ``pages`` is the
        :meth:`extract_kv_blocks` tree (host arrays; device arrays are
        copied back through the host — the handoff path is host-mediated
        anyway); the pool is donated so the write is in place, and on a TP
        mesh the result shardings are pinned so the pool stays sharded
        across the update.  The caller owns ``blocks`` (freshly allocated,
        refcount 1) — this never consults the allocator."""
        if self._kv_scatter_jit is None:
            def scatter(kv, idx, pay):
                return jax.tree_util.tree_map(
                    lambda c, p: c.at[idx].set(p.astype(c.dtype)), kv, pay
                )

            if self._kv_shardings is not None:
                self._kv_scatter_jit = jax.jit(
                    scatter, donate_argnums=(0,),
                    out_shardings=self._kv_shardings,
                )
            else:
                self._kv_scatter_jit = jax.jit(scatter, donate_argnums=(0,))
        idx = [int(b) for b in blocks]
        n = len(idx)
        pad = self._handoff_pad(n) - n
        if pad:
            # duplicate-index scatter of IDENTICAL content: whichever
            # duplicate wins, the page's bits are the same
            idx += [idx[-1]] * pad
            pages = jax.tree_util.tree_map(
                lambda p: np.concatenate(
                    [np.asarray(p),
                     np.broadcast_to(np.asarray(p)[-1:],
                                     (pad,) + np.asarray(p).shape[1:])]),
                pages)
        self.kv = self._kv_scatter_jit(
            self.kv, jnp.asarray(idx, jnp.int32),
            jax.tree_util.tree_map(jnp.asarray, pages),
        )

    # -- per-replica telemetry ----------------------------------------------
    def replica_stats(self) -> List[Dict[str, float]]:
        """Host-side per-replica serving stats: the allocator/hit-rate rows
        from the state manager plus this engine's speculation totals — the
        exact figures ``update_replica_gauges`` publishes (the
        router's load surface reads this directly; tests assert on it)."""
        rows = self.mgr.replica_stats()
        for r, row in enumerate(rows):
            drafted, accepted = self._spec_by_replica[r]
            row["spec_drafted"] = drafted
            row["spec_accepted"] = accepted
            row["spec_accept_rate"] = accepted / drafted if drafted else 0.0
        return rows

    def update_replica_gauges(self) -> None:
        """Refresh the ``serve/replicaN/*`` gauges (prefix-hit rate, pool
        headroom fraction, spec accept rate) from ``replica_stats`` — cheap
        host math the paired scheduler runs once per tick on partitioned
        engines, so cross-replica imbalance is visible to the
        router's load surface and the future online-tuning controller.
        The names ride this engine's claimed ``serve`` prefix, so
        ``release_prefix`` at close sweeps them with the rest."""
        if not self.telemetry.enabled:
            return  # registry.gauge() is a shared no-op when disabled
        reg = self.telemetry.registry
        for r, row in enumerate(self.replica_stats()):
            pre = f"{self._ns}/replica{r}"
            reg.gauge(f"{pre}/prefix_hit_rate").set(row["prefix_hit_rate"])
            reg.gauge(f"{pre}/pool_headroom").set(row["headroom"])
            reg.gauge(f"{pre}/spec_accept_rate").set(row["spec_accept_rate"])

    # -- teardown -----------------------------------------------------------
    # -- live retune surface -------------------------------------------------
    def apply_knobs(self, *, enable_speculation: Optional[bool] = None,
                    spec_max_draft: Optional[int] = None,
                    kv_watermark: Optional[float] = None,
                    prefill_chunk: Optional[int] = None) -> Dict[str, Any]:
        """Retune the engine-owned LIVE knobs — the ones read per tick off
        plain attributes, never baked into a compiled program — validated
        against the same gates as construction.  Raises ``ValueError`` on
        any invalid value BEFORE applying anything (all-or-nothing).
        Everything else (tp, replicas, weight quant, ``quant_comm``,
        ``comm_tiles``, pool geometry) is frozen into the jits /
        ``ServingContext`` and can only change through a rebuild
        (``close()`` + ``build_serve_engine``).  Returns the applied
        ``{knob: value}``.  Call from the engine's single-owner thread
        (the scheduler applies staged knobs at its tick boundary)."""
        spec_on = (self.enable_speculation if enable_speculation is None
                   else bool(enable_speculation))
        draft = (self.spec_max_draft if spec_max_draft is None
                 else int(spec_max_draft))
        if spec_on and draft < 1:
            raise ValueError("spec_max_draft must be >= 1 when speculating")
        if spec_on and not self.enable_speculation \
                and self._scheduler is not None and not self._scheduler.idle:
            # turning the drafter ON mid-flight would hand live sequences
            # drafter state they were never admitted with; require a drain
            raise ValueError(
                "enable_speculation can only turn on while the scheduler "
                "is drained (live sequences carry no drafter state)")
        if kv_watermark is not None and not 0.0 <= float(kv_watermark) < 1.0:
            raise ValueError(
                f"kv_watermark must be in [0, 1), got {kv_watermark}")
        if prefill_chunk is not None and int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        applied: Dict[str, Any] = {}
        if enable_speculation is not None:
            self.enable_speculation = spec_on
            applied["enable_speculation"] = spec_on
        if spec_max_draft is not None:
            self.spec_max_draft = draft
            applied["spec_max_draft"] = draft
        if kv_watermark is not None:
            self.kv_watermark = float(kv_watermark)
            applied["kv_watermark"] = self.kv_watermark
        if prefill_chunk is not None:
            self.prefill_chunk = int(prefill_chunk)
            applied["prefill_chunk"] = self.prefill_chunk
        return applied

    def close(self) -> Dict[str, int]:
        """Tear this engine down so another can be built in-process without
        inheriting its footprint (the autotuner runs trial engines
        back-to-back): cancel every scheduler-managed request, release
        every tracked sequence, audit the allocator, return the engine's
        claimed telemetry namespaces (a shared ``Telemetry`` hands
        ``serve``/``sched``/``comm`` to the NEXT engine instead of marching
        to ``serve2``, ``serve3``, ...), and drop the param/KV/jit
        references holding device memory.  Idempotent.  Returns
        ``{"blocks_in_use": n, "cached_blocks": m}`` post-release so
        callers can assert the zero-leak invariant."""
        if getattr(self, "_closed", False):
            return dict(self._close_audit)
        if self._scheduler is not None:
            self._scheduler.close()
        self.refresh_routing_stats()
        for uid in list(self.mgr.seqs):
            self.mgr.release(uid)
        in_use = 0
        cached = 0
        for a in self.mgr.allocators:
            a.audit()  # raises on any broken refcount/cache invariant
            # post-audit identity: every block is free, cached, or held
            in_use += a.total_blocks - a.free_blocks - a.cached_blocks
            cached += a.cached_blocks
        self._close_audit = {"blocks_in_use": in_use, "cached_blocks": cached,
                             **self.runner.audit()}
        self.telemetry.flush()
        for ns in (self._ns, self._sched_ns, self._comm_ns):
            self.telemetry.release_prefix(ns)
        # drop the big device references (params tree, KV pool, compiled
        # dispatches with their donated-buffer plumbing) — gc can then
        # reclaim the device buffers even if the engine object lingers
        self.params = None
        self.kv = None
        self.mgr.cow_hook = None
        self.mgr.release_hook = None
        self._tracked = {}
        for attr in ("_packed_prefill_jit", "_packed_prefill_ctx_jit",
                     "_cow_jit", "_decode_jit", "_decode_burst_jit",
                     "_spec_jit", "_tables_dev", "_samp_dev", "_chain",
                     "_kv_gather_jit", "_kv_scatter_jit"):
            setattr(self, attr, None)
        self._closed = True
        return dict(self._close_audit)

    # -- serving scheduler --------------------------------------------------
    @property
    def scheduler(self):
        """Lazily-built ``ServeScheduler`` bound to this engine: queueing
        admission (``submit`` never throws on capacity), chunked prefill,
        watermark headroom, preemption-by-recompute.  Scheduler-managed
        sequences and direct ``put()``/``step()`` sequences share the KV
        pool but tick independently."""
        if self._scheduler is None:
            from .scheduler import ServeScheduler

            self._scheduler = ServeScheduler(
                self, prefill_chunk=self.prefill_chunk,
                kv_watermark=self.kv_watermark, serve=self.serve,
                faults=self.faults,
            )
        return self._scheduler

    # -- convenience (v1-style generate) -----------------------------------
    def generate(
        self, prompt_tokens: Sequence[int], sampling: SamplingParams = SamplingParams()
    ) -> List[int]:
        """Single-prompt convenience: submits through the scheduler, so it
        rides the same admission/chunked-prefill/decode tick as real load
        and no longer side-drives other active sequences via bare ``step()``
        calls (scheduler ticks only touch scheduler-managed sequences)."""
        sched = self.scheduler
        uid = sched.next_uid()
        sched.submit(uid, prompt_tokens, sampling)
        sched.run(wait_for=[uid])
        req = sched.requests[uid]
        if req.state != "finished":
            # a failed/timed-out/cancelled one-shot has no partial-result
            # contract to honor — surface the typed terminal state loudly
            state, err = req.state, req.error
            sched.pop_result(uid)
            raise RuntimeError(f"generate() request {state}: {err or state}")
        return sched.pop_result(uid)


def build_serve_engine(params, cfg, sec, *, telemetry=None, serve=None,
                       faults=None, devices=None) -> InferenceEngineV2:
    """The canonical config -> engine seam: build an ``InferenceEngineV2``
    from a validated ``config.ServeEngineConfig`` (or a dict coerced into
    one).  ``tp``/``serve_replicas``/``seq_shards`` > 1 bring up the
    batch x seq x model mesh here, so every caller — autotuner trials,
    front ends — constructs multi-chip
    engines through one path instead of re-deriving mesh arithmetic.

    ``devices`` restricts the mesh to a device subset (defaults to the
    first ``tp * serve_replicas * seq_shards`` of ``jax.devices()``)."""
    from ..config.config import ServeEngineConfig, _coerce

    sec = sec if isinstance(sec, ServeEngineConfig) \
        else _coerce(ServeEngineConfig, dict(sec))
    grid = None
    if sec.tp > 1 or sec.serve_replicas > 1 or sec.seq_shards > 1:
        from ..parallel.topology import initialize_mesh

        devs = list(devices if devices is not None else jax.devices())
        need = sec.tp * sec.serve_replicas * sec.seq_shards
        if len(devs) < need:
            raise ValueError(
                f"serve_engine tp={sec.tp} x serve_replicas="
                f"{sec.serve_replicas} x seq_shards={sec.seq_shards} "
                f"needs {need} devices, have {len(devs)}"
            )
        axes = {"model": sec.tp}
        if sec.serve_replicas > 1:
            axes["batch"] = sec.serve_replicas
        if sec.seq_shards > 1:
            axes["seq"] = sec.seq_shards
        grid = initialize_mesh(devices=devs[:need], **axes)
    return InferenceEngineV2(
        params, cfg, grid=grid, telemetry=telemetry, serve=serve,
        faults=faults, **sec.engine_kwargs(),
    )
