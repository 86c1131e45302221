"""Pallas paged-attention kernel + packed prefill tests (VERDICT r3 item 5).

Reference: inference/v2/kernels/ragged_ops (blocked attention),
ragged/ragged_wrapper.py (packed atom building).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import (
    _paged_attention_decode_dense,
    init_paged_cache,
)
from deepspeed_tpu.ops.pallas import paged_attention as pk


@pytest.fixture(autouse=True)
def _interpret():
    pk.set_interpret(True)
    yield
    pk.set_interpret(False)


def _setup(B=4, hq=8, hkv=2, hd=64, nb=32, bs=16, P=6, lens=(5, 16, 33, 90), dtype=jnp.float32):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, hq, hd)), dtype)
    ck = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), dtype)
    cv = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), dtype)
    table = np.full((B, P), -1, np.int32)
    nxt = 1
    for b in range(B):
        for i in range(-(-int(lens[b]) // bs)):
            table[b, i] = nxt % nb
            nxt += 1
    return q, ck, cv, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def test_kernel_parity_vs_dense_gather():
    q, ck, cv, table, lens = _setup()
    out_k = pk.paged_attention_decode_kernel(q, ck, cv, table, lens)
    out_d = _paged_attention_decode_dense(q, ck, cv, table, lens)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d), atol=2e-5)


def test_kernel_parity_gqa_and_mha():
    for hq, hkv in ((8, 8), (8, 2), (4, 1)):
        q, ck, cv, table, lens = _setup(hq=hq, hkv=hkv)
        out_k = pk.paged_attention_decode_kernel(q, ck, cv, table, lens)
        out_d = _paged_attention_decode_dense(q, ck, cv, table, lens)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_d), atol=2e-5,
            err_msg=f"hq={hq} hkv={hkv}",
        )


def test_kernel_ignores_garbage_in_dead_pages():
    """Pages past a sequence's length may hold other sequences' data: the
    kernel must never read them (it routes only live table entries)."""
    q, ck, cv, table, lens = _setup(lens=(5, 16, 33, 90))
    out1 = pk.paged_attention_decode_kernel(q, ck, cv, table, lens)
    # poison every block NOT referenced by live pages
    live = set()
    bs = ck.shape[1]
    for b in range(table.shape[0]):
        for i in range(-(-int(lens[b]) // bs)):
            live.add(int(table[b, i]))
    dead = [blk for blk in range(ck.shape[0]) if blk not in live]
    ck2 = ck.at[jnp.asarray(dead)].set(1e4)
    cv2 = cv.at[jnp.asarray(dead)].set(1e4)
    out2 = pk.paged_attention_decode_kernel(q, ck2, cv2, table, lens)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-5)


def _tiled_setup(hq, hkv, bs, lens, poison=None, seed=0):
    """The cells' head layouts (hd 128) over interleaved, non-contiguous
    table ids with ``-1`` padding; ``poison`` overwrites every page no row
    owns (K and V alike)."""
    hd = 128
    lens = np.asarray(lens, np.int32)
    B = len(lens)
    P = -(-int(lens.max()) // bs) + 2
    need = sum(-(-int(n) // bs) for n in lens)
    nb = need + 5
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, hq, hd)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), jnp.float32)
    table = np.full((B, P), -1, np.int32)
    ids = iter(rng.permutation(nb))
    for i in range(P):  # page i of every row in turn: a row's ids interleave
        for b in range(B):
            if i * bs < lens[b]:
                table[b, i] = next(ids)
    if poison is not None:
        dead = jnp.asarray([x for x in range(nb) if x not in set(table[table >= 0].tolist())])
        ck, cv = ck.at[dead].set(poison), cv.at[dead].set(poison)
    return q, ck, cv, jnp.asarray(table), jnp.asarray(lens)


# (hq, hkv, block): Mistral-7B's and Nemotron-3-Super's attention blocks
_CELL_SHAPES = [(32, 8, 32), (32, 2, 128)]


def _case_lens(bs, hkv, case):
    kt = pk.tile_keys(jnp.zeros((1, bs, hkv, 128), jnp.float32), jnp.zeros((1, 64), jnp.int32))
    return {
        "dead": 0, "one": 1, "mid_page": bs + bs // 2 + 1,
        "tile_last_key": kt, "tile_first_key": kt + 1,
        "tiles_and_tail": 2 * kt + bs + 3,
    }[case]


@pytest.mark.parametrize("case", ["dead", "one", "mid_page", "tile_last_key",
                                  "tile_first_key", "tiles_and_tail"])
@pytest.mark.parametrize("hq,hkv,bs", _CELL_SHAPES)
def test_tiled_kernel_parity_at_the_cells_shapes(hq, hkv, bs, case, monkeypatch):
    """One row of the length under test between two others: the kernel's key
    tiles (several pages each) against the dense gather."""
    # small tiles keep the interpreter quick; the rule is the module's
    monkeypatch.setattr(pk, "_TILE_BYTES", 2 * bs * hkv * 128 * 4)
    n = _case_lens(bs, hkv, case)
    q, ck, cv, table, lens = _tiled_setup(hq, hkv, bs, (bs + 1, n, 3))
    out_k = pk.paged_attention_decode_kernel(q, ck, cv, table, lens)
    out_d = _paged_attention_decode_dense(q, ck, cv, table, lens)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d), atol=2e-5)
    if n == 0:
        assert not np.asarray(out_k)[1].any()


@pytest.mark.parametrize("hq,hkv,bs", _CELL_SHAPES)
def test_kernel_never_reads_nan_in_pages_no_row_owns(hq, hkv, bs, monkeypatch):
    """NaN in every page outside the rows' live tables: neither a fetched
    tile's unfetched tail nor a dead slot may bring one to the output."""
    monkeypatch.setattr(pk, "_TILE_BYTES", 2 * bs * hkv * 128 * 4)
    lens = (0, 1, 2 * bs + 1, 5 * bs - 1)
    q, ck, cv, table, lens = _tiled_setup(hq, hkv, bs, lens, poison=jnp.nan)
    out_k = np.asarray(pk.paged_attention_decode_kernel(q, ck, cv, table, lens))
    assert np.isfinite(out_k).all()
    clean = _tiled_setup(hq, hkv, bs, lens, poison=0.0)
    out_d = np.asarray(_paged_attention_decode_dense(*clean))
    np.testing.assert_allclose(out_k, out_d, atol=2e-5)


def test_dead_row_is_finite_zeros_from_kernel_and_dense_body():
    """length 0 = no row: the runners hand it to inactive slots, and the two
    bodies stay each other's ground truth (``finite_guard`` sees no NaN even
    where the slot's table clips to poisoned pages)."""
    q, ck, cv, table, lens = _tiled_setup(8, 2, 16, (0, 40, 0), poison=jnp.nan)
    for body in (pk.paged_attention_decode_kernel, _paged_attention_decode_dense):
        out = np.asarray(body(q, ck, cv, table, lens))
        assert np.isfinite(out).all(), body.__name__
        assert not out[0].any() and not out[2].any(), body.__name__
        assert out[1].any(), body.__name__


def test_dispatch_notes_the_tile_the_rule_chose():
    from deepspeed_tpu.inference.paged import paged_attention_decode
    from deepspeed_tpu.ops.pallas import record_dispatch

    q, ck, cv, table, lens = _setup()
    with record_dispatch() as log:
        paged_attention_decode(q, ck, cv, table, lens)
    (entry,) = [e for e in log if e["kernel"] == "paged_decode"]
    assert entry["ran"] and entry["tile_keys"] == pk.tile_keys(ck, table)
    assert entry["tile_keys"] % ck.shape[1] == 0  # whole pages


def test_dispatch_routes_to_kernel_in_interpret_mode():
    from deepspeed_tpu.inference.paged import paged_attention_decode

    q, ck, cv, table, lens = _setup()
    out = paged_attention_decode(q, ck, cv, table, lens)
    ref = _paged_attention_decode_dense(q, ck, cv, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# packed multi-prompt prefill
# ---------------------------------------------------------------------------
def _engine(**kw):
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=128).replace(dtype=jnp.float32)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    return InferenceEngineV2(
        params, cfg, max_seqs=4, num_blocks=64, block_size=8, **kw
    ), cfg


# slow: 22 s: the v1 engine's packed prefill against one sequential prefill a prompt, each its own compile
@pytest.mark.slow
def test_packed_prefill_matches_sequential():
    """N prompts in ONE packed dispatch produce the same first tokens and
    the same decode continuations as one-prefill-per-prompt."""
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(1, 250, n))) for n in (5, 11, 17)]

    packed, _ = _engine(prefill_budget=128)
    first_packed = packed.put([1, 2, 3], prompts)

    seq_engine, _ = _engine(prefill_budget=1)  # budget 1 forces one-per-pack
    first_seq = seq_engine.put([1, 2, 3], prompts)
    assert first_packed == first_seq

    # decode continuations agree too (same KV contents)
    for _ in range(3):
        a = packed.step()
        b = seq_engine.step()
        assert a == b


def test_packed_prefill_one_dispatch_for_many_prompts():
    engine, _ = _engine(prefill_budget=128)
    calls = []
    orig = engine._run_packed_prefill

    def counting(entries, sampling, out):
        calls.append(len(entries))
        return orig(entries, sampling, out)

    engine._run_packed_prefill = counting
    rng = np.random.default_rng(2)
    prompts = [list(map(int, rng.integers(1, 250, n))) for n in (6, 9, 12)]
    engine.put([1, 2, 3], prompts)
    assert calls == [3]  # all three prompts shared one dispatch


def test_packed_prefill_splits_at_budget():
    # budget accounting is PAGE-ALIGNED (block_size 8): 10-token prompts
    # cost 16 padded slots each, so budget 32 holds two prompts per pack
    engine, _ = _engine(prefill_budget=32)
    calls = []
    orig = engine._run_packed_prefill

    def counting(entries, sampling, out):
        calls.append(sum(end - start for _, start, end in entries))
        return orig(entries, sampling, out)

    engine._run_packed_prefill = counting
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(1, 250, n))) for n in (10, 10, 10)]
    engine.put([1, 2, 3], prompts)
    assert len(calls) == 2  # 16+16 padded, then 16: splits after two prompts
    assert all(c <= 32 for c in calls)


def test_packed_kernel_matches_dense_reference():
    """hd<128 PACKED variant (kv heads side-by-side on the lane dim,
    block-diagonal queries) — r4 VERDICT weak #1's kernel gap.  Interpret
    mode runs the same kernel body the chip executes."""
    import numpy as np
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _packed_mode,
        _paged_decode_packed,
    )

    assert _packed_mode(64, 2) and _packed_mode(32, 4)
    assert not _packed_mode(128, 2) and not _packed_mode(64, 1)

    rng = np.random.default_rng(0)
    B, nb, bs, P, hq, hkv, hd = 4, 16, 8, 4, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((B, hq, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((nb, bs, hkv, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((nb, bs, hkv, hd)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(nb)[: B * P].reshape(B, P), jnp.int32
    )
    lens = jnp.asarray(rng.integers(1, bs * P, B), jnp.int32)
    out = _paged_decode_packed(q, ck, cv, tables, lens, float(hd) ** -0.5)

    g = hq // hkv
    for b in range(B):
        k = np.asarray(ck)[np.asarray(tables)[b]].reshape(-1, hkv, hd)[: int(lens[b])]
        v = np.asarray(cv)[np.asarray(tables)[b]].reshape(-1, hkv, hd)[: int(lens[b])]
        kk = np.repeat(k, g, axis=1)
        vv = np.repeat(v, g, axis=1)
        s = np.einsum("hd,khd->hk", np.asarray(q)[b], kk) / np.sqrt(hd)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hk,khd->hd", p, vv)
        np.testing.assert_allclose(
            np.asarray(out)[b], ref, rtol=2e-3, atol=2e-3
        )
