"""The gated delta rule's two forms (``ops/gdn.py``) against each other and
against the benchmark's plain one-token recurrence
(``benchmark/models/qwen3_next.py:recurrence``): float32, CPU."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.models.qwen3_next import recurrence  # noqa: E402

from deepspeed_tpu.ops import gdn  # noqa: E402

HK, HV, DK, DV = 2, 4, 16, 8
TOL = 2e-5


def _inputs(key, n, alike: float = 0.0, dims=(HK, HV, DK, DV), identical: bool = False):
    """What the recurrence consumes for ``n`` tokens: unit keys (``alike``: the
    share of one common direction in every key, the case the solve has to be
    stable in), queries of length Dk^-1/2, log decays from ~0 to ~-3.
    ``identical``: every key the SAME direction, beta 0.999, decay ~1 (``I + A``
    is then ~all ones under its diagonal: the solve's worst case)."""
    hk, hv, dk, dv = dims
    ks = jax.random.split(key, 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    common = jax.random.normal(ks[5], (1, hk, dk))
    q = unit(jax.random.normal(ks[0], (n, hk, dk))) * dk ** -0.5
    k = unit((1 - alike) * jax.random.normal(ks[1], (n, hk, dk)) + alike * 4 * common)
    v = jax.random.normal(ks[2], (n, hv, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (n, hv), minval=-6.0, maxval=1.0))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (n, hv)))
    if identical:
        k, g, beta = jnp.broadcast_to(unit(common), k.shape), jnp.full_like(g, -1e-4), jnp.full_like(beta, 0.999)
    return q, k, v, g, beta


# the two forms and the reference as ONE program a shape each: called op by op,
# every slice, pad and scan of theirs compiles on its own
_inputs = jax.jit(_inputs, static_argnames=("n", "alike", "dims", "identical"))
gdn_scan, gdn_step, recurrence = jax.jit(gdn.gdn_scan), jax.jit(gdn.gdn_step), jax.jit(recurrence)


def _chunked(seqs, chunk, loaded=None):
    """Sequences (tuples of per-token inputs) laid out as ``gdn_scan`` takes
    them: chunks of ``chunk`` rows, each sequence starting on a chunk, the last
    chunk of each padded with rows of g = beta = 0.  Returns (arrays [G, L,
    ...], valid [G, L], cont [G], the chunk each sequence ends in)."""
    parts, valid, cont, ends = [[] for _ in range(5)], [], [], []
    for seq in seqs:
        n = seq[0].shape[0]
        c = -(-n // chunk)
        for a, out in zip(seq, parts):
            a = jnp.pad(a, ((0, c * chunk - n),) + ((0, 0),) * (a.ndim - 1))
            out.append(a.reshape(c, chunk, *a.shape[1:]))
        valid.append((jnp.arange(c * chunk) < n).reshape(c, chunk))
        cont += [False] + [True] * (c - 1)
        ends.append(len(cont) - 1)
    return [jnp.concatenate(p) for p in parts], jnp.concatenate(valid), jnp.asarray(cont), ends


def _scan_against_the_recurrence(seqs, chunk):
    """Sequences sharing one ``gdn_scan`` call: the largest distance of its
    outputs and of its last states from the reference's recurrence."""
    (_, hv, dv), dk = seqs[0][2].shape, seqs[0][1].shape[-1]
    arrays, valid, cont, ends = _chunked(seqs, chunk)
    zeros = jnp.zeros((len(cont), hv, dk, dv))
    o, states = gdn_scan(*arrays, zeros, cont)
    o = np.asarray(o).reshape(-1, hv, dv)
    at, far_o, far_s = 0, 0.0, 0.0
    for seq, end in zip(seqs, ends):
        n = seq[0].shape[0]
        want_o, want_s = recurrence(*(a[None] for a in seq))
        far_o = max(far_o, np.abs(o[at:at + n] - np.asarray(want_o[0])).max())
        far_s = max(far_s, np.abs(np.asarray(states[end]) - np.asarray(want_s[0])).max())
        at += -(-n // chunk) * chunk
    return far_o, far_s


@pytest.mark.parametrize("chunk", [4, 8, 16])
@pytest.mark.parametrize("alike", [0.0, 0.9], ids=["keys_random", "keys_alike"])
def test_chunked_scan_is_the_recurrence(chunk, alike):
    """Two sequences of unequal length sharing one call (chunk edges inside
    both, padding rows at both ends): outputs and last states equal the
    reference's token-by-token recurrence."""
    seqs = [_inputs(jax.random.PRNGKey(i), n, alike) for i, n in enumerate((37, 21))]
    assert max(_scan_against_the_recurrence(seqs, chunk)) <= TOL


SERVED = (1, 2, 128, 128)  # the served head widths (Dk = Dv = 128), two value heads on one key head


@pytest.mark.parametrize("chunk", [32, 64, 128])
@pytest.mark.parametrize("keys", ["keys_random", "keys_alike", "keys_identical"])
def test_chunked_scan_by_blocks_is_the_recurrence(chunk, keys):
    """Chunks of more than ``SOLVE_BLOCK`` rows (the inverse merged from
    inverted diagonal blocks: 1, 2 and 3 levels) at the served widths, two
    sequences with chunk edges inside both and padding at both ends."""
    assert chunk // gdn.SOLVE_BLOCK in (2, 4, 8)
    kw = {"keys_random": {}, "keys_alike": {"alike": 0.9}, "keys_identical": {"identical": True}}[keys]
    seqs = [_inputs(jax.random.PRNGKey(i), n, dims=SERVED, **kw) for i, n in enumerate((300, 150))]
    assert max(_scan_against_the_recurrence(seqs, chunk)) <= TOL


def test_the_inverse_by_blocks_solves_the_triangular_system():
    """``(I + A)^-1 @ rhs`` by blocks is ``jax.lax.linalg.triangular_solve``'s answer on
    the same ``A`` and ``rhs``, for a chunk of 128 alike keys that do not decay."""
    _, k, v, _, beta = _inputs(jax.random.PRNGKey(9), 128, alike=0.9, dims=SERVED)
    k = jnp.moveaxis(k, 0, 1)                                          # [Hk, L, Dk]
    a = jnp.tril(beta.T[..., None] * (k @ jnp.swapaxes(k, 1, 2)), -1)  # [Hv, L, L], at decay 1
    rhs = beta.T[..., None] * jnp.moveaxis(v, 0, 1)
    want = jax.lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True, unit_diagonal=True)
    got = gdn._solve_unit_lower(a, rhs)
    assert np.abs(np.asarray(got - want)).max() <= 1e-6 * np.abs(np.asarray(want)).max()


def test_a_state_handed_from_pack_to_pack():
    """A sequence scanned in two calls, the second loading the state the first
    left (``loaded``, not ``cont``): the same state and outputs as one call."""
    seq = _inputs(jax.random.PRNGKey(3), 48)
    arrays, _, cont, _ = _chunked([seq], 8)
    zeros = jnp.zeros((6, HV, DK, DV))
    o_all, s_all = gdn_scan(*arrays, zeros, cont)
    first = [a[:2] for a in arrays]
    _, s1 = gdn_scan(*first, zeros[:2], cont[:2])
    loaded = zeros[:4].at[0].set(s1[-1])
    o2, s2 = gdn_scan(*[a[2:] for a in arrays], loaded, jnp.asarray([False, True, True, True]))
    assert np.abs(np.asarray(s2[-1] - s_all[-1])).max() <= TOL
    assert np.abs(np.asarray(o2 - o_all[2:])).max() <= TOL


def test_padding_rows_leave_the_state_as_it_was():
    """A chunk of padding only (g = beta = 0) hands its incoming state on
    unchanged, whatever q, k and v hold there."""
    seq = _inputs(jax.random.PRNGKey(4), 8)
    arrays = [a[None] for a in seq]
    arrays[3], arrays[4] = jnp.zeros_like(arrays[3]), jnp.zeros_like(arrays[4])
    loaded = jax.random.normal(jax.random.PRNGKey(5), (1, HV, DK, DV))
    _, states = gdn_scan(*arrays, loaded, jnp.asarray([False]))
    assert np.abs(np.asarray(states - loaded)).max() <= 1e-6


def test_the_step_token_by_token_is_the_recurrence_and_spares_idle_slots():
    """``gdn_step`` over a sequence in slot 1 of 3 equals the recurrence; the
    other slots' states come back bit-identical, in whatever dtype they are
    kept."""
    seq = _inputs(jax.random.PRNGKey(6), 19)
    want_o, want_s = recurrence(*(a[None] for a in seq))
    noise = jax.random.normal(jax.random.PRNGKey(7), (3, HV, DK, DV))
    active = jnp.asarray([False, True, False])
    for dtype, tol in ((jnp.float32, TOL), (jnp.bfloat16, 0.1)):
        s = noise.at[1].set(0.0).astype(dtype)
        outs = []
        for t in range(19):
            rows = [jnp.broadcast_to(a[t], (3, *a.shape[1:])) for a in seq]
            o, s = gdn_step(s, *rows, active)
            outs.append(o[1])
        assert s.dtype == dtype
        assert np.array_equal(np.asarray(s[0::2].astype(jnp.float32)),
                              np.asarray(noise[0::2].astype(dtype).astype(jnp.float32)))
        assert np.abs(np.asarray(jnp.stack(outs) - want_o[0])).max() <= tol
        assert np.abs(np.asarray(s[1].astype(jnp.float32) - want_s[0])).max() <= tol


def test_the_scan_and_the_step_agree_across_a_pack_and_its_ticks():
    """A prompt scanned in chunks, then decode steps from the state it left:
    the same as the recurrence over all of it."""
    seq = _inputs(jax.random.PRNGKey(8), 29)
    head = [a[:24] for a in seq]
    arrays, _, cont, _ = _chunked([head], 8)
    _, states = gdn_scan(*arrays, jnp.zeros((3, HV, DK, DV)), cont)
    s = states[-1][None]
    for t in range(24, 29):
        o, s = gdn_step(s, *(a[t][None] for a in seq), jnp.asarray([True]))
    want_o, want_s = recurrence(*(a[None] for a in seq))
    assert np.abs(np.asarray(o[0] - want_o[0, -1])).max() <= TOL
    assert np.abs(np.asarray(s[0] - want_s[0])).max() <= TOL
