"""The yardstick of a model of gated GQA of two kinds (full layers on K / V
pages, window layers on K / V rings, every expert held): ``costs_window.py``'s
needed work at the published widths, the reader that divides it by a body's
time, and the serving driver's replay held to the reference: logits, the
window's edge exactly, the kept ring; each control shows where it should."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import costs, costs_window, harness  # noqa: E402
from benchmark.drivers import serve_windowed  # noqa: E402
from benchmark.readers import counter_ratio, window_roofline  # noqa: E402

M = harness.load_json(harness.HERE / "configs" / "laguna_xs2_l5_serve_1chip.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_configuration_holds_two_full_layers_of_48_heads_and_three_window_layers_of_64():
    assert costs_window.heads_of(M, "full_attention") == (48, 2)
    assert costs_window.heads_of(M, "sliding_attention") == (64, 3)
    assert costs_window.heads_of(dict(M, num_hidden_layers=40), "full_attention") == (48, 10)
    assert costs_window.heads_of(dict(M, num_hidden_layers=40), "sliding_attention") == (64, 30)


def test_a_pair_costs_four_flops_a_head_and_dim_and_rows_are_read_once():
    fl, by = costs_window.attention(1000, 10, 100, M, "full_attention")
    assert fl == 4.0 * 48 * 128 * 1000
    assert by == 2.0 * 128 * (2 * 48 * 10 + 2 * 8 * 100)  # q in + o out; K rows + V rows
    wfl, wby = costs_window.attention(1000, 10, 100, M, "sliding_attention")
    assert wfl == fl * 64 / 48 and wby > by
    # a 512-token chunk over 8k cached keys, one full layer: compute bounds it
    pairs = costs.causal_pairs(512, 8192)
    fl, by = costs_window.attention(pairs, 512, 8192 + 512, M, "full_attention")
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(fl / 197e12)
    # a window layer's chunk needs 512 keys a query whatever lies under it
    wpairs = 512 * 512
    assert wpairs < pairs / 8


def test_the_readers_have_nothing_to_read_without_a_trace_or_their_counters():
    obs = {"trace": None, "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "ticks": []}
    assert window_roofline.read(obs, "^jit_packed(_ctx)?_impl$", "full_attn", "full_attn") is None
    share = dict(num="window_keys_attended", den="causal_keys", scale=100.0)
    assert counter_ratio.read({}, **share) is None
    assert counter_ratio.read({"counters": {"window_keys_attended": 5, "causal_keys": 0}},
                              **share) is None
    assert counter_ratio.read({"counters": {"window_keys_attended": 5, "causal_keys": 100}},
                              **share) == 5.0


def test_the_traced_packs_are_the_spans_inside_the_traced_ticks():
    class Trace:
        def whole_spans(self, name, key):
            return [1, 2]

    obs = {"trace": Trace(), "ticks": [(0.0, 1.0), (1.0, 2.0), (2.0, 3.0), (3.0, 4.0)],
           "spans": [("prefill_pack", 0.2, 0.8, {"tokens": 1}),
                     ("prefill_pack", 1.1, 1.9, {"tokens": 2}),
                     ("decode_tick", 1.9, 2.0, {"batch": 3}),
                     ("prefill_pack", 2.2, 2.9, {"tokens": 4}),
                     ("prefill_pack", 2.95, 3.5, {"tokens": 5})]}
    assert [a["tokens"] for a in window_roofline._traced_pack_args(obs)] == [2, 4]


def test_every_control_names_what_the_comparison_reads():
    arch = harness.module("models", M["model_type"])
    assert set(arch.DEPARTURES) < set(serve_windowed.CONTROLS)
    assert {"fp8_weights", "served_tokens_swapped"} < set(serve_windowed.CONTROLS)
    with pytest.raises(ValueError, match="no departure"):
        with arch.departure("no_such_thing"):
            pass


@pytest.fixture(scope="module")
def replayed():
    """The driver's replay against the reference at the rehearsal size
    (float32; window 12, a ring of 48 rows): prompts of 3 and 2 chunks, 6 fed
    tokens each; per request (logits' max |d|, the kept ring's error, window
    queries checked, those that saw other keys)."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.transformer import init_params

    m = harness.rehearsed(M, True)
    arch = harness.module("models", m["model_type"])
    e = m["engine"]
    cfg = arch.transformer_config(m, max_seq_len=e["max_seq_len"])
    eng = InferenceEngineV2(
        init_params(jax.random.PRNGKey(3), cfg), cfg, max_seqs=e["max_seqs"],
        num_blocks=e["num_blocks"], block_size=e["block_size"], max_seq_len=e["max_seq_len"],
        prefill_buckets=(e["prefill_chunk"],), prefill_chunk=e["prefill_chunk"])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (75, 41)]
    fed = [rng.integers(0, cfg.vocab_size, 6).tolist() for _ in prompts]
    replay = serve_windowed._Replay(jax, np, eng, cfg)
    schedule = serve_windowed._alone(prompts, fed, e["prefill_chunk"])
    k, window = m["num_experts_per_tok"], m["sliding_window"]
    replays = replay(prompts, fed, schedule)

    def against_reference(departing=None):
        out = []
        for (got, probes, kept, edges), p, f in zip(replays, prompts, fed):
            buf = np.zeros((1, 88), np.int32)
            buf[0, :len(p) + len(f)] = p + f
            forced = serve_windowed._forced(np, probes, 88, k)
            if departing is None:
                lg, seen = arch.probe(eng.params, buf, m, forced, at=len(p) - 1, rows=len(f) + 1)
            else:
                with arch.departure(departing):
                    lg, seen = arch.probe(eng.params, buf, m, forced, at=len(p) - 1,
                                          rows=len(f) + 1)
            seen = [{key: np.asarray(v[0]) for key, v in layer.items()} for layer in seen]
            assert np.asarray(lg).shape == (1, len(f) + 1, m["vocab_size"])
            d = float(np.abs(got - np.asarray(lg)[0]).max())
            mine, theirs = serve_windowed._ring_refs(np, kept, seen, len(p) + len(f), window)
            assert len(mine) == len(theirs) == 6 and mine[0].shape == (window, 2, 16)
            out.append((d, serve_windowed._state_error(np, mine, theirs),
                        *serve_windowed._edge_misses(np, edges, seen)))
        return out

    yield against_reference
    eng.close()


def test_the_replays_logits_edges_and_kept_rings_are_the_references(replayed):
    for d, ring_off, n_edge, edge_wrong in replayed():
        assert d < 1e-4 and ring_off < 1e-5 and n_edge > 0 and edge_wrong == 0


@pytest.mark.parametrize("name", ["no_window", "window_off_by_one"])
def test_a_departure_of_the_windows_edge_shows_in_the_mask_exactly(replayed, name):
    """One key more than the window holds: every query past the window saw
    another set of keys than the reference's mask allows, whatever the logits say."""
    for (_, _, n_edge, wrong), n in zip(replayed(departing=name), (75 + 6, 41 + 6)):
        assert wrong == 3 * (n - 12) and n_edge == 3 * n


@pytest.mark.parametrize("name", ["rotary_sets_swapped", "no_yarn", "no_output_gate",
                                  "routing_not_scaled"])
def test_a_departure_of_the_mathematics_shows_in_the_logits(replayed, name):
    """Each control of the mathematics moves the reference away from the
    program by far more than rounding, and leaves the window's edge alone."""
    for d, _, _, wrong in replayed(departing=name):
        assert d > 1e-2 and wrong == 0


def test_swapped_rotary_tables_show_in_the_kept_ring_too(replayed):
    for _, ring_off, _, _ in replayed(departing="rotary_sets_swapped"):
        assert ring_off > 0.1
