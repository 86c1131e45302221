"""The trace reduction: busy union, self times, idle gaps by host span, kernel
time by name - on hand-made events and on a small trace recorded on the chip."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness, xplane  # noqa: E402
from benchmark.xplane import HostEvent, Op, Trace  # noqa: E402


def hand_made():
    # device 0: a `while` [1.0, 3.0] holding two kernels, then a lone fusion;
    # idle [0, 1), [3, 4), [4.5, 5)
    ops = [Op("while", 1.0, 3.0), Op("custom-call bf16[64,32,128]", 1.2, 1.7),
           Op("custom-call bf16[64,32,128]", 2.0, 2.5), Op("fusion bf16[64,14336]", 4.0, 4.5)]
    xplane._self_times(ops)
    host = [HostEvent("bench.capture", 0.0, 5.0, {}),
            HostEvent("bench.tick", 0.0, 3.5, {"tick": 0}),
            HostEvent("shard_args", 0.0, 0.4, {}),       # innermost at t=0
            HostEvent("bench.tick", 3.6, 5.0, {"tick": 1})]
    return Trace((0.0, 5.0), {0: ops}, host)


def test_merge_and_union():
    assert xplane.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.union_len([(0, 2.5), (3, 4)]) == 3.5


def test_busy_is_a_union_not_a_sum():
    tr = hand_made()
    assert tr.busy_s() == pytest.approx(2.5)      # while 2.0 + fusion 0.5
    assert tr.idle_share() == pytest.approx(0.5)


def test_self_time_charges_children_to_their_parent():
    secs = hand_made().op_seconds()
    assert secs["while"] == pytest.approx(1.0)     # 2.0 minus two 0.5 kernels
    assert secs["custom-call bf16[64,32,128]"] == pytest.approx(1.0)
    assert sum(secs.values()) == pytest.approx(2.5)


def test_kernel_time_by_name():
    secs, calls = hand_made().kernel_seconds(r"^custom-call bf16\[\d+,32,128\]$")
    assert (secs, calls) == (pytest.approx(1.0), 2)
    assert hand_made().kernel_seconds("nothing")[1] == 0


def test_idle_gaps_named_by_the_innermost_host_span():
    gaps = hand_made().idle_gaps()
    assert gaps["shard_args"] == pytest.approx(1.0)   # [0, 1): innermost at 0
    # [3, 4) begins inside tick 0 (which ends at 3.5); [4.5, 5) inside tick 1
    assert gaps["bench.tick"] == pytest.approx(1.5)
    assert sum(gaps.values()) == pytest.approx(2.5)


def test_gap_with_no_host_span_is_named_so():
    tr = Trace((0.0, 2.0), {0: [Op("fusion", 1.0, 2.0, 1.0)]},
               [HostEvent("bench.capture", 0.0, 2.0, {})])
    assert tr.idle_gaps() == {xplane.NO_HOST: pytest.approx(1.0)}


def test_breakdown_lists_are_sorted_and_capped():
    b = xplane.breakdown(hand_made(), top=2)
    assert b["device_ops"][0][1] >= b["device_ops"][1][1]
    assert b["idle_gaps"][0] == ["bench.tick", pytest.approx(1.5)]
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) <= 2


@pytest.mark.parametrize("name,key", [
    ("%fusion.123 = bf16[64,14336]{1,0:T(8,128)(2,1)} fusion(bf16[64,4096]{1,0} %p0, %p1), kind=kOutput",
     "fusion bf16[64,14336]"),
    ("packed_ctx_impl.28 = (f32[256,32,128]{2,1,0:T(8,128)S(1)}, f32[256,32]{1,0:T(8,128)}, "
     "f32[256,32]{1,0:T(8,128)S(1)}) custom-call(s32[64,128]{1,0:T(8,128)S(1)} %copy-done.48)",
     "custom-call packed_ctx_impl (f32[256,32,128],f32[256,32],f32[256,32])"),
    ("decode_impl.18 = bf16[64,32,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[64]{0:T(128)S(1)} %c)",
     "custom-call decode_impl bf16[64,32,128]"),
    ("%custom-call.7 = bf16[32,4096,128]{2,1,0} custom-call(%a)", "custom-call bf16[32,4096,128]"),
    ("slice_bitcast_fusion = (bf16[4096,4096]{0,1:T(8,128)(2,1)}, /*index=1*/bf16[4096,4096]{0,1}) fusion(%x)",
     "fusion slice_bitcast_fusion (bf16[4096,4096],bf16[4096,4096])"),
    ("%all-gather-start.2 = (bf16[1024,4096]{1,0}, bf16[4096,4096]{1,0}) all-gather-start(%x)",
     "all-gather-start (bf16[1024,4096],bf16[4096,4096])"),
    ("slice-start.2 = ((bf16[512,1024]{1,0:T(8,128)(2,1)}), bf16[128,1024]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) "
     "async-start(bf16[512,1024]{1,0} %x), calls=%async", "async-start slice-start ((bf16[512,1024]),bf16[128,1024],s32[])"),
    ("copy.3", "copy"),
    ("jit_decode_impl(13831262894102790010)", "jit_decode_impl(13831262894102790010)"),
])
def test_op_key_survives_renumbering(name, key):
    assert xplane.op_key(name) == key


RECORDED = harness.HERE / "testdata" / "small_tpu_v5e.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """Four ticks of one small jitted program (two matmuls, a tanh, the
    program's flash kernel) recorded on a TPU v5e by
    ``benchmark/tools/record_small_trace.py`` (PR 23)."""
    assert RECORDED.is_file()
    return xplane.reduce_trace(xplane.load(str(RECORDED)))


FLASH_FWD = r"^custom-call (\S+ )?\(bf16\[\d+,\d+,128\],f32\[\d+,\d+,1\]\)$"


def test_recorded_window_is_the_capture_annotation(recorded):
    assert sorted(recorded.devices) == [0]
    assert 0.008 < recorded.window_s < 0.1          # four ticks + four 2 ms sleeps
    ticks = recorded.host_spans("bench.tick")
    assert [int(h.stats["tick"]) for h in ticks] == [0, 1, 2, 3]
    assert all(recorded.window[0] <= h.start and h.end <= recorded.window[1] for h in ticks)


def test_recorded_kernel_time_by_name(recorded):
    secs, calls = recorded.kernel_seconds(FLASH_FWD)
    # one flash forward a tick; the device ran the fourth tick's program after
    # the capture closed (its clock trails the host's by ~2 ms), so it is clipped
    assert calls == 3
    assert 3 * 5e-6 < secs < 3 * 1e-4                 # 18.8 us a call on the v5e
    assert recorded.kernel_seconds(r"^custom-call nothing")[1] == 0


def test_recorded_busy_union_and_self_times_agree(recorded):
    busy = recorded.busy_s()
    assert 0 < busy < 0.05 * recorded.window_s        # tiny ops, long host sleeps
    # ops on one device line only nest, so self times partition the busy union
    assert sum(recorded.op_seconds().values()) == pytest.approx(busy, rel=1e-6)
    assert recorded.idle_share() == pytest.approx(1 - busy / recorded.window_s)


def test_recorded_idle_gaps_cover_the_rest_of_the_window(recorded):
    gaps = recorded.idle_gaps()
    assert sum(gaps.values()) == pytest.approx(recorded.window_s - recorded.busy_s(), rel=1e-6)
    # the device ran each tick's program during the host's sleep between ticks
    # (device and host clocks differ by ~2 ms here), where no annotation is open
    assert max(gaps, key=gaps.get) == xplane.NO_HOST


def test_recorded_breakdown_names_the_kernel_first(recorded):
    b = xplane.breakdown(recorded)
    assert b["device_ops"][0][0] == "custom-call tick (bf16[8,512,128],f32[8,512,1])"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


# -- roofline readers on hand-made traces ------------------------------------
MISTRAL_2L = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
              "num_hidden_layers": 2}
V5E = {"platform": "tpu", "kind": "TPU v5 lite"}


def test_flash_roofline_counts_needed_work_not_the_remat_rerun():
    from benchmark.readers import flash_roofline

    # 10 steps x 2 layers; a layer-step spends fwd 1.4 + rerun fwd 1.3 + dkv 2.4 + dq 1.7 ms
    kinds = (("custom-call closed_call (bf16[32,4096,128],f32[32,4096,1])", 1.4e-3),
             ("custom-call rematted_computation (bf16[32,4096,128],f32[32,4096,1])", 1.3e-3),
             ("custom-call checkpoint (bf16[32,4096,128],bf16[32,4096,128])", 2.4e-3),
             ("custom-call checkpoint bf16[32,4096,128]", 1.7e-3),
             ("fusion bf16[4096,14336]", 43.2e-3))
    ops, t = [], 0.0
    for _ in range(20):
        for name, d in kinds:
            ops.append(Op(name, t, t + d, d))
            t += d
    spec = harness.load_json(harness.HERE / "metrics" / "flash_roofline.train.json")
    obs = {"trace": Trace((0.0, t), {0: ops}, []), "device": V5E, "model": MISTRAL_2L,
           "micro": 1, "seq": 4096, "steps": 100, "window": (0.0, 10 * t)}
    got = flash_roofline.read(obs, **spec["params"])
    fwd = 4 * 32 * 128 * (4096 * 4097 // 2) / 197e12          # 0.698 ms, FLOP-bound
    assert got == pytest.approx(100 * (fwd + 2.5 * fwd) / 6.8e-3, rel=1e-3)
    assert 30 < got < 40
    assert flash_roofline.read(dict(obs, trace=None), **spec["params"]) is None


def test_paged_decode_roofline_reads_the_traced_ticks():
    from benchmark.readers import paged_decode_roofline

    # two ticks traced whole (0 and 1), one cut by the window's end (2)
    host = [HostEvent("bench.capture", 0.0, 1.0, {}),
            HostEvent("bench.tick", 0.1, 0.2, {"tick": 0}),
            HostEvent("bench.tick", 0.3, 0.4, {"tick": 1}),
            HostEvent("bench.tick", 0.9, 1.1, {"tick": 2})]
    ops = [Op("custom-call decode_impl bf16[64,32,128]", 0.1, 0.101, 1e-3),
           Op("custom-call decode_impl bf16[64,32,128]", 0.3, 0.301, 1e-3)]
    obs = {"trace": Trace((0.0, 1.0), {0: ops}, host), "device": V5E,
           "model": dict(MISTRAL_2L, num_hidden_layers=1),
           "ticks": [(0, 0, 2, 3000, 2, 0), (0, 0, 2, 5000, 2, 0), (0, 0, 2, 9000, 2, 0)]}
    spec = harness.load_json(harness.HERE / "metrics" / "paged_decode_roofline.chat.json")
    need = sum(2 * (2 * 8 * 128 * ctx) + 2 * 2 * (2 * 32 * 128) for ctx in (3000, 5000)) / 819e9
    assert paged_decode_roofline.read(obs, **spec["params"]) == pytest.approx(100 * need / 2e-3)
