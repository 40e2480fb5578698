"""The trace reduction, on a small recorded trace (300 ms of the chat
cell on a TPU v5e, cut from a chip run of PR 23) and on synthetic events."""

import os

import pytest

from benchlib import tracefile

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chat_300ms.xplane.pb")


@pytest.mark.parametrize("text,kind,shape", [
    ("%fusion.277 = bf16[16,18176]{1,0:T(8,128)(2,1)} fusion(bf16[16,4544]"
     "{1,0} %x), kind=kOutput", "fusion", "bf16[16,18176]"),
    ("%convert_bitcast_fusion.14 = f32[8,1,71,64]{3,1,0,2:T(8,128)S(1)} "
     "fusion(bf16[71,64] %y)", "convert_bitcast_fusion", "f32[8,1,71,64]"),
    ("%while.3 = (s32[], bf16[16,4544]{1,0}) while((s32[], bf16[16,4544]) "
     "%t)", "while", "(s32[],bf16[16,4544])"),
    ("%broadcast.62.clone = f32[4,2048]{1,0} broadcast(f32[] %c)",
     "broadcast", "f32[4,2048]"),
    ("%all-reduce-start.5 = bf16[8,8192]{1,0} all-reduce-start(bf16[8,8192]"
     " %p)", "all-reduce-start", "bf16[8,8192]"),
])
def test_names_lose_xlas_serial_numbers(text, kind, shape):
    assert tracefile.op_kind(text) == kind
    assert tracefile.out_shape(text) == shape


def test_program_names_lose_their_hash():
    assert tracefile.program_name(
        "jit_decode_fn(7155025908650796737)") == "decode_fn"
    assert tracefile.program_name("jit_step_fn(11)") == "step_fn"


def test_flash_kernels_are_told_apart_by_their_outputs():
    call = ' custom-call(bf16[4,71,2048,64] %q), ' \
           'custom_call_target="tpu_custom_call"'
    fwd = "%closed_call.9 = (bf16[4,71,2048,64]{3,2,1,0}, " \
          "f32[4,71,2048,128]{3,2,1,0})" + call
    dkv = "%checkpoint.21 = (bf16[4,71,2048,64]{3,2,1,0}, " \
          "bf16[4,71,2048,64]{3,2,1,0})" + call
    dq = "%checkpoint.20 = bf16[4,71,2048,64]{3,2,1,0}" + call
    alloc = '%custom-call.5 = bf16[16,1,1025,1,64]{2,4,3,1,0} ' \
            'custom-call(), custom_call_target="AllocateBuffer"'
    assert [tracefile.flash_kind(t) for t in (fwd, dkv, dq, alloc)] == [
        "flash_fwd", "flash_dkv", "flash_dq", None]
    assert tracefile.flash_kind("%fusion.1 = bf16[4,71,2048,64] fusion()") \
        is None


def test_busy_union_idle_and_gap_names_on_synthetic_events():
    ms = 1e6
    dev = {"modules": [("jit_prefill_fn(1)", 0, 30 * ms),
                       ("jit_decode_fn(2)", 50 * ms, 40 * ms)],
           "ops": [("%while.1 = (s32[]) while(%t)", 50 * ms, 40 * ms),
                   ("%fusion.1 = bf16[8,8]{1,0} fusion(%a)", 0, 10 * ms),
                   ("%fusion.2 = bf16[8,8]{1,0} fusion(%a)", 5 * ms, 10 * ms),
                   ("%copy.7 = bf16[4]{0} copy(%b)", 20 * ms, 10 * ms),
                   ("%fusion.9 = bf16[2,2]{1,0} fusion(%c)", 60 * ms, 20 * ms)],
           "async_ops": []}
    r = tracefile.reduce_planes([dev], 0, 100 * ms)
    # union: [0,15] + [20,30] + [60,80] = 45 ms; the while is not a leaf.
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["groups"]["prefill_fn/fusion/bf16[8,8]"] == {
        "seconds": pytest.approx(0.020), "count": 2}
    assert "decode_fn/while/(s32[])" not in r["groups"]
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["idle/before_prefill_fn"] == pytest.approx(0.005)
    assert gaps["idle/before_decode_fn"] == pytest.approx(0.030)
    assert gaps["idle/at_the_end_of_the_window"] == pytest.approx(0.020)
    assert r["programs"]["decode_fn"] == {"seconds": pytest.approx(0.040),
                                          "launches": 1}


def test_collective_time_is_exposed_where_no_compute_runs():
    ms = 1e6
    dev = {"modules": [("jit_prefill_fn(1)", 0, 100 * ms)],
           "ops": [("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 40 * ms),
                   ("%all-reduce.3 = bf16[8]{0} all-reduce(%a)", 40 * ms,
                    10 * ms),
                   ("%fusion.2 = bf16[8]{0} fusion(%a)", 60 * ms, 20 * ms)],
           "async_ops": [("%all-gather-start.1 = bf16[8]{0} "
                          "all-gather-start(%a)", 55 * ms, 15 * ms)]}
    r = tracefile.reduce_planes([dev, dev], 0, 100 * ms)
    assert r["devices"] == 2
    assert r["collective_s"] == pytest.approx(0.025)
    # sync all-reduce wholly exposed (10) + the async one before compute
    # resumes at 60 (5).
    assert r["collective_exposed_s"] == pytest.approx(0.015)


def test_recorded_trace_reduces_to_the_values_read_by_hand():
    devices, t_lo, t_hi = tracefile.read_xplane(FIXTURE)
    assert len(devices) == 1
    r = tracefile.reduce_planes(devices, t_lo, t_hi)
    assert r["window_s"] == pytest.approx(0.299641859, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.244881276, rel=1e-6)
    idle = 1 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.18275, abs=1e-4)
    assert r["programs"]["prefill_fn"]["launches"] == 3
    assert r["programs"]["decode_fn"] == {
        "seconds": pytest.approx(0.124639537, rel=1e-6), "launches": 1}
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert names[0] == "decode_fn/fusion/bf16[16,4544]"
    assert all("." not in n.split("/")[1] and "(" not in n.split("/")[0]
               for n in r["groups"])
    assert any(k["kernel"] == "flash_fwd" and k["program"] == "prefill_fn"
               for k in r["kernels"])
    assert len(r["breakdown"]["device_ops"]) <= 10
