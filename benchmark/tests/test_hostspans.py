"""hostspans arithmetic on synthetic intervals, then on a small capture of
the real server on the chip (fixtures/chat_spans_300ms.xplane.pb, PR 24):
the idle shares by span add up to tracefile's idle share, the scope shares
to 100 % less the unscoped share."""

import os

import pytest

from benchlib import hostspans, spanread, tracefile

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chat_spans_300ms.xplane.pb")
MS = 1e6   # ns


def op(kind, start_ms, dur_ms, shape="bf16[8,8]{1,0}"):
    return (f"%{kind}.1 = {shape} {kind}(%p)", start_ms * MS, dur_ms * MS)


def device(ops, op_names=None, modules=()):
    return {"ops": ops, "op_names": op_names or [""] * len(ops),
            "modules": list(modules)}


def test_flatten_names_each_stretch_by_the_innermost_span():
    spans = [("tick", 0, 100), ("tick.admit", 5, 20), ("prefill", 8, 18),
             ("prefill.sync", 10, 15), ("decode", 30, 90),
             ("decode.dispatch", 40, 50), ("worker.finish", 100, 110)]
    assert hostspans.flatten(spans) == [
        (0, 5, "tick"), (5, 8, "tick.admit"), (8, 10, "prefill"),
        (10, 15, "prefill.sync"), (15, 18, "prefill"),
        (18, 20, "tick.admit"), (20, 30, "tick"), (30, 40, "decode"),
        (40, 50, "decode.dispatch"), (50, 90, "decode"), (90, 100, "tick"),
        (100, 110, "worker.finish")]
    assert hostspans.flatten([]) == []


def test_idle_goes_to_the_innermost_span_and_shares_sum_to_the_idle_share():
    # Busy 10-40 and 60-90 of a 0-100 ms window: idle 0-10, 40-60, 90-100.
    dev = device([op("fusion", 10, 30), op("fusion", 60, 30),
                  op("while", 10, 80)])   # an enclosing op is not work
    spans = [(n, s * MS, e * MS) for n, s, e in [
        ("worker.idle", 0, 8), ("tick", 8, 58), ("decode", 9, 57),
        ("decode.sync", 12, 45), ("decode.replay", 45, 57),
        ("worker.finish", 58, 59), ("tick", 59, 95),
        ("decode.operands", 59, 60)]]
    red = hostspans.reduce_capture([dev], spans, 0.0, 100 * MS)
    by = {k: round(v * 1e3, 6) for k, v in red["idle_by_span"].items()}
    assert by == {"worker.idle": 8.0, "tick": 7.0, "decode": 1.0,
                  "decode.sync": 5.0, "decode.replay": 12.0,
                  "worker.finish": 1.0, "decode.operands": 1.0,
                  "unnamed": 5.0}
    fam = {k: round(v * 1e3, 6) for k, v in red["idle_by_family"].items()}
    assert fam == {"no_work": 8.0, "bookkeeping": 21.0, "runtime": 5.0,
                   "operands": 1.0, "unnamed": 5.0}
    trace = tracefile.reduce_planes(
        [{"modules": [], "ops": dev["ops"]}], 0.0, 100 * MS)
    assert sum(red["idle_by_family"].values()) == pytest.approx(
        trace["window_s"] - trace["busy_s"])
    assert red["idle_s"] == pytest.approx(0.040)
    assert red["longest_gaps"][0]["seconds"] == pytest.approx(0.020)
    assert set(red["longest_gaps"][0]["spans"]) == {
        "decode.replay", "decode.sync", "tick"}


def test_several_chips_are_reduced_apart_and_averaged():
    spans = [("tick", 0.0, 100 * MS)]
    busy = device([op("fusion", 0, 100)])
    half = device([op("fusion", 0, 50)])
    red = hostspans.reduce_capture([busy, half], spans, 0.0, 100 * MS)
    assert red["devices"] == 2
    assert red["idle_by_family"] == {"bookkeeping": pytest.approx(0.025)}
    assert red["idle_s"] == pytest.approx(0.025)


def test_scopes_innermost_for_the_table_any_depth_for_the_shares():
    names = ["jit(decode_fn)/jit(main)/while/body/block/attn/attn.core/dot",
             "jit(step_fn)/jit(main)/transpose(jvp(block))/ffn/dot_general",
             "jit(decode_fn)/jit(main)/while/body/block/add",
             "jit(decode_fn)/jit(main)/copy", ""]
    dev = device([op("fusion", 10 * i, 10) for i in range(5)], names,
                 modules=[("jit_decode_fn(123)", 0.0, 50 * MS)])
    red = hostspans.reduce_capture([dev], [], 0.0, 50 * MS)
    assert hostspans.scope_stack(names[1]) == ["block", "ffn"]
    assert red["scope_s"] == {"attn.core": 0.01, "ffn": 0.01, "block": 0.01,
                              "unscoped": 0.02}
    assert red["under_scope_s"]["attn"] == 0.01
    assert red["under_scope_s"]["block"] == 0.03
    assert red["program_scope_s"]["decode_fn/attn.core"] == 0.01
    assert red["has_scopes"] and not red["has_spans"]
    assert sum(red["scope_s"].values()) == pytest.approx(red["op_s"])


def test_nothing_to_read_gives_nothing(tmp_path, monkeypatch):
    red = hostspans.reduce_capture([device([op("fusion", 0, 10)])], [],
                                   0.0, 10 * MS)
    assert not red["has_spans"] and not red["has_scopes"]
    assert red["idle_by_family"] == {}
    # No traced run, no capture, a program without phases: None each time.
    assert spanread.reduction({"trace": None, "cell": "x"}) is None
    assert spanread.idle_share({"trace": {"window_s": 1}, "cell": "no_such"},
                               "runtime") is None
    assert spanread.phase_seconds({"census": {"compiles": 3}},
                                  "startup.imports") is None
    assert spanread.decode_chunk({"census": {"decode_chunk": 4},
                                  "decode_chunk": 8}) == 4
    assert spanread.decode_chunk({"census": None, "decode_chunk": 8}) == 8


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the chip fixture of PR 24 is not in this tree")
def test_on_the_recorded_capture_the_shares_add_up():
    devices, spans, lo, hi = hostspans.read_capture(FIXTURE)
    red = hostspans.reduce_capture(devices, spans, lo, hi)
    old_devices, old_lo, old_hi = tracefile.read_xplane(FIXTURE)
    trace = tracefile.reduce_planes(old_devices, old_lo, old_hi)
    assert (lo, hi) == (old_lo, old_hi)
    idle_share = 100 * (1 - trace["busy_s"] / trace["window_s"])
    by_family = 100 * sum(red["idle_by_family"].values()) / red["window_s"]
    assert by_family == pytest.approx(idle_share, abs=0.1)
    assert red["has_spans"] and red["has_scopes"]
    assert {"decode.sync", "decode.replay"} <= set(red["idle_by_span"])
    op_s = red["op_s"]
    scoped = sum(v for k, v in red["scope_s"].items() if k != "unscoped")
    unscoped = red["scope_s"].get("unscoped", 0.0)
    assert 100 * scoped / op_s == pytest.approx(
        100 - 100 * unscoped / op_s, abs=1e-6)
    # What has no scope in this capture is what the compiler made itself:
    # layout copies of the stacked weights, which carry no op name at all.
    assert 100 * unscoped / op_s < 20
    assert all("copy" in k or "(no op name)" in k
               for k, _ in red["unscoped_top"][:4])
    assert red["under_scope_s"]["attn"] > red["under_scope_s"]["attn.core"] > 0
    assert red["under_scope_s"]["ffn"] > red["under_scope_s"]["attn"]
    assert red["under_scope_s"]["layers"] >= red["under_scope_s"]["block"]


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the chip fixture of PR 24 is not in this tree")
def test_xspace_reads_what_profile_data_reads():
    """The plain-Python reader against jax.profiler.ProfileData: the same
    planes, lines, events, whole nanoseconds and event stats; and what
    ProfileData does not show, the op name on an operation's metadata."""
    from jax.profiler import ProfileData

    from benchlib import xspace

    planes = xspace.read(FIXTURE)
    data = ProfileData.from_file(FIXTURE)
    n = 0
    for plane, mine in zip(data.planes, planes, strict=True):
        assert plane.name == mine["name"]
        for line, my_line in zip(plane.lines, mine["lines"], strict=True):
            assert line.name == my_line["name"]
            for ev, my_ev in zip(line.events, my_line["events"],
                                 strict=True):
                assert (ev.name, ev.start_ns, ev.duration_ns,
                        dict(ev.stats)) == my_ev[:4]
                n += 1
    assert n > 10000
    ops = next(ln["events"] for pl in planes
               if pl["name"] == "/device:TPU:0"
               for ln in pl["lines"] if ln["name"] == "XLA Ops")
    assert any("/attn.core/" in str(ev[4].get("tf_op")) for ev in ops)
