"""Every per-layer metric of BENCHMARK.json has a reader of its own whose
data (layer, unit, source, moves) matches its entry; a reader that finds
nothing to read returns nothing."""

import json
import os

import pytest

from benchlib import arith, spec

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
FIXTURE_TRACE = os.path.join(os.path.dirname(__file__), "fixtures",
                             "chat_300ms.xplane.pb")


def reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", name + ".py"), "lm_" + name)


@pytest.mark.parametrize("entry", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_reader_matches_its_entry_and_returns_nothing_on_nothing(entry):
    mod = reader(entry["name"])
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    empty = {"parts": {}, "trace": None, "records": [], "all_records": [],
             "counters": {}, "census": None, "step_lines": []}
    assert not mod.read(empty)


def test_every_cell_resolves_to_its_two_data_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"] in ("open_loop", "closed_loop",
                                        "train_job")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert os.path.exists(os.path.join(
                spec.BENCH_DIR, "layer_metrics", m["name"] + ".py"))


def test_traced_readers_on_the_recorded_trace():
    from benchlib import tracefile

    devices, lo, hi = tracefile.read_xplane(FIXTURE_TRACE)
    trace = tracefile.reduce_planes(devices, lo, hi)
    cell = spec.load_cell("falcon7b_chat")
    recs = [{"token_times": [1.0 + 0.01 * i], "prompt_tokens": 300,
             "max_tokens": 64, "ok": True, "due_t": 0.9} for i in range(3)]
    ctx = {"trace": trace, "records": recs, "all_records": recs,
           "trace_window": (0.5, 2.0), "config": cell.config,
           "traffic": cell.traffic, "params": {"max_slots": 16},
           "peaks": spec.peaks_for("TPU v5 lite"), "device": {"count": 1},
           "decode_chunk": 8}
    idle = reader("device_idle_share.chat").read(ctx)
    assert idle == pytest.approx(18.275, abs=0.01)
    roof = reader("decode_roofline").read(ctx)
    # One decode step of 15.6 ms against 7.2 GB of weights at 819 GB/s.
    assert 50 < roof < 65
    assert 0 < reader("flash_prefill_roofline").read(ctx) <= 100
    # The `prefill` spans of the window say they carried 900 tokens.
    ctx.update(cell="falcon7b_chat", _syncspans={
        "window_s": 0.3, "prefill": (900, 3),
        "sync": {"has_children": False}})
    assert reader("prefill_tok_s").read(ctx) == pytest.approx(
        900 / trace["programs"]["prefill_fn"]["seconds"])
    assert arith.prefilled_in(recs, (2.0, 3.0)) == []
