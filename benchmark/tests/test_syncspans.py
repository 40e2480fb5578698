"""syncspans arithmetic on synthetic intervals: the idle under `*.sync` by
child, the part of it the event loop's thread was busy in, the tokens the
`prefill` spans of a window carried; nothing where there is nothing."""

import os

import pytest

from benchlib import hostspans, syncspans

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chat_spans_300ms.xplane.pb")
MS = 1e6   # ns


def spans(*rows):
    return [(n, s * MS, e * MS) for n, s, e in rows]


WORKER = spans(
    ("tick", 0, 100), ("decode", 2, 60), ("decode.dispatch", 3, 5),
    ("decode.sync", 10, 50), ("decode.sync.ready", 11, 44),
    ("decode.sync.pull", 44, 48), ("prefill", 60, 95),
    ("prefill.sync", 70, 90), ("prefill.sync.ready", 70, 84),
    ("prefill.sync.pull", 84, 89))


def test_idle_under_sync_splits_by_child_and_adds_up_to_the_parents():
    # Idle 40-52 (under decode.sync from 40 to 50) and 80-92 (under
    # prefill.sync from 80 to 90) of a 0-100 ms window.
    gaps = [[(40 * MS, 52 * MS), (80 * MS, 92 * MS)]]
    loop = spans(("api.write", 42, 43), ("api.write", 46, 47.5),
                 ("api.submit", 86, 100), ("api.write", 60, 61))
    red = syncspans.split_sync(gaps, WORKER, loop)
    assert red["has_children"]
    assert red["ready_s"] * 1e3 == pytest.approx(4 + 4)     # 40-44, 80-84
    # 44-48 and 48-50 (the parent outside its children), 84-89 and 89-90.
    assert red["pull_s"] * 1e3 == pytest.approx(6 + 6)
    # api.write 42-43 and 46-47.5, api.submit 86-90; 60-61 is no idle.
    assert red["loop_busy_s"] * 1e3 == pytest.approx(1 + 1.5 + 4)
    # hostspans names the same idle by the parents alone.
    parents = [s for s in WORKER if not s[0].endswith(("ready", "pull"))]
    dev = {"ops": [("%fusion.1 = bf16[8]{0} fusion(%p)", lo, hi - lo)
                   for lo, hi in ((0, 40 * MS), (52 * MS, 80 * MS),
                                  (92 * MS, 100 * MS))],
           "op_names": [""] * 3, "modules": []}
    assert hostspans.device_gaps(dev["ops"], 0.0, 100 * MS) == gaps[0]
    whole = hostspans.reduce_capture([dev], parents, 0.0, 100 * MS)
    assert red["ready_s"] + red["pull_s"] == pytest.approx(
        whole["idle_by_span"]["decode.sync"]
        + whole["idle_by_span"]["prefill.sync"])
    first, second = red["longest"]
    assert first["seconds"] == second["seconds"] == pytest.approx(0.010)
    assert first["children"] == {
        "decode.sync.ready": pytest.approx(0.004),
        "decode.sync.pull": pytest.approx(0.004),
        "decode.sync": pytest.approx(0.002)}
    assert second["loop"] == {"api.submit": pytest.approx(0.004)}


def test_several_chips_are_averaged_and_a_verify_pull_counts():
    worker = spans(("tick", 0, 100), ("verify", 10, 60),
                   ("verify.sync.ready", 30, 50),
                   ("verify.sync.pull", 50, 58))
    gaps = [[(40 * MS, 60 * MS)], []]
    red = syncspans.split_sync(gaps, worker, [])
    assert red["devices"] == 2 and red["loop_busy_s"] == 0.0
    assert red["ready_s"] * 1e3 == pytest.approx(10 / 2)
    assert red["pull_s"] * 1e3 == pytest.approx(8 / 2)   # not verify's own


def test_a_program_without_the_children_reads_as_nothing():
    parents = [s for s in WORKER if not s[0].endswith(("ready", "pull"))]
    red = syncspans.split_sync([[(40 * MS, 52 * MS)]], parents, [])
    assert not red["has_children"]
    ctx = {"trace": {"window_s": 1.0, "programs": {}}, "cell": "x",
           "_syncspans": {"window_s": 0.1, "sync": red, "prefill": None}}
    assert syncspans.sync_share(ctx, "ready") is None
    assert syncspans.prefill_tok_s(ctx) is None
    # No traced run, no capture: None each time, and nothing raised.
    assert syncspans.reduction({"trace": None, "cell": "x"}) is None
    assert syncspans.sync_share({"trace": {"window_s": 1},
                                 "cell": "no_such"}, "pull") is None
    assert syncspans.prefill_span_tokens([(0, 5, None)], [1], 0, 10) is None


def test_prefill_tokens_by_the_dispatch_that_began_in_the_window():
    # (start, end, tokens) of four prefill spans; the window is 100-200.
    prefills = [(80 * MS, 120 * MS, 1500), (130 * MS, 160 * MS, 1100),
                (190 * MS, 240 * MS, 1900), (250 * MS, 280 * MS, 1000)]
    # The first dispatch began before the window, the third inside it
    # although its span ends after; the fourth lies outside.
    dispatches = [85 * MS, 132 * MS, 195 * MS, 252 * MS]
    assert syncspans.prefill_span_tokens(
        prefills, dispatches, 100 * MS, 200 * MS) == (1100 + 1900, 2)
    assert syncspans.prefill_span_tokens(
        prefills, dispatches, 300 * MS, 400 * MS) == (0, 0)
    ctx = {"trace": {"programs": {"prefill_fn": {"seconds": 0.25}}},
           "cell": "x",
           "_syncspans": {"window_s": 0.1, "prefill": (3000, 2),
                          "sync": {"has_children": False}}}
    assert syncspans.prefill_tok_s(ctx) == pytest.approx(12000.0)


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the chip fixture of PR 24 is not in this tree")
def test_on_a_capture_from_before_the_children_nothing_is_read():
    cap = syncspans.read_capture(FIXTURE)
    assert cap["worker"] and cap["gaps"][0]
    assert {n for n, _, _ in cap["worker"]} <= set(hostspans.FAMILY)
    red = syncspans.split_sync(cap["gaps"], cap["worker"], cap["loop"])
    assert not red["has_children"]
    assert syncspans.prefill_span_tokens(
        cap["prefills"], cap["dispatches"], cap["t_lo"], cap["t_hi"]) is None
