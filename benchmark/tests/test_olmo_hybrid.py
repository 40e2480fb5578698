"""PR 26: the hybrid configuration through the harness on the CPU at a toy
size (fixtures of its own: tests/fixtures_hybrid), its reference's int8
control, the delta rule's operation and byte counts, and the `linattn.*`
readers on a synthetic capture."""

import json
import os

import numpy as np
import pytest

import run
from benchlib import linattn, spec

FIX = os.path.join(os.path.dirname(__file__), "fixtures_hybrid")
MS = 1e6   # ns


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(FIX, "configs", "tiny-olmo-hybrid.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "reference", "olmo_hybrid.py"), "ref_olmo_hybrid")


def test_the_real_configuration_keeps_every_published_number():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    pub = cfg["published"]
    changed = {k for k, v in pub.items() if cfg["as_run"].get(k) != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert {k for k, v in pub.items() if cfg.get(k) != v} == changed
    a = cfg["as_run"]
    assert a["num_hidden_layers"] % len(a["layer_period"]) == 0
    assert a["layer_period"] == pub["layer_types"][:len(a["layer_period"])]
    assert set(cfg["limits"]["serve"]) == set(cfg["limits_why"])
    from runbooks_tpu.models.config import get_config

    model = get_config(cfg["model"], **cfg["model_overrides"])
    assert (model.hidden_size, model.num_heads * model.head_dim,
            model.intermediate_size, model.vocab_size, model.num_layers) == (
        a["hidden_size"], a["num_attention_heads"] * a["head_dim"],
        a["intermediate_size"], a["vocab_size"], a["num_hidden_layers"])
    assert (model.linear_num_heads, model.linear_key_head_dim,
            model.linear_value_head_dim, model.linear_conv_kernel) == (
        a["linear_num_value_heads"], a["linear_key_head_dim"],
        a["linear_value_head_dim"], a["linear_conv_kernel_dim"])
    assert list(model.layer_pattern) == a["layer_period"]


def test_tiny_hybrid_cell_through_the_harness(capsys, monkeypatch):
    """The normal entry point serves the hybrid, the window's tokens are
    checked against the reference, the line has the contract's keys."""
    monkeypatch.setattr(run, "require_tpu", lambda ident, chips, child: {
        "platform": ident["platform"], "kind": "TPU v5 lite",
        "count": int(ident["device_count"])})
    rc = run.main(["--workload", "tiny_hybrid_doc", "--seed",
                   str(2 ** 31 + 7), "--seconds", "2", "--trace", "0",
                   "--bench-root", FIX])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    result = json.loads(lines[-1])
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert any("served_logit_gap_mean" in ln and "ok" in ln for ln in lines)


# At this toy size, over 1536 positions a seed (CPU, five seeds): the
# stated precision reads a mean gap of 0.0095 .. 0.0128, the int8 control
# 0.068 .. 0.089. The fixture's limit lies between, at about three times
# the sound runs' largest, as the cell's does.
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_comes_out_not_correct(ref, conf, seed):
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import forward, init_params
    from runbooks_tpu.train.step import layout_invariant_init

    as_run = conf["as_run"]
    limit = conf["limits"]["serve"]["served_logit_gap_mean"]
    w = ref.init_weights(as_run, seed)
    cfg = get_config(conf["model"], **dict(conf["model_overrides"],
                                           attention_impl="xla"))
    with layout_invariant_init():
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(seed))
    toks = np.random.default_rng(seed).integers(1, 512, (16, 96))
    served = np.asarray(jnp.argmax(jax.jit(
        lambda p, t: forward(cfg, p, t)[0])(params, jnp.asarray(toks)), -1))
    rows = np.arange(96)
    sound, control = [], []
    for i in range(len(toks)):
        logits = np.asarray(ref.logits_at(as_run, w, toks[i], rows))
        low = np.asarray(ref.logits_at(as_run, w, toks[i], rows, low=True))
        best = logits.max(-1)
        sound.append(best - logits[rows, served[i]])
        control.append(best - logits[rows, low.argmax(-1)])
    sound, control = np.mean(sound), np.mean(control)
    assert sound <= limit < control, (sound, control)
    assert control > 3 * sound


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    assert "runbooks_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def test_delta_rule_operations_and_bytes():
    k = spec.kernel("gated_delta")
    heads, dk, dv = 30, 96, 192
    assert k.operations(1, 1, dk, dv) == 6 * 96 * 192
    assert k.operations(10, heads, dk, dv) == 10 * 30 * 6 * 96 * 192
    # A token: q, k (96 each) and v, o (192 each) in bfloat16, g and beta
    # in float32; a pass: the float32 state read and written.
    assert k.bytes_moved(1, 0, 1, dk, dv) == (96 + 96 + 192 + 192) * 2 + 8
    assert k.bytes_moved(0, 1, 1, dk, dv) == 96 * 192 * 4 * 2
    assert k.bytes_moved(7, 3, heads, dk, dv) == 30 * (
        7 * 1160 + 3 * 147456)
    peaks = spec.peaks_for("TPU v5 lite")
    # Prefill: 110 592 operations against 1160 bytes a token and head is
    # 95 operations a byte, under the chip's 240: memory bound.
    secs, bound = k.least_seconds(16384, 4, heads, dk, dv, peaks)
    assert bound == "memory"
    assert secs == pytest.approx(30 * (16384 * 1160 + 4 * 147456) / 819e9)
    # Decode: the state's 147 kB a head and step dwarf everything.
    secs, bound = k.least_seconds(4, 4, heads, dk, dv, peaks)
    assert bound == "memory" and secs == pytest.approx(
        30 * 4 * (1160 + 147456) / 819e9)
    # A chip with ten times the bandwidth would be compute bound.
    fast = dict(peaks, hbm_bytes_per_s=peaks["hbm_bytes_per_s"] * 10)
    assert k.least_seconds(16384, 4, heads, dk, dv, fast)[1] == "compute"


def op(kind, start_ms, dur_ms):
    return (f"%{kind}.1 = bf16[8,8]{{1,0}} {kind}(%p)", start_ms * MS,
            dur_ms * MS)


BLOCK = "jit({})/layers/while/body/closed_call/block/attn/"


def synthetic_devices():
    pre, dec = BLOCK.format("prefill_fn"), BLOCK.format("decode_fn")
    ops = [op("fusion", 0, 10), op("fusion", 10, 30), op("while", 10, 40),
           op("fusion", 40, 10), op("fusion", 50, 20), op("fusion", 70, 5),
           op("fusion", 100, 8), op("fusion", 108, 2), op("copy", 110, 10)]
    names = [pre + "linattn.proj/dot_general",
             pre + "linattn.core/while/body/closed_call/dot_general",
             pre + "linattn.core/while",        # enclosing: not work
             pre + "linattn.out/mul",
             pre + "attn.core/flash.fwd/pallas_call",
             "jit(prefill_fn)/layers/while/body/closed_call/block/ffn/dot",
             dec + "linattn.core/reduce_sum",
             "transpose(jvp(block))/attn/linattn.conv/mul", ""]
    modules = [("jit_prefill_fn(123)", 0.0, 80 * MS),
               ("jit_decode_fn(456)", 100 * MS, 20 * MS)]
    return [{"ops": ops, "op_names": names, "modules": modules}]


def test_linattn_reduction_of_a_synthetic_capture():
    assert linattn.scope_of(BLOCK.format("x") + "linattn.core/w/b") \
        == "linattn.core"
    assert linattn.scope_of("jit(f)/block/attn/attn.core/mul") == ""
    assert linattn.scope_of("") == ""
    red = linattn.reduce_ops(synthetic_devices())
    ms = lambda d: {k: round(v * 1e3, 6) for k, v in d.items()}  # noqa
    assert round(red["op_s"] * 1e3, 6) == 95.0        # the while is not in
    assert ms(red["scope_s"]) == {"linattn.proj": 10.0, "linattn.core": 38.0,
                                  "linattn.out": 10.0, "linattn.conv": 2.0}
    assert ms(red["program_scope_s"]) == {
        "prefill_fn/linattn.proj": 10.0, "prefill_fn/linattn.core": 30.0,
        "prefill_fn/linattn.out": 10.0, "decode_fn/linattn.core": 8.0,
        "decode_fn/linattn.conv": 2.0}


def reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", name + ".py"), "lm_" + name)


def test_linattn_readers_on_the_synthetic_capture():
    cell = spec.load_cell("olmohybrid7b_doc")
    assert {m["name"] for m in cell.per_layer} >= {
        "linattn_device_share.doc", "linattn_core_roofline",
        "attn_device_share.doc", "prefill_tok_s", "warmup_programs"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    # Two prompts prefilled in the window; 3 + 1 generated tokens arrived
    # in it after their requests' first (one request began before it).
    recs = [{"token_times": [1.0, 1.1, 1.2, 1.3], "prompt_tokens": 3000},
            {"token_times": [1.5, 2.5], "prompt_tokens": 2000},
            {"token_times": [0.2, 0.9], "prompt_tokens": 2500},
            {"token_times": [], "prompt_tokens": 2100}]
    ctx = {"cell": "olmohybrid7b_doc", "trace": {"programs": {}},
           "_linattn": linattn.reduce_ops(synthetic_devices()),
           "all_records": recs, "records": recs, "trace_window": (0.5, 2.0),
           "config": cell.config, "peaks": spec.peaks_for("TPU v5 lite")}
    share = reader("linattn_device_share.doc").read(ctx)
    assert share == pytest.approx(100 * 60 / 95)
    roof = reader("linattn_core_roofline").read(ctx)
    least = 12 * 30 * ((5000 * 1160 + 2 * 147456)
                       + 4 * (1160 + 147456)) / 819e9
    assert roof == pytest.approx(100 * least / 0.038)
    # Nothing under linattn.core, or nothing served in the window: nothing.
    bare = dict(ctx, _linattn=dict(ctx["_linattn"], scope_s={
        "linattn.proj": 0.01}))
    assert reader("linattn_core_roofline").read(bare) is None
    assert reader("linattn_core_roofline").read(
        dict(ctx, trace_window=(5.0, 6.0))) is None
    # A program without the scopes (the parent): both return nothing.
    for name in ("linattn_device_share.doc", "linattn_core_roofline"):
        assert reader(name).read(dict(ctx, _linattn=None)) is None
