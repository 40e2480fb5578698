"""PR 32: the window-and-full configuration through the harness on the CPU
at a toy size (fixtures of its own: tests/fixtures_mimo), its reference's
int8 control, the window kernel model's operation and byte counts, and the
`swa.*` readers (benchlib/scopefamily.py) on a synthetic capture and
synthetic counters."""

import json
import os

import numpy as np
import pytest

import run
from benchlib import scopefamily, spec

FIX = os.path.join(os.path.dirname(__file__), "fixtures_mimo")
MS = 1e6   # ns
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "mimov2flash_doc"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(FIX, "configs", "tiny-mimo-v2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "reference", "mimo_v2_flash.py"), "ref_mimo_v2")


def real_config():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "mimo-v2-flash.json")) as f:
        return json.load(f)


def test_the_real_configuration_keeps_every_published_number():
    cfg = real_config()
    pub, a = cfg["published"], cfg["as_run"]
    changed = {k for k, v in pub.items() if a.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "hybrid_layer_pattern", "moe_layer_freq"}
    assert {k for k, v in pub.items() if cfg.get(k) != v} == changed
    # The cut keeps to the guide's floors: a whole period of the pattern
    # (and more than four layers) after the dense one, 8 experts, an
    # eighth of the vocabulary; no width is cut.
    assert a["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert a["hybrid_layer_pattern"][1:] == pub["hybrid_layer_pattern"][6:12]
    assert a["moe_layer_freq"] == pub["moe_layer_freq"][:7]
    assert a["num_hidden_layers"] == len(a["hybrid_layer_pattern"]) == 7
    assert a["n_routed_experts"] == a["num_experts"] == 32
    assert a["num_experts_routed"] == pub["n_routed_experts"] == 256
    assert a["vocab_size"] * 8 == pub["vocab_size"]
    assert set(cfg["limits"]["serve"]) == set(cfg["limits_why"])
    assert {"published", "as_run", "reduced_why", "assumed",
            "deployment"} <= set(cfg)
    assert "eight chips" in cfg["reduced_why"]
    from runbooks_tpu.models.config import CONFIGS, get_config

    whole = CONFIGS[cfg["model"]]
    # The preset is the leading layer and the seven WHOLE periods: the
    # published first period is one window layer short (48 = 43 + 5).
    assert whole.num_layers == 1 + 7 * 6 and pub["num_hidden_layers"] == 48
    assert (whole.moe_num_experts, whole.vocab_size) == (
        pub["n_routed_experts"], pub["vocab_size"])
    m = get_config(cfg["model"], **cfg["model_overrides"])
    assert (m.hidden_size, m.num_heads, m.intermediate_size, m.vocab_size,
            m.num_layers, m.leading_dense_layers) == (
        a["hidden_size"], a["num_attention_heads"], a["intermediate_size"],
        a["vocab_size"], a["num_hidden_layers"], a["first_k_dense_replace"])
    assert (m.head_dim, m.value_head_dim, m.rotary_dim, m.num_kv_heads,
            m.sliding_num_kv_heads, m.sliding_window, m.ring_len) == (
        a["head_dim"], a["v_head_dim"], a["rotary_dim"],
        a["num_key_value_heads"], a["swa_num_key_value_heads"],
        a["sliding_window"], a["ring_slots"])
    assert a["rotary_dim"] == round(
        a["partial_rotary_factor"] * a["head_dim"])
    assert (m.rope_theta, m.sliding_rope_theta, m.sliding_sink,
            m.attn_value_scale, m.norm_eps) == (
        a["rope_theta"], a["swa_rope_theta"],
        a["add_swa_attention_sink_bias"], a["attention_value_scale"],
        a["layernorm_epsilon"])
    kinds = [1 if k == "sliding_attention" else 0 for k in m.layer_pattern]
    assert [0] + kinds * m.num_periods == a["hybrid_layer_pattern"]
    assert (m.moe_num_experts, m.moe_experts_here, m.moe_experts_first,
            m.moe_top_k, m.moe_width, m.moe_shared_experts,
            m.moe_routed_scale, m.moe_router, m.moe_router_bias) == (
        a["num_experts_routed"], a["num_experts"], a["first_expert_held"],
        a["num_experts_per_tok"], a["moe_intermediate_size"], 0, 1.0,
        a["scoring_func"], True)
    assert not m.tie_embeddings and not m.attn_bias and not m.qk_norm
    # ISSUE 32's arithmetic: 5.85 G parameters, 11.7 GB in bfloat16.
    assert 5.84e9 < m.num_params < 5.86e9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row_letter_for_letter():
    cfg = real_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "mimo-v2-flash")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]


def test_tiny_mimo_cell_through_the_harness(capsys, monkeypatch):
    """The normal entry point serves window layers through their rings
    beside full layers and a share of the experts, the window's tokens are
    checked against the reference, the line has the contract's keys."""
    monkeypatch.setattr(run, "require_tpu", lambda ident, chips, child: {
        "platform": ident["platform"], "kind": "TPU v5 lite",
        "count": int(ident["device_count"])})
    rc = run.main(["--workload", "tiny_mimo_doc", "--seed",
                   str(2 ** 31 + 13), "--seconds", "2", "--trace", "0",
                   "--bench-root", FIX])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    result = json.loads(lines[-1])
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert any("served_logit_gap_mean" in ln and "ok" in ln for ln in lines)
    assert any("routing from the bfloat16-rounded input" in ln
               for ln in lines)


# At this toy size, over 1536 positions a seed (CPU, three seeds): the
# stated precision against the int8 control; the limit lies between, with
# room on both sides, as the cell's limit does at its size.
TOY_LIMIT = 0.017


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_comes_out_not_correct(ref, conf, seed):
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import forward, init_params
    from runbooks_tpu.train.step import layout_invariant_init

    as_run = conf["as_run"]
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
    assert sound <= TOY_LIMIT < control, (sound, control)
    assert control > 2.5 * sound


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "runbooks_tpu" not in body
    assert 'default_matmul_precision("highest")' in source
    # Every key under a mask, experts one at a time: none of the
    # program's forms.
    for word in ("ragged_dot", "argsort", "pallas", "ring", "cache"):
        assert word not in body, word


def test_window_attention_operations_and_bytes():
    k = spec.kernel("window_attention")
    H, n, d, dv, W = 64, 8, 192, 128, 128
    # Sum over t < n of min(t + 1, W).
    assert k.prefill_pairs(1, W) == 1 and k.prefill_pairs(128, W) == 8256
    assert k.prefill_pairs(1500, W) == 8256 + (1500 - 128) * 128
    assert k.prefill_pairs(1500, W) == sum(min(t + 1, W)
                                           for t in range(1500))
    assert k.prefill_operations(1, H, d, dv) == 2 * 64 * 320
    assert k.prefill_bytes(1, H, n, d, dv) == (64 + 8) * 320 * 2
    assert k.decode_live(50, W) == 50 and k.decode_live(1500, W) == 128
    assert k.decode_operations(1, H, d, dv) == 2 * 64 * 320
    assert k.decode_bytes(1, n, d, dv) == 8 * 320 * 2
    peaks = spec.peaks_for("TPU v5 lite")
    # A 1500-token prompt: 128 pairs a token x 40 960 operations against
    # 46 080 bytes a token is 114 operations a byte: memory bound on this
    # chip (its ridge is at 240), which a full layer's 750 pairs a token
    # is not.
    assert k.least_seconds(
        k.prefill_operations(k.prefill_pairs(1500, W), H, d, dv),
        k.prefill_bytes(1500, H, n, d, dv), peaks) == (
        pytest.approx(1500 * 46080 / 819e9), "memory")
    # Decode: 40 960 operations against 5120 bytes a live key: memory.
    assert k.least_seconds(k.decode_operations(1e6, H, d, dv),
                           k.decode_bytes(1e6, n, d, dv), peaks) == (
        pytest.approx(5120e6 / 819e9), "memory")


def op(kind, start_ms, dur_ms):
    return (f"%{kind}.1 = bf16[8,8]{{1,0}} {kind}(%p)", start_ms * MS,
            dur_ms * MS)


BLOCK = "jit({})/layers/while/body/closed_call/block/"


def synthetic_devices():
    pre, dec = BLOCK.format("prefill_fn"), BLOCK.format("decode_fn")
    ops = [op("fusion", 0, 10), op("fusion", 10, 30), op("while", 10, 40),
           op("fusion", 40, 10), op("fusion", 50, 20), op("fusion", 70, 5),
           op("fusion", 100, 8), op("fusion", 108, 2), op("copy", 110, 10)]
    names = [pre + "attn/swa.qkv/dot_general",
             pre + "attn/swa.core/flash.fwd/pallas_call",
             pre + "attn/swa.core/while",            # enclosing: not work
             pre + "attn/swa.ring_write/scatter",
             pre + "attn/attn.core/flash.fwd/pallas_call",   # a full layer
             "jit(prefill_fn)/leading_layers/block/ffn/dot_general",
             dec + "attn/swa.core/reduce_sum",
             dec + "attn/swa.out/dot_general", ""]
    modules = [("jit_prefill_fn(123)", 0.0, 80 * MS),
               ("jit_decode_fn(456)", 100 * MS, 20 * MS)]
    return [{"ops": ops, "op_names": names, "modules": modules}]


def test_scope_family_reduction_of_a_synthetic_capture():
    assert scopefamily.scope_of(
        BLOCK.format("x") + "attn/swa.core/flash.fwd/w", "swa") == "swa.core"
    assert scopefamily.scope_of("jit(f)/block/attn/attn.core/mul", "swa") \
        == ""
    assert scopefamily.scope_of("jit(f)/block/ffn/moe.experts/x", "moe") \
        == "moe.experts"
    assert scopefamily.scope_of("", "swa") == ""
    red = scopefamily.reduce_ops(synthetic_devices(), "swa")
    ms = lambda d: {k: round(v * 1e3, 6) for k, v in d.items()}  # noqa
    assert round(red["op_s"] * 1e3, 6) == 95.0        # the while is not in
    assert ms(red["scope_s"]) == {"swa.qkv": 10.0, "swa.core": 38.0,
                                  "swa.ring_write": 10.0, "swa.out": 2.0}
    assert ms(red["program_scope_s"]) == {
        "prefill_fn/swa.qkv": 10.0, "prefill_fn/swa.core": 30.0,
        "prefill_fn/swa.ring_write": 10.0, "decode_fn/swa.core": 8.0,
        "decode_fn/swa.out": 2.0}
    # The family is an argument: the same capture read for another one.
    assert scopefamily.reduce_ops(synthetic_devices(), "mla")["scope_s"] \
        == {}


def reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", name + ".py"), "lm_" + name)


NEW = ("swa_device_share.doc", "swa_core_roofline",
       "swa_visited_over_needed")


def test_new_readers_on_synthetic_capture_and_counters():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | {
        "attn_device_share.doc", "ffn_device_share.doc", "prefill_tok_s",
        "warmup_programs", "device_idle_share.doc", "moe_device_share.doc",
        "moe_experts_roofline", "moe_load_max_over_mean"}
    assert not {m["name"] for m in cell.per_layer} & {
        "decode_roofline", "flash_prefill_roofline", "linattn_core_roofline",
        "mla_core_roofline", "mla_device_share.doc"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic_name == "doc_flood"
    recs = [{"token_times": [1.0, 1.1, 1.2, 1.3], "prompt_tokens": 1800},
            {"token_times": [1.5, 2.5], "prompt_tokens": 100},
            {"token_times": [0.2, 0.9], "prompt_tokens": 1500},
            {"token_times": [], "prompt_tokens": 1100}]
    counters = {"serve_window_blocks_visited_total": 190.0,
                "serve_window_blocks_grid_total": 240.0,
                "serve_window_scores_visited_total": 190.0 * 512 * 128,
                "serve_window_scores_needed_total": 2540160.0}
    trace = {"programs": {"prefill_fn": {"launches": 2, "seconds": 0.08},
                          "decode_fn": {"launches": 3, "seconds": 0.02}}}
    ctx = {"cell": CELL, "trace": trace, "counters": counters,
           "_scopefamily_swa": scopefamily.reduce_ops(synthetic_devices(),
                                                      "swa"),
           "all_records": recs, "records": recs, "trace_window": (0.5, 2.0),
           "config": cell.config, "peaks": spec.peaks_for("TPU v5 lite"),
           "census": {"decode_chunk": 8}}
    assert reader("swa_device_share.doc").read(ctx) == pytest.approx(
        100 * 60 / 95)
    assert reader("swa_visited_over_needed").read(ctx) == pytest.approx(
        190 * 512 * 128 / 2540160)
    # Two prompts prefilled in the window (1800 and 100 tokens: the short
    # one never fills a window); 3 + 1 generated tokens arrived in it after
    # their requests' first, each reading a full window of its ring. Both
    # programs are memory bound at these widths; 5 window layers.
    pairs = (8256 + (1800 - 128) * 128) + 100 * 101 // 2
    pre = max(pairs * 2 * 64 * 320 / 197e12, 1900 * 72 * 320 * 2 / 819e9)
    dec = max(4 * 128 * 2 * 64 * 320 / 197e12, 4 * 128 * 8 * 320 * 2 / 819e9)
    assert reader("swa_core_roofline").read(ctx) == pytest.approx(
        100 * 5 * (pre + dec) / 0.038)
    # Nothing under the scope, or nothing served in the window: nothing.
    bare = dict(ctx, _scopefamily_swa=dict(
        ctx["_scopefamily_swa"], scope_s={"swa.qkv": 0.01}))
    assert reader("swa_core_roofline").read(bare) is None
    assert reader("swa_core_roofline").read(
        dict(ctx, trace_window=(5.0, 6.0))) is None
    # A program without the scopes or the counters (the parent, a model
    # without window layers): every reader returns nothing, none raises.
    for name in NEW:
        assert reader(name).read(dict(ctx, _scopefamily_swa=None,
                                      counters={})) is None
        assert reader(name).read({"cell": "x", "config": cell.config}) is None
