"""PR 40: the LFM2 configuration through the harness on the CPU at a toy
size (fixtures of its own: tests/fixtures_lfm2), its reference's int8
control, its file against the catalog's row, and the reader it brings
(`shortconv_device_share.doc`) on a synthetic capture."""

import json
import os

import numpy as np
import pytest

import run
from benchlib import scopefamily, spec

FIX = os.path.join(os.path.dirname(__file__), "fixtures_lfm2")
MS = 1e6   # ns
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "lfm2moe_doc"
FULL, CONV = "full_attention", "conv"
NEW = "shortconv_device_share.doc"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(FIX, "configs", "tiny-lfm2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "reference", "lfm2_moe.py"), "ref_lfm2_moe")


def real_config():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", name + ".py"), "lm_" + name)


def test_the_real_configuration_keeps_every_published_number():
    cfg = real_config()
    pub, a = cfg["published"], cfg["as_run"]
    changed = {k for k, v in pub.items() if a.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types"}
    assert {k for k, v in pub.items() if cfg.get(k) != v} == changed
    # The cut is depth alone: one leading dense conv layer and two whole
    # periods; every width, all 64 experts, 4 a token and the whole
    # vocabulary are the published ones.
    assert a["num_hidden_layers"] == 9 == 1 + 2 * 4 \
        and a["num_dense_layers"] == a["first_k_dense_replace"] == 1
    assert a["layer_types"] == [CONV] + [FULL, CONV, CONV, CONV] * 2 \
        == pub["layer_types"][1:10]
    assert pub["layer_types"][:2] == [CONV, CONV] \
        and pub["layer_types"][38:] == [FULL, CONV]
    assert (a["num_experts"], a["num_experts_per_tok"],
            a["moe_intermediate_size"], a["vocab_size"]) == (
        64, 4, 1536, 65536)
    assert set(cfg["limits"]["serve"]) | set(
        cfg.get("limits_left_out", {})) == {
        "served_logit_gap_mean", "served_logit_gap_max"}
    assert set(cfg["limits"]["serve"]) == set(cfg["limits_why"])
    assert {"published", "as_run", "reduced_why", "assumed",
            "deployment"} <= set(cfg)
    assert "one chip shares each layer" in cfg["reduced_why"].lower() \
        and "one chip shares each layer" in cfg["deployment"]
    from runbooks_tpu.models.config import CONFIGS, get_config

    whole = CONFIGS[cfg["model"]]
    assert whole.num_layers == 38 and pub["num_hidden_layers"] == 40
    m = get_config(cfg["model"], **cfg["model_overrides"])
    assert (m.hidden_size, m.intermediate_size, m.vocab_size, m.num_layers,
            m.leading_dense_layers, m.head_dim, m.norm_eps, m.num_heads,
            m.num_kv_heads, m.conv_kernel, m.rope_theta) == (
        a["hidden_size"], a["intermediate_size"], a["vocab_size"],
        a["num_hidden_layers"], a["num_dense_layers"], a["head_dim"],
        a["norm_eps"], a["num_attention_heads"], a["num_key_value_heads"],
        a["conv_L_cache"], a["rope_parameters"]["rope_theta"])
    assert [m.leading_layer_kind] * m.leading_dense_layers \
        + list(m.layer_pattern) * m.num_periods == a["layer_types"]
    assert (m.moe_num_experts, m.moe_experts_here, m.moe_top_k,
            m.moe_width, m.moe_shared_experts, m.moe_routed_scale,
            m.moe_router, m.moe_router_bias, m.moe_router_bias_std,
            m.moe_router_eps) == (
        a["num_experts"], a["num_experts"], a["num_experts_per_tok"],
        a["moe_intermediate_size"], 0, a["routed_scaling_factor"],
        a["router"], a["use_expert_bias"], a["router_bias_std"],
        a["router_eps"])
    assert m.tie_embeddings and m.qk_norm and m.qk_norm_width == "head" \
        and not m.attn_bias and m.conv_kernel - 1 == a["conv_tail"]
    # ISSUE 40's arithmetic: 5.18 G parameters, 10.36 GB in bfloat16.
    assert 5.17e9 < m.num_params < 5.19e9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row_letter_for_letter():
    cfg = real_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2-24b-a2b")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]


def test_tiny_lfm2_cell_through_the_harness(capsys, monkeypatch):
    """The normal entry point serves conv layers through their tails
    beside full layers through K/V, a leading conv layer and every expert,
    the window's tokens are checked against the reference, the line has
    the contract's keys."""
    monkeypatch.setattr(run, "require_tpu", lambda ident, chips, child: {
        "platform": ident["platform"], "kind": "TPU v5 lite",
        "count": int(ident["device_count"])})
    rc = run.main(["--workload", "tiny_lfm2_doc", "--seed",
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
# room on both sides (sound 0.040 .. 0.060, control 0.194 .. 0.209), as the
# cell's limits do at its size.
TOY_LIMIT = 0.11


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
    print(f"seed {seed}: sound {sound:.5f} control {control:.5f}")
    assert sound <= TOY_LIMIT < control, (sound, control)
    assert control > 2.5 * sound


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "runbooks_tpu" not in body
    assert 'default_matmul_precision("highest")' in source
    # Three shifted products, every key under a mask, experts one at a
    # time: none of the program's forms.
    body = body.replace("conv_L_cache", "")
    for word in ("ragged_dot", "argsort", "pallas", "causal_conv", "tail",
                 "cache", "conv_general"):
        assert word not in body, word


def op(kind, start_ms, dur_ms):
    return (f"%{kind}.1 = bf16[8,8]{{1,0}} {kind}(%p)", start_ms * MS,
            dur_ms * MS)


BLOCK = "jit({})/layers/while/body/closed_call/block/"


def synthetic_devices():
    pre, dec = BLOCK.format("prefill_fn"), BLOCK.format("decode_fn")
    ops = [op("fusion", 0, 10), op("fusion", 10, 3), op("while", 10, 40),
           op("fusion", 13, 7), op("fusion", 20, 20), op("fusion", 40, 30),
           op("fusion", 70, 5), op("fusion", 100, 4), op("fusion", 104, 1),
           op("fusion", 105, 6), op("copy", 111, 9)]
    names = [pre + "attn/shortconv.in/dot_general",
             pre + "attn/shortconv.core/mul",
             pre + "attn/shortconv.core/while",       # enclosing: not work
             pre + "attn/shortconv.out/dot_general",
             pre + "attn/attn.core/flash.fwd/pallas_call",   # a full layer
             pre + "ffn/moe.experts/gmm/pallas_call",
             "jit(prefill_fn)/leading_layers/block/attn/shortconv.in/"
             "dot_general",
             dec + "attn/shortconv.in/dot_general",
             dec + "attn/shortconv.core/dynamic_slice",
             dec + "attn/attn.core/reduce_sum", ""]
    modules = [("jit_prefill_fn(123)", 0.0, 80 * MS),
               ("jit_decode_fn(456)", 100 * MS, 20 * MS)]
    return [{"ops": ops, "op_names": names, "modules": modules}]


def test_new_reader_on_synthetic_capture():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} >= {
        NEW, "attn_device_share.doc", "ffn_device_share.doc",
        "prefill_tok_s", "warmup_programs",
        "device_idle_share.doc", "moe_device_share.doc",
        "moe_experts_roofline", "moe_load_max_over_mean"}
    # Not the readers of other models' mixers and kernels.
    assert not {m["name"] for m in cell.per_layer} & {
        "swa_core_roofline", "swa_kind_core_roofline",
        "swa_device_share.doc", "swa_visited_over_needed",
        "attn_gate_device_share.doc", "decode_roofline",
        "flash_prefill_roofline", "linattn_core_roofline",
        "linattn_device_share.doc", "mla_core_roofline",
        "mla_device_share.doc"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic_name == "doc_flood"
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NEW)
    mod = reader(NEW)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["workloads"] == [CELL] and bench["per_layer"][-1] is entry
    assert bench["workloads"][-1]["name"] == CELL \
        and bench["configs"][-1]["name"] == "lfm2-24b-a2b" \
        and len(bench["workloads"]) == 9 \
        and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # What the accepted sparse readers read of the configuration is there.
    a = cell.config["as_run"]
    assert (a["first_k_dense_replace"], a["num_experts"],
            a["num_experts_per_tok"], a["moe_intermediate_size"],
            a["hidden_size"]) == (1, 64, 4, 1536, 2048)
    devices = synthetic_devices()
    ctx = {"cell": CELL, "trace": {"programs": {}}, "counters": {},
           "_scopefamily_shortconv": scopefamily.reduce_ops(devices,
                                                            "shortconv"),
           "config": cell.config}
    # 95 ms of operations (the while is not in); 10 + 3 + 7 + 5 + 4 + 1
    # under shortconv.*, the leading layer's included.
    assert mod.read(ctx) == pytest.approx(100 * 30 / 95)
    # A program without the scopes (the parent, a model without such
    # layers), no trace, no capture: nothing, and nothing raised.
    assert mod.read(dict(ctx, _scopefamily_shortconv=None)) is None
    assert mod.read(dict(ctx, _scopefamily_shortconv=scopefamily.reduce_ops(
        devices, "linattn"))) is None
    assert mod.read({"cell": "x", "config": cell.config}) is None
    assert mod.read({"cell": "x", "config": cell.config, "trace": {}}) is None
