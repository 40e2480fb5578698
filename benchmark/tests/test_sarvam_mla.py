"""PR 30: the latent-attention, sparse-expert configuration through the
harness on the CPU at a toy size (fixtures of its own:
tests/fixtures_sarvam), its reference's int8 control, the two kernel
models' operation and byte counts, and the `moe.*` / `mla.*` readers on a
synthetic capture and synthetic counters."""

import json
import os

import numpy as np
import pytest

import run
from benchlib import sparse, spec

FIX = os.path.join(os.path.dirname(__file__), "fixtures_sarvam")
MS = 1e6   # ns
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(FIX, "configs", "tiny-sarvam-mla.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "reference", "sarvam_mla.py"), "ref_sarvam_mla")


def real_config():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "sarvam-105b.json")) as f:
        return json.load(f)


def test_the_real_configuration_keeps_every_published_number():
    cfg = real_config()
    pub, a = cfg["published"], cfg["as_run"]
    changed = {k for k, v in pub.items() if a.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {k for k, v in pub.items() if cfg.get(k) != v} == changed
    # The cut keeps to the guide's floors: four layers after the dense
    # one, 8 experts, an eighth of the vocabulary; no width is cut.
    assert a["num_hidden_layers"] - a["first_k_dense_replace"] >= 4
    assert a["num_experts"] >= 8 and a["num_experts_routed"] == 128
    assert a["vocab_size"] * 8 >= pub["vocab_size"]
    assert set(cfg["limits"]["serve"]) == set(cfg["limits_why"])
    assert {"published", "as_run", "reduced_why", "assumed",
            "deployment"} <= set(cfg)
    from runbooks_tpu.models.config import CONFIGS, get_config

    whole = CONFIGS[cfg["model"]]
    assert (whole.num_layers, whole.moe_num_experts, whole.vocab_size) == (
        pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"])
    m = get_config(cfg["model"], **cfg["model_overrides"])
    assert (m.hidden_size, m.num_heads, m.intermediate_size, m.vocab_size,
            m.num_layers, m.leading_dense_layers) == (
        a["hidden_size"], a["num_attention_heads"], a["intermediate_size"],
        a["vocab_size"], a["num_hidden_layers"], a["first_k_dense_replace"])
    assert (m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
            m.kv_lora_rank, m.head_dim, m.q_head_dim) == (
        a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"],
        a["kv_lora_rank"], a["head_dim"], a["q_head_dim"])
    assert (m.moe_num_experts, m.moe_experts_here, m.moe_experts_first,
            m.moe_top_k, m.moe_width, m.moe_shared_experts,
            m.moe_routed_scale, m.moe_router_bias) == (
        a["num_experts_routed"], a["num_experts"], a["first_expert_held"],
        a["num_experts_per_tok"], a["moe_intermediate_size"],
        a["num_shared_experts"], a["routed_scaling_factor"],
        a["moe_router_enable_expert_bias"])
    y = a["rope_scaling"]
    assert m.rope_yarn == (
        y["factor"], y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"], y["mscale"], y["mscale_all_dim"])
    assert m.rope_theta == a["rope_theta"] and m.norm_eps == a["rms_norm_eps"]
    assert m.qk_norm == a["use_qk_norm"] and not m.tie_embeddings


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row_letter_for_letter():
    cfg = real_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "sarvam-105b")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "sarvam-105b")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]


def test_tiny_sarvam_cell_through_the_harness(capsys, monkeypatch):
    """The normal entry point serves latent attention and a share of the
    experts, the window's tokens are checked against the reference, the
    line has the contract's keys."""
    monkeypatch.setattr(run, "require_tpu", lambda ident, chips, child: {
        "platform": ident["platform"], "kind": "TPU v5 lite",
        "count": int(ident["device_count"])})
    rc = run.main(["--workload", "tiny_sarvam_doc", "--seed",
                   str(2 ** 31 + 11), "--seconds", "2", "--trace", "0",
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
# stated precision reads a mean gap of 0.0048 .. 0.0104, the int8 control
# 0.027 .. 0.034; 0.018 lies between, with room on both sides, as the
# cell's limit does at its size. (The fixture's own limit is the cell's
# 0.1: the harness test above compares some 20 tokens of 2 requests, and
# one routing flip among them, a gap of 0.9, reads 0.05.)
TOY_LIMIT = 0.018


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_comes_out_not_correct(ref, conf, seed):
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import forward, init_params
    from runbooks_tpu.train.step import layout_invariant_init

    as_run = conf["as_run"]
    limit = TOY_LIMIT
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
    assert control > 2.5 * sound


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "runbooks_tpu" not in body
    assert 'default_matmul_precision("highest")' in source
    # Expanded attention only, experts one at a time: none of the
    # program's forms.
    for word in ("ragged_dot", "argsort", "pallas", "q_lat", "absorb"):
        assert word not in body, word


def test_expert_operations_and_bytes():
    k = spec.kernel("moe_experts")
    h, f = 4096, 2048
    assert k.operations(1, h, f) == 6 * 4096 * 2048
    # A (layer, expert) pair hit: three bfloat16 matrices of h x f; an
    # assignment: its row in and out.
    assert k.bytes_moved(0, 1, h, f) == 3 * 4096 * 2048 * 2
    assert k.bytes_moved(1, 0, h, f) == 2 * 4096 * 2
    peaks = spec.peaks_for("TPU v5 lite")
    # A decode step: 16 assignments here over 12 pairs hit: the weights'
    # bytes, by four orders of magnitude.
    secs, bound = k.least_seconds(16, 12, h, f, peaks)
    assert bound == "memory"
    assert secs == pytest.approx((12 * 50331648 + 16 * 16384) / 819e9)
    # A prefill of 2048 tokens: 4096 assignments here over 160 pairs,
    # 128 rows an expert: still the weights (128 x 2 operations a weight
    # byte / 2 = 128 an element, under the chip's 240 a byte x 2).
    assert k.least_seconds(4096, 160, h, f, peaks)[1] == "memory"
    # Eight rows of 2048 tokens a forward, 1024 rows an expert (the
    # ridge is at 240): compute.
    assert k.least_seconds(163840, 160, h, f, peaks)[1] == "compute"


def test_latent_attention_operations_and_bytes():
    k = spec.kernel("mla_attention")
    H, dq, dv, r, dr = 64, 192, 128, 512, 64
    assert k.prefill_operations(1, H, dq, dv) == 2 * 64 * 320
    assert k.prefill_bytes(1, H, dq, dv) == 64 * 2 * 320 * 2
    assert k.decode_operations(1, H, r, dr) == 2 * 64 * (576 + 512)
    assert k.decode_bytes(1, r, dr) == 1152
    peaks = spec.peaks_for("TPU v5 lite")
    # A 1500-token prompt: 1.1 M pairs x 40 960 operations: compute.
    pairs = 1500 * 1501 // 2
    secs, bound = k.least_seconds(
        k.prefill_operations(pairs, H, dq, dv),
        k.prefill_bytes(1500, H, dq, dv), peaks)
    assert bound == "compute"
    assert secs == pytest.approx(pairs * 40960 / 197e12)
    # Absorbed decode: 139 264 operations against 1152 bytes a cached
    # token is 121 a byte, under the chip's 240: memory bound.
    assert k.least_seconds(k.decode_operations(1e6, H, r, dr),
                           k.decode_bytes(1e6, r, dr), peaks) == (
        pytest.approx(1152e6 / 819e9), "memory")


def op(kind, start_ms, dur_ms):
    return (f"%{kind}.1 = bf16[8,8]{{1,0}} {kind}(%p)", start_ms * MS,
            dur_ms * MS)


BLOCK = "jit({})/layers/while/body/closed_call/block/"


def synthetic_devices():
    pre, dec = BLOCK.format("prefill_fn"), BLOCK.format("decode_fn")
    ops = [op("fusion", 0, 10), op("fusion", 10, 30), op("while", 10, 40),
           op("fusion", 40, 10), op("fusion", 50, 20), op("fusion", 70, 5),
           op("fusion", 100, 8), op("fusion", 108, 2), op("copy", 110, 10)]
    names = [pre + "attn/mla.kv_up/dot_general",
             pre + "ffn/moe.experts/while/body/closed_call/ragged_dot",
             pre + "ffn/moe.experts/while",      # enclosing: not work
             pre + "ffn/moe.shared/dot_general",
             pre + "attn/mla.core/flash.fwd/pallas_call",
             "jit(prefill_fn)/leading_layers/block/ffn/dot_general",
             dec + "attn/mla.core/reduce_sum",
             dec + "ffn/moe.experts/ragged_dot", ""]
    modules = [("jit_prefill_fn(123)", 0.0, 80 * MS),
               ("jit_decode_fn(456)", 100 * MS, 20 * MS)]
    return [{"ops": ops, "op_names": names, "modules": modules}]


def test_sparse_reduction_of_a_synthetic_capture():
    assert sparse.scope_of(BLOCK.format("x") + "ffn/moe.experts/w/b") \
        == "moe.experts"
    assert sparse.scope_of("jit(f)/block/attn/mla.core/flash.fwd/x") \
        == "mla.core"
    assert sparse.scope_of("jit(f)/block/attn/attn.core/mul") == ""
    assert sparse.scope_of("") == ""
    red = sparse.reduce_ops(synthetic_devices())
    ms = lambda d: {k: round(v * 1e3, 6) for k, v in d.items()}  # noqa
    assert round(red["op_s"] * 1e3, 6) == 95.0        # the while is not in
    assert ms(red["scope_s"]) == {"mla.kv_up": 10.0, "moe.experts": 32.0,
                                  "moe.shared": 10.0, "mla.core": 28.0}
    assert ms(red["program_scope_s"]) == {
        "prefill_fn/mla.kv_up": 10.0, "prefill_fn/moe.experts": 30.0,
        "prefill_fn/moe.shared": 10.0, "prefill_fn/mla.core": 20.0,
        "decode_fn/mla.core": 8.0, "decode_fn/moe.experts": 2.0}


def reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", name + ".py"), "lm_" + name)


NEW = ("moe_device_share.doc", "mla_device_share.doc",
       "moe_experts_roofline", "mla_core_roofline",
       "moe_load_max_over_mean")


def test_new_readers_on_synthetic_capture_and_counters():
    cell = spec.load_cell("sarvam105b_doc")
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | {
        "attn_device_share.doc", "ffn_device_share.doc", "prefill_tok_s",
        "warmup_programs", "device_idle_share.doc"}
    assert not {m["name"] for m in cell.per_layer} & {
        "decode_roofline", "flash_prefill_roofline", "linattn_core_roofline"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    # Two prompts prefilled in the window; 3 + 1 generated tokens arrived
    # in it after their requests' first (one request began before it).
    recs = [{"token_times": [1.0, 1.1, 1.2, 1.3], "prompt_tokens": 1800},
            {"token_times": [1.5, 2.5], "prompt_tokens": 1200},
            {"token_times": [0.2, 0.9], "prompt_tokens": 1500},
            {"token_times": [], "prompt_tokens": 1100}]
    counters = {"serve_moe_assignments_total": 4000.0,      # here + elsewhere
                "serve_moe_expert_tokens_total": 1000.0,    # here
                "serve_moe_expert_hits_total": 600.0,
                "serve_moe_expert_calls_total": 800.0,
                "serve_moe_layer_peak_assignments_total": 50.0}
    trace = {"programs": {"prefill_fn": {"launches": 2, "seconds": 0.08},
                          "decode_fn": {"launches": 3, "seconds": 0.02}}}
    ctx = {"cell": "sarvam105b_doc", "trace": trace, "counters": counters,
           "_sparse": sparse.reduce_ops(synthetic_devices()),
           "all_records": recs, "records": recs, "trace_window": (0.5, 2.0),
           "config": cell.config, "peaks": spec.peaks_for("TPU v5 lite"),
           "census": {"decode_chunk": 8}}
    assert reader("moe_device_share.doc").read(ctx) == pytest.approx(
        100 * 42 / 95)
    assert reader("mla_device_share.doc").read(ctx) == pytest.approx(
        100 * 38 / 95)
    # 3000 prompt tokens + 4 decoded, x 8 experts x 5 layers x a quarter
    # held here; 2 + 3 x 8 forwards x 160 pairs x three quarters hit.
    assignments = 3004 * 8 * 5 * 0.25
    hits = 26 * 160 * 0.75
    ops_s = 6 * 4096 * 2048 * assignments / 197e12
    mem_s = (hits * 3 * 4096 * 2048 + assignments * 2 * 4096) * 2 / 819e9
    assert mem_s > ops_s
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(
        100 * mem_s / 0.032)
    pairs = 1800 * 1801 // 2 + 1200 * 1201 // 2
    cached = 1801 + 1802 + 1803 + 1501
    least = 6 * (pairs * 2 * 64 * 320 / 197e12 + cached * 1152 / 819e9)
    assert reader("mla_core_roofline").read(ctx) == pytest.approx(
        100 * least / 0.028)
    assert reader("moe_load_max_over_mean").read(ctx) == pytest.approx(
        50 / (1000 / 32))
    # Nothing under the scope, or nothing served in the window: nothing.
    bare = dict(ctx, _sparse=dict(ctx["_sparse"], scope_s={"mla.q": 0.01}))
    for name in ("moe_experts_roofline", "mla_core_roofline"):
        assert reader(name).read(bare) is None
        assert reader(name).read(dict(ctx, trace_window=(5.0, 6.0))) is None
    # A program without the scopes or the counters (the parent, a dense
    # model): every reader returns nothing and none raises.
    for name in NEW:
        assert reader(name).read(dict(ctx, _sparse=None, counters={})) \
            is None
        assert reader(name).read({"cell": "x", "config": cell.config}) is None
