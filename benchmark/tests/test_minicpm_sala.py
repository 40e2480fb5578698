"""PR 45: the MiniCPM-SALA configuration through the harness on the CPU at
a toy size (fixtures of its own: tests/fixtures_sala), its reference's
int8 control, its file against the catalog's row, the two kernel models,
and the five readers it brings on a synthetic capture."""

import json
import os

import numpy as np
import pytest

import run
from benchlib import scopefamily, spec

FIX = os.path.join(os.path.dirname(__file__), "fixtures_sala")
MS = 1e6   # ns
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "minicpmsala_doc16k"
SPARSE, LIGHT = "minicpm4", "lightning-attn"
NEW = ("lightning_device_share.doc", "lightning_core_roofline",
       "bsa_device_share.doc", "bsa_core_roofline",
       "bsa_visited_over_needed")


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(FIX, "configs", "tiny-minicpm-sala.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "reference", "minicpm_sala.py"), "ref_minicpm_sala")


def real_config():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "minicpm-sala.json")) as f:
        return json.load(f)


def reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", name + ".py"), "lm_" + name)


def test_the_real_configuration_keeps_every_published_number():
    cfg = real_config()
    pub, a = cfg["published"], cfg["as_run"]
    changed = {k for k, v in pub.items() if a.get(k) != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "mixer_types"}
    assert {k for k, v in pub.items() if cfg.get(k) != v} == changed
    # The cut is depth alone, two whole regular periods; every width and
    # the whole vocabulary are the published ones.
    assert a["num_hidden_layers"] == 8 and a["mixer_types"] == \
        [SPARSE, LIGHT, LIGHT, LIGHT] * 2 == a["layer_period"] * 2
    assert [i for i, m in enumerate(pub["mixer_types"]) if m == SPARSE] == [
        0, 9, 16, 17, 22, 29, 30, 31] and len(pub["mixer_types"]) == 32
    assert a["published_num_hidden_layers"] == pub["num_hidden_layers"] == 32
    assert a["sparse_config"] == {
        "block_size": 64, "topk": 64, "window_size": 2048, "init_blocks": 1,
        "kernel_size": 32, "kernel_stride": 16, "dense_len": 8192}
    assert set(cfg["limits"]["serve"]) == set(cfg["limits_why"]) == {
        "served_logit_gap_mean", "served_logit_gap_max"}
    assert {"published", "as_run", "reduced_why", "assumed",
            "deployment"} <= set(cfg)
    assert "four pipeline stages of 8 layers" in cfg["deployment"] \
        and "ONE chip shares each layer" in cfg["deployment"]
    assert {"sparse_config", "lightning_decay", "gates", "weights",
            "qk_norm", "block"} <= set(cfg["assumed"])
    from runbooks_tpu.models.config import CONFIGS, get_config

    assert CONFIGS[cfg["model"]].num_layers == 32
    m = get_config(cfg["model"], **cfg["model_overrides"])
    sp = m.sparse_read
    assert (m.hidden_size, m.intermediate_size, m.vocab_size, m.num_layers,
            m.head_dim, m.norm_eps, m.num_heads, m.num_kv_heads,
            m.linear_num_heads, m.linear_key_head_dim,
            m.linear_value_head_dim, m.linear_rope_theta,
            m.embed_multiplier, m.lightning_decay_layers) == (
        a["hidden_size"], a["intermediate_size"], a["vocab_size"],
        a["num_hidden_layers"], a["head_dim"], a["rms_norm_eps"],
        a["num_attention_heads"], a["num_key_value_heads"],
        a["lightning_nh"], a["lightning_head_dim"], a["lightning_head_dim"],
        a["rope_theta"], a["scale_emb"], 31)
    assert m.residual_scale == pytest.approx(a["scale_depth"] / 32 ** 0.5)
    assert m.logit_divisor == a["hidden_size"] / a["dim_model_base"] == 16
    assert dict(zip(("block_size", "topk", "window_size", "init_blocks",
                     "kernel_size", "kernel_stride", "dense_len"), sp)) \
        == a["sparse_config"]
    assert ["minicpm4" if k == "full_attention" else "lightning-attn"
            for k in m.layer_pattern] * m.num_periods == a["mixer_types"]
    assert m.lightning and m.position_type == "none" and m.qk_norm \
        and m.attn_gate and m.attn_gate_width == "element" \
        and not m.tie_embeddings and m.sparse_exclude_window
    # ISSUE 45's arithmetic: 2.82 G parameters, 5.64 GB in bfloat16.
    assert 2.81e9 < m.num_params < 2.83e9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row_letter_for_letter():
    cfg = real_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "minicpm-sala")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]


def test_tiny_sala_cell_through_the_harness(capsys, monkeypatch):
    """The normal entry point serves sparse-read layers through K/V and
    the compressed keys beside lightning layers through their state,
    prompts on both sides of dense_len in one server; the window's tokens
    are checked against the reference; the line has the contract's keys."""
    monkeypatch.setattr(run, "require_tpu", lambda ident, chips, child: {
        "platform": ident["platform"], "kind": "TPU v5 lite",
        "count": int(ident["device_count"])})
    rc = run.main(["--workload", "tiny_sala_doc", "--seed",
                   str(2 ** 31 + 13), "--seconds", "2", "--trace", "0",
                   "--bench-root", FIX])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    result = json.loads(lines[-1])
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert any("served_logit_gap_mean" in ln and "ok" in ln for ln in lines)


# At this toy size (CPU, three seeds, 8 prompts of 96 tokens, every row,
# the program in bfloat16 activations): the stated precision reads
# 0.00004 .. 0.00006, the int8 control 0.00019 .. 0.00027; the limit lies
# between, 1.8 times the one's largest and 0.58 of the other's smallest, as
# the cell's limits do at its size. (The logits are small: the head's input
# is divided by hidden / dim_model_base.)
TOY_LIMIT = 0.00011


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
    toks = np.random.default_rng(seed).integers(1, 512, (8, 96))
    served = np.asarray(jnp.argmax(jax.jit(
        lambda p, t: forward(cfg, p, t)[0])(params, jnp.asarray(toks)), -1))
    # Every row of a prompt of 96 tokens (>= dense_len: the sparse read):
    # `logits_at` takes the prompt's length from its first row, so the
    # whole prompt's rows come from the reference's own forward.
    dm = ref.dims(as_run)

    def whole(low):
        mm = ref.matmul_int8 if low else ref.matmul

        @jax.jit
        def logits(w, t):
            with jax.default_matmul_precision("highest"):
                x = ref.hidden_states(dm, w, t, 96, mm)
                return mm(x / dm["logit_div"],
                          w["head"].astype(jnp.float32))
        return logits

    exact, int8 = whole(False), whole(True)
    rows = np.arange(96)
    sound, control = [], []
    for i in range(len(toks)):
        logits = np.asarray(exact(w, jnp.asarray(toks[i])))
        low = np.asarray(int8(w, jnp.asarray(toks[i])))
        best = logits.max(-1)
        sound.append(best - logits[rows, served[i]])
        control.append(best - logits[rows, low.argmax(-1)])
    sound, control = np.mean(sound), np.mean(control)
    print(f"seed {seed}: sound {sound:.5f} control {control:.5f}")
    assert sound <= TOY_LIMIT < control, (sound, control)


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "runbooks_tpu" not in body
    assert 'default_matmul_precision("highest")' in source
    # A scan over tokens, a sort, one softmax over every key under a mask:
    # none of the program's forms (chunks, a rank by comparisons, a
    # running softmax, a cache).
    for word in ("pallas", "cumsum", "reduce_window", "cache", "chunk",
                 "top_k"):
        assert word not in body, word


def test_kernel_models_count_what_the_choice_needs():
    sp = real_config()["as_run"]["sparse_config"]
    k = spec.kernel("block_sparse_attention")
    # Below dense_len every key; a token deep in a long row its window,
    # the initial block and 63 blocks: 6144 keys, of 16k.
    assert k.keys_read(5000, 6000, sp) == 5001
    assert k.keys_read(16000, 16001, sp) == 2048 + 64 + 63 * 64 == 6144
    # While the candidates are few the read is the dense one.
    assert k.keys_read(5000, 9000, sp) == 5001
    assert k.keys_read(6143, 9000, sp) == 6144
    assert k.whole_kernels(30, sp) == 0 and k.whole_kernels(31, sp) == 1 \
        and k.whole_kernels(16383, sp) == 1023
    n = 14592
    dense = n * (n + 1) / 2
    assert 0.55 < k.prompt_pairs(n, sp) / dense < 0.70   # ISSUE: 60 % at 16k
    assert k.decode_pairs(16000, sp) == 6144
    ops = k.core_operations(k.prompt_pairs(n, sp), 32, 128)
    secs, bound = k.least_seconds(ops, k.prefill_bytes(n, 32, 2, 128, sp),
                                  {"bf16_flops": 197e12,
                                   "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and secs == pytest.approx(ops / 197e12)
    light = spec.kernel("lightning_attention")
    assert light.operations(1, 32, 128, 128) == 4 * 128 * 128 * 32
    assert light.bytes_moved(0, 1, 32, 128, 128) == 32 * 128 * 128 * 4 * 2
    assert light.least_seconds(8, 8, 32, 128, 128, {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})[1] == "memory"


def op(kind, start_ms, dur_ms):
    return (f"%{kind}.1 = bf16[8,8]{{1,0}} {kind}(%p)", start_ms * MS,
            dur_ms * MS)


BLOCK = "jit({})/layers/while/body/closed_call/block/"


def synthetic_devices():
    pre, dec = BLOCK.format("prefill_fn"), BLOCK.format("decode_fn")
    ops = [op("fusion", 0, 10), op("fusion", 10, 20), op("while", 30, 30),
           op("fusion", 30, 5), op("fusion", 35, 25), op("fusion", 60, 2),
           op("fusion", 62, 8), op("fusion", 100, 4), op("fusion", 104, 1),
           op("fusion", 105, 3), op("copy", 108, 12)]
    names = [pre + "attn/lightning.proj/dot_general",
             pre + "attn/lightning.core/dot_general",
             pre + "attn/bsa.core/while",             # enclosing: not work
             pre + "attn/while/body/bsa.select/reduce_window",
             pre + "attn/while/body/bsa.core/while/body/dot_general",
             pre + "attn/bsa.compress/reduce_sum",
             pre + "ffn/dot_general",
             dec + "attn/lightning.core/mul",
             dec + "attn/bsa.select/dot_general",
             dec + "attn/bsa.core/dot_general", ""]
    modules = [("jit_prefill_fn(123)", 0.0, 80 * MS),
               ("jit_decode_fn(456)", 100 * MS, 20 * MS)]
    return [{"ops": ops, "op_names": names, "modules": modules}]


def test_new_readers_on_synthetic_capture():
    cell = spec.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert names >= set(NEW) | {
        "attn_device_share.doc", "ffn_device_share.doc", "prefill_tok_s",
        "warmup_programs", "device_idle_share.doc", "ttft_pending_ms.doc",
        "startup_weights_s"}
    # Not the readers of other models' mixers and kernels.
    assert not names & {
        "swa_core_roofline", "swa_device_share.doc",
        "swa_visited_over_needed", "decode_roofline",
        "flash_prefill_roofline", "linattn_core_roofline",
        "linattn_device_share.doc", "mla_core_roofline",
        "moe_device_share.doc", "shortconv_device_share.doc"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic_name == "doc_long16k"
    mix = cell.traffic
    assert mix["lengths"]["prompt"] == {"dist": "uniform", "min": 13312,
                                        "max": 15872}
    assert mix["server_params"] == {"max_slots": 8, "max_seq_len": 16384,
                                    "warmup": True}
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        mod = reader(name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert entry["workloads"] == [CELL]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    devices = synthetic_devices()
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    base = {"cell": CELL, "trace": {"programs": {}}, "config": cell.config,
            "peaks": peaks, "trace_window": (0.0, 1.0),
            "counters": {"serve_bsa_pairs_visited_total": 300.0,
                         "serve_bsa_pairs_needed_total": 120.0,
                         "serve_bsa_blocks_chosen_total": 5.0},
            # One prompt of 14 000 tokens dispatched in the window; two
            # tokens decoded in it, at positions 14 001 and 14 002.
            "_syncspans": {"prefill": (14000, 1), "window_s": 1.0,
                           "sync": {}},
            "all_records": [{"prompt_tokens": 14000,
                             "token_times": [0.2, 0.5, 0.6]}]}
    ctx = dict(base, **{
        "_scopefamily_" + fam: scopefamily.reduce_ops(devices, fam)
        for fam in ("lightning", "bsa")})
    # 90 ms of operations (the while is not in).
    assert reader("lightning_device_share.doc").read(ctx) == pytest.approx(
        100 * (10 + 20 + 4) / 90)
    assert reader("bsa_device_share.doc").read(ctx) == pytest.approx(
        100 * (5 + 25 + 2 + 1 + 3) / 90)
    assert reader("bsa_visited_over_needed").read(ctx) == pytest.approx(2.5)
    a = cell.config["as_run"]
    light = spec.kernel("lightning_attention")
    least = 6 * (light.least_seconds(14000, 1, 32, 128, 128, peaks)[0]
                 + light.least_seconds(2, 2, 32, 128, 128, peaks)[0])
    assert reader("lightning_core_roofline").read(ctx) == pytest.approx(
        100 * least / 0.024)
    k = spec.kernel("block_sparse_attention")
    sp = a["sparse_config"]
    pairs = k.decode_pairs(14001, sp) + k.decode_pairs(14002, sp)
    least = 2 * (
        k.least_seconds(k.core_operations(k.prompt_pairs(14000, sp), 32,
                                          128),
                        k.prefill_bytes(14000, 32, 2, 128, sp), peaks)[0]
        + k.least_seconds(k.core_operations(pairs, 32, 128),
                          k.decode_bytes(pairs, [14001, 14002], 32, 2, 128,
                                         sp), peaks)[0])
    assert reader("bsa_core_roofline").read(ctx) == pytest.approx(
        100 * least / 0.028)
    # A program without the scopes or the counters (the parent, a model
    # without such layers), no trace, no capture: nothing, nothing raised.
    bare = dict(base, counters={}, _scopefamily_lightning=None,
                _scopefamily_bsa=None)
    for name in NEW:
        assert reader(name).read(bare) is None
        assert reader(name).read({"cell": "x", "config": cell.config}) \
            is None
        assert reader(name).read({"cell": "x", "config": cell.config,
                                  "trace": {}}) is None
