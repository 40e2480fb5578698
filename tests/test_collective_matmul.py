"""Overlapped collective-matmul tests (ops/collective_matmul.py).

Oracle strategy: the GSPMD path (collective_matmul="off") is the reference
— every ring result (primitive values, full-model logits, train-step loss
and grads, cached prefill/decode, quantized serving weights, LoRA,
accumulation) must match it to float tolerance on 2- and 4-way tensor
meshes carved from the 8 virtual CPU devices. Jaxpr evidence proves the
ring actually formed: ppermute present in the ring jaxprs (with exact
counts for the primitives), absent from the GSPMD jaxpr, and no psum
(all-reduce) after the row-parallel partial dots.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.controller.common import validate_params
from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import (
    KVCache,
    forward,
    init_params,
    resolve_collective_matmul,
)
from runbooks_tpu.ops.collective_matmul import (
    matmul_reduce_scatter,
    ring_ag_matmul,
    ring_supported,
)
from runbooks_tpu.ops.quantization import quantize, quantized_matmul
from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

TP2_MESH = dict(data=2, fsdp=2, tensor=2)
TP4_MESH = dict(data=2, fsdp=1, tensor=4)


def cm_cfg(**over):
    # debug is GQA (4 q heads over 2 kv heads); f32 for exact-math CPU
    # comparisons against the GSPMD oracle.
    kw = dict(dtype="float32")
    kw.update(over)
    return get_config("debug", **kw)


def toks(cfg, b=4, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [TP2_MESH, TP4_MESH],
                         ids=["tp2", "tp4"])
@pytest.mark.parametrize("bidirectional", [False, True], ids=["uni", "bidir"])
def test_primitive_values_match_matmul(mesh_shape, bidirectional):
    mesh = make_mesh(MeshConfig(**mesh_shape))
    x = jax.random.normal(jax.random.key(0), (4, 8, 64), jnp.float32)
    w_col = jax.random.normal(jax.random.key(1), (64, 32), jnp.float32)
    w_row = jax.random.normal(jax.random.key(2), (64, 64), jnp.float32)
    assert ring_supported("ag", x.shape, w_col, mesh)
    assert ring_supported("rs", x.shape, w_row, mesh)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda x, w: ring_ag_matmul(
            x, w, mesh=mesh, compute_dtype=jnp.float32,
            bidirectional=bidirectional))(x, w_col)
        z = jax.jit(lambda x, w: matmul_reduce_scatter(
            x, w, mesh=mesh, compute_dtype=jnp.float32,
            bidirectional=bidirectional))(x, w_row)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w_col),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(x @ w_row),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mesh_shape", [TP2_MESH, TP4_MESH],
                         ids=["tp2", "tp4"])
def test_primitive_grads_match_matmul(mesh_shape):
    """The custom VJPs (AG bwd = matmul-RS ring + re-circulated dw ring;
    RS bwd = AG ring) must reproduce plain-autodiff gradients."""
    mesh = make_mesh(MeshConfig(**mesh_shape))
    x = jax.random.normal(jax.random.key(0), (4, 8, 64), jnp.float32)
    w_col = jax.random.normal(jax.random.key(1), (64, 32), jnp.float32)
    w_row = jax.random.normal(jax.random.key(2), (64, 64), jnp.float32)

    with jax.set_mesh(mesh):
        gx, gw = jax.jit(jax.grad(
            lambda x, w: jnp.sum(ring_ag_matmul(
                x, w, mesh=mesh, compute_dtype=jnp.float32) ** 2),
            argnums=(0, 1)))(x, w_col)
        hx, hw = jax.jit(jax.grad(
            lambda x, w: jnp.sum(matmul_reduce_scatter(
                x, w, mesh=mesh, compute_dtype=jnp.float32) ** 2),
            argnums=(0, 1)))(x, w_row)
    gx_r, gw_r = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2),
                          argnums=(0, 1))(x, w_col)
    hx_r, hw_r = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2),
                          argnums=(0, 1))(x, w_row)
    for got, want in ((gx, gx_r), (gw, gw_r), (hx, hx_r), (hw, hw_r)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_primitive_quantized_matches_quantized_matmul(bits):
    """Dequant-fused ring == the fused quantized_matmul reference, both
    primitives, both packings (block 16 keeps tp=4 chunks block-aligned)."""
    mesh = make_mesh(MeshConfig(**TP4_MESH))
    x = jax.random.normal(jax.random.key(0), (4, 8, 64), jnp.float32)
    w_col = jax.random.normal(jax.random.key(1), (64, 32), jnp.float32)
    w_row = jax.random.normal(jax.random.key(2), (64, 64), jnp.float32)
    qa_col = quantize(w_col, bits=bits, block_size=16)
    qa_row = quantize(w_row, bits=bits, block_size=16)
    assert ring_supported("ag", x.shape, qa_col, mesh)
    assert ring_supported("rs", x.shape, qa_row, mesh)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda x: ring_ag_matmul(
            x, qa_col, mesh=mesh, compute_dtype=jnp.float32))(x)
        z = jax.jit(lambda x: matmul_reduce_scatter(
            x, qa_row, mesh=mesh, compute_dtype=jnp.float32))(x)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(quantized_matmul(x, qa_col, compute_dtype=jnp.float32)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(z),
        np.asarray(quantized_matmul(x, qa_row, compute_dtype=jnp.float32)),
        rtol=1e-5, atol=1e-5)


def test_primitive_jaxpr_ring_evidence():
    """tp-1 ppermutes per unidirectional ring, zero psums: the collective
    really is decomposed, not re-formed as a blocking all-reduce."""
    mesh = make_mesh(MeshConfig(**TP4_MESH))
    x = jax.random.normal(jax.random.key(0), (4, 8, 64), jnp.float32)
    w_col = jax.random.normal(jax.random.key(1), (64, 32), jnp.float32)
    w_row = jax.random.normal(jax.random.key(2), (64, 64), jnp.float32)
    with jax.set_mesh(mesh):
        ag_txt = str(jax.make_jaxpr(lambda x, w: ring_ag_matmul(
            x, w, mesh=mesh, bidirectional=False))(x, w_col))
        rs_txt = str(jax.make_jaxpr(lambda x, w: matmul_reduce_scatter(
            x, w, mesh=mesh, bidirectional=False))(x, w_row))
    assert ag_txt.count("ppermute") == 3  # tp-1 hops
    assert rs_txt.count("ppermute") == 3
    assert "psum" not in ag_txt
    assert "psum" not in rs_txt


def test_ring_supported_gating():
    mesh = make_mesh(MeshConfig(**TP2_MESH))
    no_tp = make_mesh(MeshConfig(data=2, fsdp=4))
    w = jnp.zeros((64, 32), jnp.float32)
    assert ring_supported("ag", (4, 8, 64), w, mesh)
    assert not ring_supported("ag", (4, 8, 64), w, no_tp)   # no tensor axis
    assert not ring_supported("ag", (4, 8, 63), w, mesh)    # contraction mismatch
    assert not ring_supported("ag", (4, 8, 65), jnp.zeros((65, 32)), mesh)
    assert not ring_supported("rs", (4, 8, 64), jnp.zeros((64, 33)), mesh)
    # Quantized: chunks must hold whole blocks.
    qa = quantize(jnp.ones((64, 32)), bits=8, block_size=64)
    assert not ring_supported("ag", (4, 8, 64), qa, mesh)   # 32-row chunk < block
    qa16 = quantize(jnp.ones((64, 32)), bits=8, block_size=16)
    assert ring_supported("ag", (4, 8, 64), qa16, mesh)


# ---------------------------------------------------------------------------
# Full model: logits / cache / jaxpr
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [TP2_MESH, TP4_MESH],
                         ids=["tp2", "tp4"])
def test_forward_logits_match_gspmd(mesh_shape):
    cfg = cm_cfg()
    ring = dataclasses.replace(cfg, collective_matmul="ring")
    params = init_params(cfg, jax.random.key(0))
    tokens = toks(cfg)
    mesh = make_mesh(MeshConfig(**mesh_shape))
    with jax.set_mesh(mesh):
        want, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)
        got, _ = jax.jit(lambda p, t: forward(ring, p, t))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_forward_jaxpr_has_ring_gspmd_does_not():
    cfg = cm_cfg()
    ring = dataclasses.replace(cfg, collective_matmul="ring")
    params = init_params(cfg, jax.random.key(0))
    tokens = toks(cfg)
    mesh = make_mesh(MeshConfig(**TP2_MESH))
    with jax.set_mesh(mesh):
        ring_txt = str(jax.make_jaxpr(
            lambda p, t: forward(ring, p, t))(params, tokens))
        off_txt = str(jax.make_jaxpr(
            lambda p, t: forward(cfg, p, t))(params, tokens))
    # 5 column-parallel rings (wq/wk/wv/wi_gate/wi_up) + 2 row-parallel
    # (attn wo, mlp wo), one ppermute each at tp=2, inside the scanned
    # layer body.
    assert ring_txt.count("ppermute") == 7
    assert off_txt.count("ppermute") == 0


def test_cached_prefill_decode_match_gspmd():
    """The serve engine's two program shapes — chunked prefill into a cache
    and single-token decode — through the ring path."""
    cfg = cm_cfg()
    ring = dataclasses.replace(cfg, collective_matmul="ring")
    params = init_params(cfg, jax.random.key(0))
    tokens = toks(cfg)
    mesh = make_mesh(MeshConfig(**TP2_MESH))

    def run(c):
        cache = KVCache.create(c, 4, 32)
        l1, cache = forward(c, params, tokens[:, :8], cache=cache)
        l2, cache = forward(c, params, tokens[:, 8:9], cache=cache)
        return l1, l2

    with jax.set_mesh(mesh):
        w1, w2 = jax.jit(lambda: run(cfg))()
        g1, g2 = jax.jit(lambda: run(ring))()
    np.testing.assert_allclose(np.asarray(g1), np.asarray(w1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(w2),
                               rtol=1e-4, atol=1e-4)


def test_forward_quantized_weights_match_gspmd():
    """int8/int4 serving weights through the ring (block 32 divides the
    h/tp = 64-row chunks of the debug shapes at tp=2)."""
    cfg = cm_cfg()
    ring = dataclasses.replace(cfg, collective_matmul="ring")
    params = init_params(cfg, jax.random.key(0))
    tokens = toks(cfg)
    mesh = make_mesh(MeshConfig(**TP2_MESH))
    for bits, mode in ((8, "int8"), (4, "int4")):
        from runbooks_tpu.ops.quantization import quantize_params

        qparams = quantize_params(
            jax.tree.map(lambda a: a, params), mode, block_size=32)
        with jax.set_mesh(mesh):
            want, _ = jax.jit(
                lambda p, t: forward(cfg, p, t))(qparams, tokens)
            got, _ = jax.jit(
                lambda p, t: forward(ring, p, t))(qparams, tokens)
            ring_txt = str(jax.make_jaxpr(
                lambda p, t: forward(ring, p, t))(qparams, tokens))
        assert ring_txt.count("ppermute") == 7, mode
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_resolve_gating():
    cfg = cm_cfg(collective_matmul="auto")
    # No mesh: off.
    assert resolve_collective_matmul(cfg) is False
    # tensor axis present: on.
    with jax.set_mesh(make_mesh(MeshConfig(**TP2_MESH))):
        assert resolve_collective_matmul(cfg) is True
        assert resolve_collective_matmul(
            dataclasses.replace(cfg, collective_matmul="off")) is False
    # No tensor axis: off.
    with jax.set_mesh(make_mesh(MeshConfig(data=2, fsdp=4))):
        assert resolve_collective_matmul(cfg) is False
    # Pipeline meshes keep GSPMD TP (stage-manual nesting unsupported).
    with jax.set_mesh(make_mesh(MeshConfig(stage=2, fsdp=2, tensor=2))):
        assert resolve_collective_matmul(cfg) is False
    with pytest.raises(ValueError, match="collective_matmul"):
        resolve_collective_matmul(
            dataclasses.replace(cfg, collective_matmul="rings"))


# ---------------------------------------------------------------------------
# Train step / LoRA / accumulation composition
# ---------------------------------------------------------------------------

def _train_setup(cfg, mesh, **step_kw):
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step

    opt = make_optimizer(OptimizerConfig(total_steps=8, warmup_steps=0))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings, **step_kw)
    return state, step


def _batch(cfg, b=8, s=16, seed=3):
    t = np.asarray(toks(cfg, b=b, s=s + 1, seed=seed))
    return {"tokens": t[:, :-1], "targets": t[:, 1:],
            "loss_mask": np.ones((b, s), np.float32)}


@pytest.mark.parametrize("step_kw", [
    pytest.param(dict(), id="plain"),
    pytest.param(dict(accumulate_steps=2), id="accum2"),
    # ROADMAP.md D5: a wrong VALUE, not a flake (loss 4.179 under the ring
    # with accumulate_steps=2 and the chunked loss, 4.119 under GSPMD) on a
    # path no benchmark cell runs. Strict, so that a repair of the ring is
    # noticed and tier-1's exit code means something meanwhile.
    pytest.param(dict(accumulate_steps=2, loss_chunk=8),
                 id="accum2-chunked-ce",
                 marks=pytest.mark.xfail(
                     strict=True, reason="ROADMAP.md D5: the ring's loss is "
                     "off in the second digit under accumulate_steps=2 "
                     "with the chunked loss")),
])
def test_train_step_matches_gspmd(step_kw):
    """Loss and grad_norm over two optimizer steps, ring vs GSPMD — with
    gradient accumulation and the chunked fused CE composed on top."""
    cfg = cm_cfg()
    ring = dataclasses.replace(cfg, collective_matmul="ring")
    mesh = make_mesh(MeshConfig(**TP2_MESH))
    batch = _batch(cfg)

    results = {}
    for name, c in (("off", cfg), ("ring", ring)):
        state, step = _train_setup(c, mesh, **step_kw)
        metrics_seen = []
        with jax.set_mesh(mesh):
            for _ in range(2):
                state, metrics = step(state, batch)
                metrics_seen.append((float(metrics["loss"]),
                                    float(metrics["grad_norm"])))
        results[name] = metrics_seen
    for (lo, go), (lr, gr) in zip(results["off"], results["ring"]):
        np.testing.assert_allclose(lr, lo, rtol=1e-5)
        np.testing.assert_allclose(gr, go, rtol=1e-4)


def test_lora_train_step_matches_gspmd():
    """LoRA merges deltas into the base weights inside the differentiated
    graph; the ring custom-VJP must carry grads back through the merge to
    A/B identically to GSPMD."""
    from runbooks_tpu.train.lora import (
        LoraConfig,
        create_lora_train_state,
        make_lora_train_step,
    )
    from runbooks_tpu.models.transformer import param_logical_axes
    from runbooks_tpu.parallel.sharding import tree_shardings
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer

    cfg = cm_cfg()
    ring = dataclasses.replace(cfg, collective_matmul="ring")
    mesh = make_mesh(MeshConfig(**TP2_MESH))
    lora_cfg = LoraConfig(rank=4)
    base = init_params(cfg, jax.random.key(0))
    base_shardings = tree_shardings(
        jax.eval_shape(lambda: base), param_logical_axes(cfg), mesh)
    base = jax.device_put(base, base_shardings)
    batch = _batch(cfg)
    opt = make_optimizer(OptimizerConfig(total_steps=8, warmup_steps=0))

    results = {}
    for name, c in (("off", cfg), ("ring", ring)):
        state, shardings = create_lora_train_state(
            c, lora_cfg, base, opt, mesh, jax.random.key(1))
        step = make_lora_train_step(c, lora_cfg, opt, mesh, shardings,
                                    base_shardings)
        with jax.set_mesh(mesh):
            state, metrics = step(state, base, batch)
            results[name] = (float(metrics["loss"]),
                             float(metrics["grad_norm"]))
    np.testing.assert_allclose(results["ring"][0], results["off"][0],
                               rtol=1e-5)
    np.testing.assert_allclose(results["ring"][1], results["off"][1],
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# Controller / serve contract surface
# ---------------------------------------------------------------------------

def test_validate_params_collective_matmul():
    for key in ("collective_matmul", "collectiveMatmul", "collectivematmul"):
        for val in ("off", "ring", "auto"):
            assert validate_params({key: val}) is None, (key, val)
        err = validate_params({key: "rings"})
        assert err is not None and key in err
    assert validate_params({"collective_matmul": "on"}) is not None
    assert validate_params({"collective_matmul": 1}) is not None


def test_trainer_config_aliases_and_validation():
    from runbooks_tpu.train.trainer import TrainJobConfig, run_training

    job = TrainJobConfig.from_params({"collectiveMatmul": "ring"})
    assert job.collective_matmul == "ring"
    job = TrainJobConfig.from_params({"collectivematmul": "auto"})
    assert job.collective_matmul == "auto"
    with pytest.raises(ValueError, match="collective_matmul"):
        run_training(TrainJobConfig(collective_matmul="rings", steps=1))


def test_serve_load_model_rejects_bad_spelling(tmp_path):
    from runbooks_tpu.serve.api import load_model

    with pytest.raises(ValueError, match="collective_matmul"):
        load_model({"model": "debug", "checkpoint": str(tmp_path),
                    "collective_matmul": "rings"})
    cfg, _ = load_model({"model": "debug", "checkpoint": str(tmp_path),
                         "collective_matmul": "auto"})
    assert cfg.collective_matmul == "auto"
    # The controller validates the camelCase spec spelling for serve specs
    # too — a validated spec must not silently serve without the ring.
    cfg, _ = load_model({"model": "debug", "checkpoint": str(tmp_path),
                         "collectiveMatmul": "ring"})
    assert cfg.collective_matmul == "ring"


def test_engine_serves_with_ring_and_logs_census(capsys):
    """End-to-end serve smoke on a TP mesh with the ring path on: warmup
    (census line), batched prefill, chunked decode. Numerical parity of the
    underlying programs is covered by the forward/cache tests."""
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = cm_cfg(collective_matmul="ring")
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh(MeshConfig(**TP2_MESH))
    eng = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                          mesh=mesh, decode_chunk=2)
    eng.warmup()
    census = [l for l in capsys.readouterr().out.splitlines()
              if "warmup census" in l]
    assert len(census) == 1 and "prefill programs" in census[0]
    reqs = [Request(prompt_tokens=[1, 2, 3, 4], max_tokens=8),
            Request(prompt_tokens=[5, 6, 7], max_tokens=8)]
    eng.generate(reqs, timeout_s=300)
    assert all(r.finished and len(r.output_tokens) == 8 for r in reqs)
