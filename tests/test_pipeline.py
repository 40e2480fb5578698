"""Pipeline-parallelism tests: the GPipe-over-stage-axis path must be
numerically identical to the plain layer scan (same params, same batch),
forward and backward, and must compose with the train step.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh


def pp_cfg(**over):
    kw = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
              num_layers=4, num_heads=4, num_kv_heads=4, head_dim=8,
              max_seq_len=16, dtype="float32")
    kw.update(over)
    return get_config("debug", **kw)


def batch_tokens(cfg, b=8, s=12, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)


def test_pipeline_forward_matches_plain():
    cfg = pp_cfg()
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg)

    plain_mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    with jax.set_mesh(plain_mesh):
        want, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)

    pp_mesh = make_mesh(MeshConfig(data=2, stage=4, fsdp=1))
    with jax.set_mesh(pp_mesh):
        got, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_more_microbatches_than_stages():
    cfg = pp_cfg(pipeline_microbatches=4)
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg)

    plain = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain):
        want, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)

    pp_mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
    with jax.set_mesh(pp_mesh):
        got, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_match_plain():
    cfg = pp_cfg()
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg)
    targets = batch_tokens(cfg, seed=1)

    def loss_fn(p, t, y):
        logits, _ = forward(cfg, p, t)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))

    plain_mesh = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain_mesh):
        want = jax.jit(jax.grad(loss_fn))(params, tokens, targets)

    pp_mesh = make_mesh(MeshConfig(stage=4, fsdp=2))
    with jax.set_mesh(pp_mesh):
        got = jax.jit(jax.grad(loss_fn))(params, tokens, targets)

    flat_w, _ = jax.tree.flatten(want)
    flat_g, _ = jax.tree.flatten(got)
    for w, g in zip(flat_w, flat_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


def test_pipeline_train_step_runs():
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step

    cfg = pp_cfg()
    mesh = make_mesh(MeshConfig(data=2, stage=2, fsdp=1, tensor=2))
    opt = make_optimizer(OptimizerConfig(total_steps=4, warmup_steps=0))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)

    tokens = np.asarray(batch_tokens(cfg, b=8, s=13))
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
             "loss_mask": np.ones((8, 12), np.float32)}
    with jax.set_mesh(mesh):
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # actually learning through the pipeline

    # Layer params really are stage-sharded (the point of PP: per-device
    # parameter memory drops by the stage factor).
    wq = state.params["layers"]["attn"]["wq"]
    assert wq.sharding.spec[0] == "stage"


def test_pipeline_rejects_indivisible():
    cfg = pp_cfg(num_layers=3)
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg)
    mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="not divisible"):
            jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)


def loss_weight_grads_ref(cfg, params, tokens, targets, mask=None):
    """Oracle: plain autodiff CE loss/grads (runs GPipe when the active
    mesh has stage > 1, plain scan otherwise)."""
    from runbooks_tpu.train.step import cross_entropy_loss

    def loss_fn(p):
        logits, _, aux = forward(cfg, p, tokens, with_aux=True)
        loss, total = cross_entropy_loss(logits, targets, mask)
        if cfg.moe_num_experts:
            loss = loss + cfg.moe_aux_coef * aux
        return loss, total

    (loss, total), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return loss, grads, total


def test_1f1b_matches_autodiff_grads():
    """The explicit 1F1B backward must reproduce plain-autodiff loss and
    grads exactly (same math, different schedule) — including with more
    microbatches than stages and a non-trivial loss mask."""
    from runbooks_tpu.models.transformer import loss_and_grads_1f1b

    cfg = pp_cfg(pipeline_microbatches=4)
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg)
    targets = batch_tokens(cfg, seed=1)
    rng = np.random.default_rng(2)
    mask = jnp.asarray(rng.integers(0, 2, tokens.shape), jnp.float32)

    plain = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain):
        want_loss, want_grads, want_total = jax.jit(
            lambda p: loss_weight_grads_ref(cfg, p, tokens, targets, mask)
        )(params)

    pp_mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
    with jax.set_mesh(pp_mesh):
        got_loss, got_grads, got_total = jax.jit(
            lambda p: loss_and_grads_1f1b(cfg, p, tokens, targets, mask)
        )(params)

    assert float(got_total) == float(want_total)
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5)
    flat_w, tw = jax.tree.flatten(want_grads)
    flat_g, tg = jax.tree.flatten(got_grads)
    assert tw == tg
    for w, g in zip(flat_w, flat_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_1f1b_train_step_matches_gpipe_step():
    """Full train step through both schedules from identical state: same
    loss metric, same updated params (1F1B is a reschedule, not a
    different optimizer path)."""
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step

    tokens = None
    results = {}
    for schedule in ("gpipe", "1f1b"):
        cfg = pp_cfg(pipeline_schedule=schedule, pipeline_microbatches=4)
        mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
        opt = make_optimizer(OptimizerConfig(total_steps=4, warmup_steps=0))
        state, shardings = create_train_state(cfg, opt, mesh,
                                              jax.random.key(0))
        step = make_train_step(cfg, opt, mesh, shardings)
        if tokens is None:
            tokens = np.asarray(batch_tokens(cfg, b=8, s=13))
        batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
                 "loss_mask": np.ones((8, 12), np.float32)}
        with jax.set_mesh(mesh):
            state, metrics = step(state, batch)
        results[schedule] = (float(metrics["loss"]),
                             jax.tree.map(np.asarray, state.params))
    assert np.isclose(results["gpipe"][0], results["1f1b"][0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(results["gpipe"][1]),
                    jax.tree.leaves(results["1f1b"][1])):
        np.testing.assert_allclose(b, a, rtol=5e-4, atol=5e-5)


def test_1f1b_rejects_indivisible_microbatches():
    from runbooks_tpu.models.transformer import loss_and_grads_1f1b

    cfg = pp_cfg(pipeline_microbatches=3)
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg, b=6)
    mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="divisible by"):
            jax.jit(lambda p: loss_and_grads_1f1b(
                cfg, p, tokens, tokens))(params)


def test_1f1b_activation_memory_bounded_by_stages():
    """1F1B's cross-tick activation state is a ring of min(M, 2S-1)
    microbatch inputs (+ the dx bank), while GPipe autodiff tapes every
    microbatch's per-layer activations. At CONSTANT microbatch size
    (batch grows with M), GPipe's tape grows by a full per-microbatch
    activation set for every added microbatch; 1F1B adds only the dx-bank
    row. Compare compiled temp growth M=2 -> M=8 on a 2-stage mesh."""
    from runbooks_tpu.models.transformer import loss_and_grads_1f1b

    if "cpu" in jax.default_backend().lower():
        # Measured: CPU temp_size_in_bytes grows ~equally for both
        # schedules at constant microbatch size (~0.4 MB/mb) — it reports
        # allocation totals without liveness-based reuse across the
        # unrolled ticks, so the cross-tick bound is invisible. TPU
        # buffer assignment is liveness-accurate; the comparison runs
        # there (ROADMAP R4: never run on a device so far).
        pytest.skip("CPU memory_analysis lacks cross-tick buffer reuse")

    mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
    mb_rows = 4  # microbatch size held constant

    def temp_bytes(schedule, m):
        cfg = pp_cfg(pipeline_microbatches=m, pipeline_schedule=schedule,
                     num_layers=4, remat_policy="none")
        params = init_params(cfg, jax.random.key(0))
        tokens = batch_tokens(cfg, b=mb_rows * m, s=16)
        targets = batch_tokens(cfg, b=mb_rows * m, s=16, seed=1)
        with jax.set_mesh(mesh):
            if schedule == "1f1b":
                fn = jax.jit(lambda p: loss_and_grads_1f1b(
                    cfg, p, tokens, targets))
            else:
                fn = jax.jit(lambda p: loss_weight_grads_ref(
                    cfg, p, tokens, targets))
            mem = fn.lower(params).compile().memory_analysis()
        if mem is None:
            pytest.skip("memory_analysis unavailable on this backend")
        return mem.temp_size_in_bytes

    gpipe_growth = temp_bytes("gpipe", 8) - temp_bytes("gpipe", 2)
    f1b_growth = temp_bytes("1f1b", 8) - temp_bytes("1f1b", 2)
    assert f1b_growth < max(gpipe_growth / 2, 1), \
        (f1b_growth, gpipe_growth)


def test_pipeline_composes_with_ring_attention():
    """SP (ring attention over the sequence axis) inside PP stages: nested
    shard_map (stage manual outside, sequence manual inside) must match the
    plain forward exactly."""
    cfg = pp_cfg(attention_impl="ring")
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg, b=4, s=8)

    plain = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain):
        want, _ = jax.jit(lambda p, t: forward(
            dataclasses.replace(cfg, attention_impl="xla"), p, t))(
                params, tokens)

    mesh = make_mesh(MeshConfig(stage=2, sequence=2, fsdp=2))
    with jax.set_mesh(mesh):
        got, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, tokens)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("over", [
    dict(tie_embeddings=True),     # tied: head must stay replicated
    dict(vocab_size=65),           # odd: 65 % 2 != 0 -> replicated fallback
], ids=["tied", "indivisible-vocab"])
def test_1f1b_replicated_head_path_matches_autodiff(over):
    """The vocab-sharded head only applies to untied, stage-divisible
    vocabularies; these configs must take the replicated-head path and
    still match plain autodiff exactly."""
    from runbooks_tpu.models.transformer import loss_and_grads_1f1b

    cfg = pp_cfg(pipeline_microbatches=4, **over)
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg)
    targets = batch_tokens(cfg, seed=1)

    plain = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain):
        want_loss, want_grads, _ = jax.jit(
            lambda p: loss_weight_grads_ref(cfg, p, tokens, targets, None)
        )(params)

    pp_mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
    with jax.set_mesh(pp_mesh):
        got_loss, got_grads, _ = jax.jit(
            lambda p: loss_and_grads_1f1b(cfg, p, tokens, targets, None)
        )(params)

    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    for w, g in zip(jax.tree.leaves(want_grads), jax.tree.leaves(got_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


def test_1f1b_bf16_activations_compile_on_cpu():
    """bf16 activations cross the pipeline's psums (y broadcast, dy, dx):
    XLA CPU's AllReducePromotion crashes on bf16 all-reduces, so _psum
    upcasts around the collective there (TPU keeps native bf16). This
    pins the CPU-gate path — without the workaround this test aborts the
    process, not just fails."""
    from runbooks_tpu.models.transformer import loss_and_grads_1f1b

    cfg = pp_cfg(pipeline_microbatches=2, dtype="bfloat16")
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg)
    pp_mesh = make_mesh(MeshConfig(stage=2, fsdp=4))
    with jax.set_mesh(pp_mesh):
        loss, grads, _ = jax.jit(
            lambda p: loss_and_grads_1f1b(cfg, p, tokens, tokens))(params)
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_pipeline_composes_with_ring_flash_inner():
    """PP x SP with the FLASH ring inner (the TPU-default composition):
    forward and 1F1B gradients must match plain autodiff. This pins the
    nesting — stage-manual shard_map outside, the flash ring's own
    shard_map + custom_vjp inside."""
    from runbooks_tpu.models.transformer import loss_and_grads_1f1b

    cfg = pp_cfg(attention_impl="ring", ring_flash_inner=True,
                 flash_block_q=16, flash_block_k=16,
                 pipeline_microbatches=2)
    params = init_params(cfg, jax.random.key(0))
    tokens = batch_tokens(cfg, b=4, s=16)
    targets = batch_tokens(cfg, b=4, s=16, seed=1)

    plain = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain):
        want_loss, want_grads, _ = jax.jit(
            lambda p: loss_weight_grads_ref(
                dataclasses.replace(cfg, attention_impl="xla"),
                p, tokens, targets, None))(params)

    mesh = make_mesh(MeshConfig(stage=2, sequence=2, fsdp=2))
    with jax.set_mesh(mesh):
        got_loss, got_grads, _ = jax.jit(
            lambda p: loss_and_grads_1f1b(cfg, p, tokens, targets,
                                          None))(params)

    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5)
    for w, g in zip(jax.tree.leaves(want_grads),
                    jax.tree.leaves(got_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-4)
