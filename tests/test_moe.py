"""MoE + expert-parallelism tests.

Oracle strategy: expert-parallel meshes must produce bit-for-bit the same
results as replicated meshes (routing is deterministic); the cached decode
path must match the no-cache forward; and the load-balance aux loss must
reach the training objective.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh


def moe_cfg(**over):
    kw = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
              num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
              max_seq_len=32, dtype="float32", moe_num_experts=4,
              moe_top_k=2)
    kw.update(over)
    return get_config("debug", **kw)


def tokens_for(cfg, b=4, s=12, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)


def test_moe_forward_and_aux():
    cfg = moe_cfg()
    params = init_params(cfg, jax.random.key(0))
    assert "moe" in params["layers"] and "mlp" not in params["layers"]
    toks = tokens_for(cfg)
    logits, _, aux = forward(cfg, params, toks, with_aux=True)
    assert logits.shape == (4, 12, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # Switch aux loss: E * sum(me*ce) >= 1 (equality at perfect balance).
    assert float(aux) >= cfg.num_layers * 0.99


def test_moe_routing_actually_mixes_experts():
    cfg = moe_cfg()
    params = init_params(cfg, jax.random.key(0))
    toks = tokens_for(cfg, b=2, s=16)
    # Zeroing one expert's weights changes the output only if that expert
    # receives traffic.
    logits1, _ = forward(cfg, params, toks)
    broken = jax.tree.map(lambda a: a, params)
    wo = np.asarray(broken["layers"]["moe"]["wo"]).copy()
    wo[:, 0] = 0.0
    broken["layers"]["moe"]["wo"] = jnp.asarray(wo)
    logits2, _ = forward(cfg, broken, toks)
    assert not np.allclose(np.asarray(logits1), np.asarray(logits2))


def test_moe_expert_parallel_matches_replicated():
    cfg = moe_cfg()
    params = init_params(cfg, jax.random.key(0))
    toks = tokens_for(cfg, b=8, s=8)

    plain = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain):
        want, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, toks)

    ep = make_mesh(MeshConfig(data=2, expert=4, fsdp=1))
    with jax.set_mesh(ep):
        got, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, toks)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_moe_cached_decode_matches_full_forward():
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = moe_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2)

    prompt = [5, 9, 17]
    req = Request(prompt_tokens=list(prompt), max_tokens=6, temperature=0.0)
    engine.generate([req])

    toks = list(prompt)
    for _ in range(6):
        logits, _ = forward(cfg, params, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert req.output_tokens == toks[len(prompt):]


def test_moe_train_step_learns_and_balances():
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step

    cfg = moe_cfg()
    mesh = make_mesh(MeshConfig(data=2, expert=2, fsdp=1, tensor=2))
    opt = make_optimizer(OptimizerConfig(total_steps=6, warmup_steps=0,
                                         learning_rate=1e-2))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)

    # Expert weights sharded over the expert axis (the memory win of EP).
    wi = state.params["layers"]["moe"]["wi_gate"]
    assert wi.sharding.spec[1] == "expert"

    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 13)).astype(np.int32)
    batch = {"tokens": data[:, :-1], "targets": data[:, 1:],
             "loss_mask": np.ones((8, 12), np.float32)}
    losses = []
    with jax.set_mesh(mesh):
        for _ in range(5):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_moe_composes_with_pipeline():
    cfg = moe_cfg(num_layers=4)
    params = init_params(cfg, jax.random.key(0))
    toks = tokens_for(cfg, b=4, s=8)

    plain = make_mesh(MeshConfig(fsdp=8))
    with jax.set_mesh(plain):
        want, _, aux_want = jax.jit(
            lambda p, t: forward(cfg, p, t, with_aux=True))(params, toks)

    pp = make_mesh(MeshConfig(stage=2, expert=2, fsdp=2))
    with jax.set_mesh(pp):
        got, _, aux_got = jax.jit(
            lambda p, t: forward(cfg, p, t, with_aux=True))(params, toks)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # aux under PP is a mean of per-microbatch balance losses — close to
    # but not identical to the full-batch loss (nonlinear in the batch).
    assert np.isfinite(float(aux_got))
    assert abs(float(aux_got) - float(aux_want)) / float(aux_want) < 0.25


def test_moe_sharded_serving_matches_unsharded():
    """The serving engine under an expert+tensor mesh produces the same
    greedy decode as unsharded (EP in the decode path)."""
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = moe_cfg()
    params = init_params(cfg, jax.random.key(0))
    prompts = [[5, 9, 17], [3, 4, 5, 6]]

    plain = InferenceEngine(cfg, params, max_slots=2)
    plain_reqs = [Request(prompt_tokens=list(p), max_tokens=6,
                          temperature=0.0) for p in prompts]
    plain.generate(plain_reqs)

    mesh = make_mesh(MeshConfig(data=1, expert=4, fsdp=1, tensor=2))
    sharded = InferenceEngine(cfg, params, max_slots=2, mesh=mesh)
    shard_reqs = [Request(prompt_tokens=list(p), max_tokens=6,
                          temperature=0.0) for p in prompts]
    sharded.generate(shard_reqs)

    for a, b in zip(plain_reqs, shard_reqs):
        assert a.output_tokens == b.output_tokens
    wi = sharded.params["layers"]["moe"]["wi_gate"]
    assert wi.sharding.spec[1] == "expert"
