"""Trainer workload tests: params.json -> training -> artifacts, and resume."""

import json
import os

import pytest

from runbooks_tpu.parallel.mesh import MeshConfig
from runbooks_tpu.train.lora import LoraConfig
from runbooks_tpu.train.optimizer import OptimizerConfig
from runbooks_tpu.train.trainer import TrainJobConfig, run_training
from runbooks_tpu.utils import contract


def job(tmp_path, steps=6, data_path=None, **kw):
    return TrainJobConfig(
        model="debug", model_overrides={"dtype": "float32"},
        mesh=MeshConfig(data=2, fsdp=2, sequence=1, tensor=2),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=0,
                                  total_steps=100, schedule="constant"),
        batch_size=4, seq_len=32, steps=steps,
        checkpoint_every=3, log_every=2,
        artifacts_dir=str(tmp_path), data_path=data_path, **kw,
    )


def test_training_writes_artifacts_and_metrics(tmp_path):
    summary = run_training(job(tmp_path))
    assert summary["final_loss"] is not None
    assert os.path.exists(tmp_path / "metrics.json")
    assert os.path.isdir(tmp_path / "checkpoints")
    steps = os.listdir(tmp_path / "checkpoints")
    assert "6" in steps


@pytest.mark.slow
def test_training_resumes_from_checkpoint(tmp_path):
    run_training(job(tmp_path, steps=3))
    # Second run with more steps resumes at 3, trains to 6.
    summary = run_training(job(tmp_path, steps=6))
    assert summary["history"][0]["step"] > 3 or summary["history"][0]["step"] == 4


def test_trainer_from_params_json(tmp_path):
    params = {
        "model": "debug", "steps": 4, "batch_size": 2, "seq_len": 16,
        "mesh_data": 1, "mesh_fsdp": 8, "mesh_tensor": 1,
        "learning_rate": 1e-3, "checkpoint_every": 10,
        "artifacts_dir": str(tmp_path),
        "model_overrides": {"dtype": "float32"},
    }
    j = TrainJobConfig.from_params(params)
    assert j.mesh.fsdp == 8 and j.steps == 4
    summary = run_training(j)
    assert summary["steps"] == 4


def test_trainer_with_jsonl_data_and_lora(tmp_path):
    data = tmp_path / "data"
    os.makedirs(data)
    with open(data / "docs.jsonl", "w") as f:
        for i in range(30):
            f.write(json.dumps({"text": f"document number {i} " * 3}) + "\n")
    summary = run_training(job(
        tmp_path, steps=4, data_path=str(data), lora=LoraConfig(rank=2)))
    assert summary["lora"] is True
    assert os.path.exists(tmp_path / "lora.json")


def test_from_params_accumulate_aliases_and_string_ints():
    # camelCase (reference spec style) and env-lowercased spellings both
    # land on accumulate_steps, and YAML-quoted ints coerce — a
    # controller-validated spec must not silently drop accumulation or
    # TypeError mid-job.
    j = TrainJobConfig.from_params({"accumulateSteps": "8",
                                    "batch_size": "64"})
    assert j.accumulate_steps == 8 and j.batch_size == 64
    j = TrainJobConfig.from_params({"accumulatesteps": 4})
    assert j.accumulate_steps == 4
    j = TrainJobConfig.from_params({"accumulate_steps": 2,
                                    "accumulateSteps": 16})
    assert j.accumulate_steps == 2  # snake_case wins


def test_trainer_fast_path_accum_chunk_prefetch(tmp_path):
    # The whole training fast path at once: 2-way grad accumulation,
    # chunked fused CE, and the async prefetcher (default depth 2).
    summary = run_training(job(
        tmp_path, steps=4, accumulate_steps=2, loss_chunk=16))
    assert summary["final_loss"] is not None
    assert summary["accumulate_steps"] == 2
    # Compile time is reported separately and excluded from the
    # steady-state tokens/sec window (the first-step reset).
    assert summary["compile_time_s"] is not None
    assert summary["compile_time_s"] > 0
    assert summary["history"][0]["compile_time_s"] == round(
        summary["compile_time_s"], 2)
    assert summary["tokens_per_sec"] > 0


def test_trainer_accum_must_divide_batch(tmp_path):
    import pytest

    with pytest.raises(ValueError, match="divide"):
        run_training(job(tmp_path, steps=2, accumulate_steps=3))


def test_trainer_rejects_oversized_tokenizer_vocab(tmp_path):
    import json as _json

    import pytest

    data = tmp_path / "data"
    os.makedirs(data)
    with open(data / "docs.jsonl", "w") as f:
        f.write(_json.dumps({"text": "hello"}) + "\n")
    # Byte tokenizer vocab is 258 > the overridden model vocab of 128:
    # must raise (not assert — python -O would strip an assert).
    import dataclasses

    small_vocab = dataclasses.replace(
        job(tmp_path, steps=1, data_path=str(data)),
        model_overrides={"dtype": "float32", "vocab_size": 128})
    with pytest.raises(ValueError, match="vocab"):
        run_training(small_vocab)


def test_params_env_roundtrip(monkeypatch):
    monkeypatch.setenv("PARAM_STEPS", "7")
    monkeypatch.setenv("PARAM_MODEL", "debug")
    params = contract.load_params(path="/nonexistent/params.json")
    assert params["steps"] == 7
    assert params["model"] == "debug"
    env = contract.params_to_env({"steps": 7, "model": "debug"})
    assert env == {"PARAM_STEPS": "7", "PARAM_MODEL": "debug"}


def test_start_up_line_says_the_heads_a_flash_step_holds(tmp_path, capsys):
    """On the flash path the start-up line and the summary carry what
    ops/flash_attention.head_block answers for the step's shapes, per
    shard: `debug`'s query heads on its KV heads, two ways over `tensor`.
    On the XLA path (the CPU's `auto`) there is no such field."""
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import flash_heads_per_step

    cfg = get_config("debug")
    flash = job(tmp_path, steps=1)
    flash.model_overrides["attention_impl"] = "flash"
    summary = run_training(flash)
    want = flash_heads_per_step(cfg, 32, 32, 2)
    assert want == {"full_attention":
                    cfg.num_heads // max(cfg.num_kv_heads, 2)}
    assert summary["flash_head_block"] == want
    start = next(json.loads(line) for line in
                 capsys.readouterr().out.splitlines()
                 if line.startswith('{"startup": "train"'))
    assert start["attention_impl"] == "flash"
    assert start["flash_head_block"] == want
    # ... and the block shape of the forward and of the backward
    # (block_shape's answers, clamped to 32 tokens a row).
    blocks = {"full_attention": {"fwd": [32, 32], "bwd": [32, 32]}}
    assert start["flash_blocks"] == summary["flash_blocks"] == blocks
    xla = run_training(job(tmp_path / "xla", steps=1))
    assert "flash_head_block" not in xla and "flash_blocks" not in xla
