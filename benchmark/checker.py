#!/usr/bin/env python3
"""The comparison that decides `correct`, in a process of its own.

Run by run.py AFTER the window has closed and the serving or training
process has exited (so the chip is free and memory_peak_bytes stays the
program's). It makes the weights again from the seed, runs the plain
reference (benchmark/reference/<family>.py) and compares it with what the
TIMED path produced:

  serve  a seeded sample of the requests the window finished (the longest
         in it): the reference runs once over each prompt with its served
         tokens, and the number compared is the widest gap by which a
         served token's logit lies below the reference's best at that
         position. Greedy tokens only. A sequence may have any length:
         it is padded to a length bucket (SEQ_BUCKETS, beyond them the
         next multiple of SEQ_STEP) and the reference is asked for the
         logits of the served rows alone, in a row bucket of their own
         (ROW_BUCKETS, then multiples of ROW_STEP), never for
         [length bucket, vocab]. What holds a check back is the chip's
         memory and run.py's 600 s for this process, nothing here.
  train  the losses of the first steps of the one compiled step the window
         then drove, the norm of the first gradient as the optimizer got
         it (from its saved state after one step), and the norm of the
         adapters' change after the first steps (from its saved state),
         each by the worst leaf.

Every number is printed beside its limit on a `check:` line. With
"control" in the job, the lower-precision control's numbers are read too
(int8 products in the reference's own mathematics); the benchmark's own
runs do not ask for it. Last line: {"correct": ..., "numbers": [...]}.
"""

from __future__ import annotations

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from benchlib import spec  # noqa: E402

# Lengths a sequence is padded to: these as they stand (the cells' shapes
# and their compiled references recur), beyond them multiples of SEQ_STEP
# (10 100 tokens -> 10 240, not 16 384). The rows whose logits are asked
# for get buckets of their own.
SEQ_BUCKETS, SEQ_STEP = (256, 512, 1024, 2048, 4096), 2048
ROW_BUCKETS, ROW_STEP = (32, 64, 128, 256), 256


def bucket(n: int, small: tuple, step: int) -> int:
    """The least of `small` that holds n; beyond them the next multiple of
    `step`."""
    return next((b for b in small if b >= n), -(-n // step) * step)


def setup_jax(chips: int):
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:   # the fixed directory the program's entry points use
        cache = os.path.join(ROOT, ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    shard = None
    if chips > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import numpy as np

        mesh = Mesh(np.array(jax.devices()[:chips]), ("x",))

        def shard(shape):
            # Split the widest non-layer axis that divides over the chips.
            axes = [None] * len(shape)
            order = sorted(range(1 if len(shape) == 3 else 0, len(shape)),
                           key=lambda i: -shape[i])
            for i in order:
                if shape[i] % chips == 0:
                    axes[i] = "x"
                    break
            return NamedSharding(mesh, P(*axes))
    return jax, shard


def check_line(n: dict) -> str:
    return (f"check: {n['name']} {n['value']:.6g} (limit {n['limit']:.6g}) "
            f"{'ok' if n['ok'] else 'OUT'}")


def number(name, value, limit):
    """A number compared: sound at its limit or under. Printed beside it."""
    n = {"name": name, "value": float(value), "limit": float(limit),
         "ok": bool(value <= limit)}
    print(check_line(n), flush=True)
    return n


def serve_gaps(ref, as_run, w, sequences, control=False):
    """For every served token of every sequence: the gap by which its
    logit lies below the reference's best at that position, and whether it
    IS the best. With `control`, also the gap of the token the int8
    products put first at each of the same positions.

    What a reference's `logits_at(as_run, w, tokens, rows, low)` may rely
    on, and must do. `tokens` is the prompt with its served tokens, padded
    with zeros to a length bucket; `rows` are the positions whose logits
    are compared, `len(prompt) - 1 ... len(tokens) - 2` and then the last
    repeated to a row bucket, so `rows[0] + 1` is the prompt's length (a
    model whose mask depends on the length of the call that computed a
    query needs it). The padding lies behind every compared row, so under
    a causal mask no compared row attends it. It returns [len(rows),
    vocab] logits, never [len(tokens), vocab], and computes a long
    sequence in blocks so that it fits: the checker does not do that for
    it."""
    import numpy as np

    gaps, low_gaps, agree = [], [], []
    for seq in sequences:
        prompt, served = seq["prompt_ids"], seq["served_ids"]
        toks = prompt + served
        padded = np.zeros(bucket(len(toks), SEQ_BUCKETS, SEQ_STEP), np.int32)
        padded[:len(toks)] = toks
        # Row p predicts token p + 1: the served tokens are predicted at
        # rows len(prompt) - 1 ... len(toks) - 2.
        rows = np.arange(len(prompt) - 1, len(toks) - 1)
        n = len(rows)
        rows_p = np.full(bucket(n, ROW_BUCKETS, ROW_STEP), rows[-1], np.int32)
        rows_p[:n] = rows
        logits = np.asarray(ref.logits_at(as_run, w, padded, rows_p))[:n]
        best = logits.max(axis=-1)
        gaps.append(best - logits[np.arange(n), np.asarray(served)])
        agree.append(logits.argmax(axis=-1) == np.asarray(served))
        if control:
            low = np.asarray(ref.logits_at(as_run, w, padded, rows_p,
                                           low=True))[:n]
            low_gaps.append(best - logits[np.arange(n), low.argmax(axis=-1)])
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)  # noqa: E731
    return {"gaps": cat(gaps), "agree": cat(agree), "control": cat(low_gaps)}


def check_serve(job, ref, jax, shard):
    import numpy as np

    as_run = job["config"]["as_run"]
    w = ref.init_weights(as_run, job["seed"], shard)
    got = serve_gaps(ref, as_run, w, job["sequences"], job.get("control"))
    gaps, low = got["gaps"], got["control"]
    if not len(gaps):
        print("check: no finished request to compare", flush=True)
        return [{"name": "sequences", "value": 0, "limit": 1, "ok": False}]
    limits = job["limits"]
    print(f"check: compared {len(gaps)} served tokens of "
          f"{len(job['sequences'])} requests; served token is the "
          f"reference's best at {got['agree'].mean():.3f} of "
          f"positions; gap mean {gaps.mean():.5g} p99 "
          f"{np.percentile(gaps, 99):.5g}", flush=True)
    out = [number("served_logit_gap_max", float(gaps.max()),
                  limits.get("served_logit_gap_max", float("inf"))),
           number("served_logit_gap_mean", float(gaps.mean()),
                  limits.get("served_logit_gap_mean", float("inf")))]
    if len(low):
        print(f"check: CONTROL (int8 products) gap max {low.max():.6g} "
              f"mean {low.mean():.6g} p99 {np.percentile(low, 99):.6g}",
              flush=True)
    return out


def find_adam(tree):
    """The (mu, nu) of the saved optimizer state, wherever optax put it."""
    if isinstance(tree, dict):
        if "mu" in tree and "nu" in tree:
            return tree["mu"], tree["nu"]
        children = tree.values()
    elif isinstance(tree, (list, tuple)):
        children = tree
    else:
        return None
    for child in children:
        found = find_adam(child)
        if found is not None:
            return found
    return None


def leaf_norms(tree, targets):
    import numpy as np

    return {f"{t}.{ab}": float(np.linalg.norm(
        np.asarray(tree[t][ab], np.float32))) for t in targets
        for ab in ("a", "b")}


def worst_leaf_gap(prog: dict, refn: dict) -> float:
    """Gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    import statistics

    med = statistics.median(refn.values())
    return max(abs(prog[k] - refn[k]) / max(refn[k], med, 1e-30)
               for k in refn)


def worst_leaf_difference(prog: dict, refn: dict, targets) -> float:
    """Norm of (program - reference) over the reference's norm, by the
    worst leaf whose reference norm is at least the median leaf's (a leaf
    that is all but zero has no direction). Unlike a gap between norms
    this sees noise that leaves the length alone, which is what a lower
    precision adds."""
    import statistics

    import numpy as np

    norms = leaf_norms(refn, targets)
    med = statistics.median(norms.values())
    worst = 0.0
    for t in targets:
        for ab in ("a", "b"):
            n = norms[f"{t}.{ab}"]
            if n >= med and n > 0:
                d = np.asarray(prog[t][ab], np.float32) \
                    - np.asarray(refn[t][ab], np.float32)
                worst = max(worst, float(np.linalg.norm(d)) / n)
    return worst


def check_train(job, ref, jax, shard):
    import numpy as np
    import orbax.checkpoint as ocp

    as_run, tj = job["config"]["as_run"], job["job"]
    rank, alpha = int(tj["lora"]["rank"]), float(tj["lora"].get("alpha", 16))
    w = ref.init_weights(as_run, job["seed"], shard)
    lora0 = ref.init_lora(as_run, job["seed"], rank)
    bs = int(tj["batch_size"])
    n_steps = len(job["losses"])
    rows = [{k: np.asarray(v, np.float32 if k == "loss_mask" else np.int32)
             for k, v in r.items()} for r in job["rows"]]

    def follow(low):
        lora = lora0
        mu = jax.tree.map(lambda x: x * 0, lora0)
        nu = jax.tree.map(lambda x: x * 0, lora0)
        losses, first = [], None
        for i in range(n_steps):
            loss, grads = ref.loss_and_grads(
                as_run, w, lora, rows[i * bs:(i + 1) * bs], alpha / rank,
                low=low)
            grads = ref.clip(grads, tj.get("grad_clip_norm"))
            first = grads if first is None else first
            lora, mu, nu = ref.adamw_step(tj, lora, grads, mu, nu, i)
            losses.append(loss)
        change = jax.tree.map(lambda a, b: a - b, lora, lora0)
        return losses, first, change

    targets = list(ref.LORA_TARGETS)
    losses, first, change = follow(False)
    ckpt = ocp.StandardCheckpointer()

    def saved(step):
        return ckpt.restore(os.path.join(
            os.path.abspath(job["checkpoints"]), str(step), "default"))

    names = {t: "attn." + t for t in targets}
    s1, s3 = saved(job["steps"][0]), saved(job["steps"][1])
    mu1, _ = find_adam(s1["opt_state"])
    b1 = float(tj["b1"])
    prog_first = {t: {ab: np.asarray(mu1[names[t]][ab], np.float32)
                      / (1 - b1) for ab in ("a", "b")} for t in targets}
    prog_change = {t: {ab: np.asarray(s3["params"][names[t]][ab], np.float32)
                       - np.asarray(lora0[t][ab]) for ab in ("a", "b")}
                   for t in targets}
    limits = job["limits"]
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(job["losses"], losses))
    print("check: losses program " + str(job["losses"]) + " reference "
          + str([round(x, 5) for x in losses]), flush=True)
    grad_gap = worst_leaf_gap(leaf_norms(prog_first, targets),
                              leaf_norms(first, targets))
    change_gap = worst_leaf_gap(leaf_norms(prog_change, targets),
                                leaf_norms(change, targets))
    diff_gap = worst_leaf_difference(prog_first, first, targets)
    out = [number("loss_gap", loss_gap, limits.get("loss_gap", float("inf"))),
           number("first_grad_difference", diff_gap,
                  limits.get("first_grad_difference", float("inf"))),
           number("first_grad_norm_gap", grad_gap,
                  limits.get("first_grad_norm_gap", float("inf"))),
           number("param_change_norm_gap", change_gap,
                  limits.get("param_change_norm_gap", float("inf")))]
    if job.get("control"):
        llow, flow, clow = follow(True)
        print("check: CONTROL (int8 products) loss_gap "
              f"{max(abs(p - r) / abs(r) for p, r in zip(llow, losses)):.6g}"
              " first_grad_difference "
              f"{worst_leaf_difference(flow, first, targets):.6g}"
              " first_grad_norm_gap "
              f"{worst_leaf_gap(leaf_norms(flow, targets), leaf_norms(first, targets)):.6g}"
              " param_change_norm_gap "
              f"{worst_leaf_gap(leaf_norms(clow, targets), leaf_norms(change, targets)):.6g}",
              flush=True)
    return out


def main(argv=None) -> int:
    job = spec.load_json((argv or sys.argv[1:])[0])
    jax, shard = setup_jax(int(job["chips"]))
    ref = spec.load_module(
        os.path.join(BENCH_DIR, "reference",
                     job["config"]["reference"] + ".py"), "bench_reference")
    numbers = (check_train if job["kind"] == "train" else check_serve)(
        job, ref, jax, shard)
    print(json.dumps({"correct": all(n["ok"] for n in numbers),
                      "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
