#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. This process never imports a JAX backend: the child
that serves or trains holds the chip, and device identity comes from that
child's start-up line. The run writes the cell's params.json into a fixed
content directory inside the checkout, starts the NORMAL entry point
(`python -m runbooks_tpu.serve.api` / `runbooks_tpu.train.trainer`), waits
for ready, offers a ramp of the cell's own traffic (set-up), measures for
--seconds, drains, stops the child, and only then has a checker process
compare what the window served with the plain reference. The last line of
stdout is the one result object; every other fact is on earlier lines.
Each number compared stands beside its limit on a `check:` line, as the
last lines of stderr, and under `checked`, the result's last key.
No TPU, or fewer chips than the cell asks for: exit non-zero, no line.

`train_tok_s` is taken between the ends of whole steps inside the window.
`serve_tok_s` is the tokens credited inside the window over its length (a
prompt at its first token, a generated token at its arrival), so it steps
by whole prompts; the reading that does not, between the first and the
last instant at which a prefill dispatch gave its first tokens
(benchlib/arith.serve_rate), is printed beside the metrics with the count
of those instants and the largest single credit's share of the tokens
counted, so every run shows how coarse its count is.

A cell is data: BENCHMARK.json names a configuration and a traffic mix,
benchmark/configs/<config>.json and benchmark/traffic/<mix>.json hold
them, and benchmark/layer_metrics/<metric>.py reads one per-layer metric.

  --sweep r1,r2,...   (open-loop mixes) one server, one set-up, the mix
                      offered at each rate in turn: finds the knee.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import shutil
import sys
import threading
import time
import urllib.error
import urllib.request

T_PROCESS = time.monotonic()   # process start, as near as Python allows

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import checker  # noqa: E402  (its top level imports no JAX)
from benchlib import arith, client, procs, prom, spec, tokenizer  # noqa: E402
from benchlib import traffic as traffic_mod  # noqa: E402

PORT = 18180
# The normal entry points. (The tests put a broken one in their place.)
SERVE_ARGV = [sys.executable, "-m", "runbooks_tpu.serve.api"]
TRAIN_ARGV = [sys.executable, "-m", "runbooks_tpu.train.trainer"]
RUN_DEADLINE_S = 1150      # a cold first run may take 1200 s
# Decode steps a dispatch on a TPU (utils/hw.backend_tuning); the server
# does not say it anywhere a client can read (PERF.md, open questions).
DECODE_CHUNK = 8


class NoChip(SystemExit):
    """The run cannot be a measurement: no result line is printed."""


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def model_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; jax.random.key may not."""
    return int(seed) % 2147483647


# --------------------------------------------------------------------------
# Shared: work directories, identity, the checker, the result line
# --------------------------------------------------------------------------

def work_dirs(root: str, cell: spec.Cell, seed: int, trace: int):
    content = os.path.join(root, ".bench_work", cell.name)
    shutil.rmtree(content, ignore_errors=True)
    os.makedirs(content)
    logs = os.path.join(root, "chiprun_out", "bench", cell.name,
                        f"seed{seed}_t{trace}")
    shutil.rmtree(logs, ignore_errors=True)
    os.makedirs(logs)
    return content, logs


def require_tpu(ident: dict, chips: int, child: procs.Child) -> dict:
    """Identity as the serving or training process reported it. Anything
    but a TPU with at least the cell's chips ends the run without a line."""
    if ident.get("platform") != "tpu" or ident.get("backend") != "tpu":
        child.stop(10)
        raise NoChip(f"benchmark: the {child.name} process runs on "
                     f"{ident.get('platform')!r}, not a TPU: no result")
    if int(ident.get("device_count", 0)) < chips:
        child.stop(10)
        raise NoChip(f"benchmark: {ident.get('device_count')} chip(s), the "
                     f"cell needs {chips}: no result")
    spec.peaks_for(str(ident["device_kind"]))   # unknown chip: an error
    return {"platform": ident["platform"], "kind": ident["device_kind"],
            "count": int(ident["device_count"])}


def run_checker(root: str, logs: str, job: dict, timeout: float = 600):
    """The reference comparison, in a process of its own, after the child
    that held the chip has exited. Prints each number beside its limit."""
    job_path = os.path.join(logs, "check_job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    child = procs.Child(
        "checker", [sys.executable, os.path.join(BENCH_DIR, "checker.py"),
                    job_path], procs.child_env(root), root, logs)
    rc = child.wait(timeout)
    for _, text in child.lines:
        if text.startswith("check:"):
            print(text, flush=True)
    verdict = next((o for _, o in reversed(child.json_lines())
                    if "correct" in o), None)
    if rc != 0 or verdict is None:
        say(f"checker failed rc={rc}:\n{child.tail()}")
        return {"correct": False, "numbers": [
            checker.number("checker_gave_no_verdict", 1, 0)]}
    return verdict


def reduce_trace(root: str, logs: str, trace_dir: str):
    """xplane.pb -> reduced JSON, in a CPU-only child (JAX reads the file)."""
    out_path = os.path.join(logs, "trace_reduced.json")
    child = procs.Child(
        "trace_reduce",
        [sys.executable, os.path.join(BENCH_DIR, "benchlib", "tracefile.py"),
         trace_dir, out_path],
        procs.child_env(root, {"JAX_PLATFORMS": "cpu"}), root, logs)
    rc = child.wait(300)
    if rc != 0 or not os.path.exists(out_path):
        say(f"trace reduction failed rc={rc}:\n{child.tail()}")
        return None
    return spec.load_json(out_path)


def layer_metrics(cell: spec.Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell, by its own reader. A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        path = os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py")
        reader = spec.load_module(path, "layer_metric_" + m["name"])
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct, attempted, failed, metrics, device, numbers,
                breakdown=None):
    """The run's last lines: every number compared beside its limit on
    stderr, then the one result object on stdout, `checked` last in it."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # A number a configuration holds to no limit (`limits_left_out`) has
    # the limit null: `Infinity` is not JSON.
    line["checked"] = {n["name"]: {
        "value": n["value"],
        "limit": n["limit"] if math.isfinite(n["limit"]) else None}
        for n in numbers}
    for n in numbers:
        print(checker.check_line(n), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def finish_device(device: dict, peak_bytes, trace) -> dict:
    device = dict(device, memory_peak_bytes=int(peak_bytes or 0))
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return device


# --------------------------------------------------------------------------
# Serving cells
# --------------------------------------------------------------------------

# Loopback only: never through a proxy the environment may name.
_OPENER = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def http_get(url: str, timeout: float = 60.0) -> bytes:
    with _OPENER.open(url, timeout=timeout) as resp:
        return resp.read()


def serve_params(cell: spec.Cell, seed: int, content: str) -> dict:
    cfg = cell.config
    tok_dir = tokenizer.write_tokenizer(
        os.path.join(content, "tokenizer"), cfg["as_run"]["vocab_size"])
    return {"model": cfg["model"], "model_overrides": cfg["model_overrides"],
            "seed": model_seed(seed), "port": PORT, "tokenizer": tok_dir,
            **cfg["mesh_params"]["serve"], **cell.traffic["server_params"]}


def start_server(root, cell, seed, content, logs):
    params = serve_params(cell, seed, content)
    with open(os.path.join(content, "params.json"), "w") as f:
        json.dump(params, f, indent=1)
    t_spawn = time.monotonic()
    child = procs.Child(
        "server", SERVE_ARGV,
        procs.child_env(root, {"RBT_CONTENT_DIR": content}), root, logs)
    got = child.wait_json(lambda o: o.get("startup") == "serve", 900)
    if got is None:
        rc = child.stop(5)
        raise NoChip(f"benchmark: server gave no start-up line (rc={rc}):\n"
                     + child.tail())
    t_startup, ident = got
    device = require_tpu(ident, cell.chips, child)
    base = f"http://127.0.0.1:{PORT}"
    while True:   # readiness flips after warm-up
        if not child.alive():
            raise NoChip("benchmark: server exited before ready:\n"
                         + child.tail())
        try:
            http_get(base + "/", timeout=5)
            break
        except (urllib.error.URLError, OSError):
            pass
        if time.monotonic() - t_spawn > RUN_DEADLINE_S - 200:
            child.stop(5)
            raise NoChip("benchmark: server not ready in time")
        time.sleep(0.25)
    t_ready = time.monotonic()
    census = json.loads(http_get(base + "/debug/programs"))["warmup_census"]
    parts = {"start_to_spawn_s": t_spawn - T_PROCESS,
             "spawn_to_weights_s": t_startup - t_spawn,
             "weights_to_ready_s": t_ready - t_startup,
             "warmup_s": census.get("warmup_seconds"),
             "warmup_compile_s": census.get("compile_seconds"),
             "warmup_programs": census.get("compiles"),
             "cache_hits": census.get("cache_hits")}
    return child, base, device, census, parts, params


def sample_for_check(records, seed: int, k: int) -> list:
    """A seeded sample of the finished measured requests, the longest in
    it: what the reference is run over after the window."""
    import numpy as np
    done = [r for r in records if r["measured"] and r["ok"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_tokens"] + len(r["ids"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 9])
    picks = [rest[i] for i in
             rng.permutation(len(rest))[:max(0, k - 1)]] if rest else []
    return [{"prompt_ids": r["prompt_ids"], "served_ids": r["ids"]}
            for r in [longest] + picks]


def run_serve(args, root, cell) -> int:
    mix = cell.traffic
    content, logs = work_dirs(root, cell, args.seed, args.trace)
    child, base, device, census, parts, params = start_server(
        root, cell, args.seed, content, logs)
    vocab = cell.config["as_run"]["vocab_size"]
    state: dict = {}

    async def scrape(session, key):
        state[key] = prom.parse(await client.get_text(session,
                                                      base + "/metrics"))

    async def profile(session):
        secs = float(mix["trace_seconds"])
        # The capture starts some 50 ms after it is asked for.
        state["trace_window"] = (client.clock() + 0.05,
                                 client.clock() + 0.05 + secs)
        async with session.post(
                f"{base}/debug/profile?seconds={secs}") as resp:
            state["profile"] = await resp.json()

    try:
        if args.sweep:
            return sweep(args, base, mix, vocab, scrape, state)
        ramp_s = float(mix["ramp_seconds"])
        hooks = [(ramp_s, lambda s: scrape(s, "m0")),
                 (ramp_s + args.seconds, lambda s: scrape(s, "m1"))]
        if args.trace:
            hooks.append((ramp_s + 1.0, profile))
        if mix["kind"] == "open_loop":
            sched = traffic_mod.open_loop_schedule(
                mix, args.seconds, vocab, args.seed)
            t_load = time.monotonic() + 0.2
            t0, t1 = t_load + ramp_s, t_load + ramp_s + args.seconds
            records = asyncio.run(client.open_loop(base, sched, t_load,
                                                   hooks))
        else:
            reqs = traffic_mod.closed_loop_list(mix, vocab, args.seed)
            n_clients = int(mix["clients_per_slot"]) * int(
                params["max_slots"])
            t_load = time.monotonic() + 0.2
            t0, t1 = t_load + ramp_s, t_load + ramp_s + args.seconds
            records = asyncio.run(client.closed_loop(
                base, reqs, n_clients, t_load, t1, (t0, t1), hooks))
        parts["ramp_s"] = ramp_s
        setup_s = t0 - T_PROCESS

        mem = json.loads(http_get(base + "/debug/memory"))
        final = prom.parse(http_get(base + "/metrics").decode())
    finally:
        rc = child.stop()
    say(f"server exit code {rc}")

    measured = [r for r in records if r["measured"]]
    failed = [r for r in measured if not r["ok"]]
    late = [r["sent_t"] - r["due_t"] for r in records if r["sent_t"]]
    say("setup parts " + json.dumps(
        {k: (round(v, 3) if isinstance(v, float) else v)
         for k, v in parts.items()}))
    say(f"generator lateness ms: max {max(late) * 1e3:.2f} "
        f"p99 {arith.percentile(late, 99) * 1e3:.2f} "
        f"mean {sum(late) / len(late) * 1e3:.3f} over {len(late)} sends")
    for r in failed[:5]:
        say(f"failed request: finish={r['finish']} tokens={len(r['ids'])}/"
            f"{r['max_tokens']} error={r['error']}")

    # What the client saw must be what the server says it generated.
    served_client = sum(len(r["ids"]) for r in records)
    served_server = int(final.get("serve_tokens_generated_total", -1))
    unexpected = int(final.get("xla_unexpected_compiles_total", 0))
    server_failed = int(final.get("serve_requests_failed_total", 0))
    say(f"tokens client {served_client} server {served_server}")
    numbers = [
        checker.number("tokens_client_server_gap",
                       abs(served_client - served_server), 0),
        checker.number("compiles_in_window", unexpected, 0),
        checker.number("server_failures", server_failed, 0),
        checker.number("requests_failed", len(failed), 0),
        checker.number("server_exit_code", abs(rc), 0)]

    verdict = run_checker(root, logs, {
        "kind": "serve", "config": cell.config, "seed": model_seed(args.seed),
        "chips": cell.chips, "control": bool(args.control),
        "limits": cell.config.get("limits", {}).get("serve", {}),
        "sequences": sample_for_check(records, args.seed,
                                      int(mix["check_requests"]))})
    numbers += verdict["numbers"]
    correct = verdict["correct"] and all(n["ok"] for n in numbers)

    trace = None
    if args.trace and state.get("profile", {}).get("path"):
        trace = reduce_trace(root, logs, state["profile"]["path"])
    peak = max((d.get("peak_bytes_in_use", 0) for d in mem["devices"]),
               default=0)
    device = finish_device(device, peak, trace)
    ctx = {"cell": cell.name, "config": cell.config, "traffic": mix,
           "params": params, "records": measured, "all_records": records,
           "window": (t0, t1), "counters": prom.delta(state["m1"],
                                                      state["m0"]),
           "census": census, "parts": parts, "trace": trace,
           "trace_window": state.get("trace_window"),
           "peaks": spec.peaks_for(device["kind"]), "device": device,
           "decode_chunk": DECODE_CHUNK}
    if args.trace:
        if trace is None:
            say("no trace was reduced: the traced run has no result")
            return 1
        metrics = layer_metrics(cell, ctx)
        breakdown = trace["breakdown"]
    else:
        e2e = {"ttft_p50_ms": lambda: arith.ttft_ms(measured, 50),
               "tpot_p90_ms": lambda: arith.tpot_ms(measured, 90),
               "serve_tok_s": lambda: arith.serve_tok_s(records, t0, t1),
               "setup_s": lambda: setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]](), "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
        try:
            rate = arith.serve_rate(records, t0, t1)
        except ValueError:   # fewer than two first-token instants
            rate = dict.fromkeys(("tok_s", "instants",
                                  "largest_credit_share"))
        say("beside the metrics: " + json.dumps({
            "ttft_p95_ms": arith.ttft_ms(measured, 95),
            "ttft_p50_ms": arith.ttft_ms(measured, 50),
            "tpot_p50_ms": arith.tpot_ms(measured, 50),
            "tpot_p90_ms": arith.tpot_ms(measured, 90),
            "serve_tok_s": arith.serve_tok_s(records, t0, t1),
            "serve_tok_s_between_first_tokens": rate["tok_s"],
            "first_token_instants": rate["instants"],
            "largest_credit_share": rate["largest_credit_share"],
            "window": [t0, t1],
            "requests_measured": len(measured)}))
    with open(os.path.join(logs, "records.json"), "w") as f:
        json.dump([{k: v for k, v in r.items()
                    if k not in ("prompt_ids", "ids")} for r in records], f)
    result_line(correct, len(measured), len(failed), metrics, device,
                numbers, breakdown)
    return 0


def sweep(args, base, mix, vocab, scrape, state) -> int:
    """One server, one set-up: the mix at each rate in turn. The knee is
    the highest rate whose backlog does not grow: queue wait flat over the
    step and every request answered in time."""
    for i, rate in enumerate(float(x) for x in args.sweep.split(",")):
        # A rate may be repeated: each step gets a seed of its own, so the
        # same call also shows how far windows at one rate scatter.
        sched = traffic_mod.open_loop_schedule(
            {**mix, "ramp_seconds": 4.0}, args.seconds, vocab,
            args.seed + i, rate=rate)
        hooks = [(4.0, lambda s: scrape(s, "m0")),
                 (4.0 + args.seconds, lambda s: scrape(s, "m1"))]
        t_load = time.monotonic() + 0.2
        records = asyncio.run(client.open_loop(base, sched, t_load, hooks))
        measured = [r for r in records if r["measured"]]
        half = len(measured) // 2
        early = [r for r in measured[:half] if r["token_times"]]
        lateh = [r for r in measured[half:] if r["token_times"]]
        d = prom.delta(state["m1"], state["m0"])
        row = {"rate": rate, "n": len(measured),
               "failed": sum(not r["ok"] for r in measured),
               "ttft_p50_ms": arith.ttft_ms(measured, 50),
               "ttft_p95_ms": arith.ttft_ms(measured, 95),
               "ttft_p50_first_half_ms": arith.ttft_ms(early, 50),
               "ttft_p50_second_half_ms": arith.ttft_ms(lateh, 50),
               "tpot_p90_ms": arith.tpot_ms(measured, 90),
               "queue_wait_ms": prom.mean_ms(d, "serve_queue_wait_seconds"),
               "drain_s": max(r["done_t"] for r in records)
               - (t_load + 4.0 + args.seconds)}
        say("sweep " + json.dumps(row))
    return 0


# --------------------------------------------------------------------------
# Training cells
# --------------------------------------------------------------------------

def copy_checkpoint(ckpt_root: str, step: int, dest: str, stop_evt) -> None:
    """The trainer keeps three checkpoints; copy step `step` as soon as it
    is whole (orbax renames the finished directory into place)."""
    src = os.path.join(ckpt_root, str(step))
    while not stop_evt.is_set():
        if os.path.isdir(src):
            try:
                shutil.copytree(src, os.path.join(dest, str(step)))
                return
            except (OSError, shutil.Error):
                shutil.rmtree(os.path.join(dest, str(step)),
                              ignore_errors=True)
        time.sleep(0.05)


def run_train(args, root, cell) -> int:
    mix, cfg = cell.traffic, cell.config
    content, logs = work_dirs(root, cell, args.seed, args.trace)
    vocab = cfg["as_run"]["vocab_size"]
    t_data0 = time.monotonic()
    tok_dir = tokenizer.write_tokenizer(os.path.join(content, "tokenizer"),
                                        vocab)
    docs = traffic_mod.train_documents(mix, vocab, args.seed)
    os.makedirs(os.path.join(content, "data"))
    with open(os.path.join(content, "data", "docs.jsonl"), "w") as f:
        for doc in docs:
            f.write(json.dumps({"text": tokenizer.text_of(doc)}) + "\n")
    job = dict(mix["job_params"])
    warm, n_check = int(mix["warm_steps"]), int(mix["check_steps"])
    params = {"model": cfg["model"], "model_overrides": cfg["model_overrides"],
              "seed": model_seed(args.seed), "tokenizer": tok_dir,
              **cfg["mesh_params"]["train"], **job}
    with open(os.path.join(content, "params.json"), "w") as f:
        json.dump(params, f, indent=1)
    env = {"RBT_CONTENT_DIR": content}
    trace_steps = int(mix["trace_steps"])
    if args.trace:
        env["RBT_PROFILE_AT_STEP"] = f"{warm + 2}:{trace_steps}"
    ckpt_root = os.path.join(content, "artifacts", "checkpoints")
    kept = os.path.join(content, "kept_checkpoints")
    os.makedirs(kept)
    stop_evt = threading.Event()
    copiers = [threading.Thread(target=copy_checkpoint, daemon=True,
                                args=(ckpt_root, s, kept, stop_evt))
               for s in (1, n_check)]
    t_spawn = time.monotonic()
    child = procs.Child(
        "trainer", TRAIN_ARGV,
        procs.child_env(root, env), root, logs)
    for th in copiers:
        th.start()
    try:
        got = child.wait_json(lambda o: o.get("startup") == "train", 600)
        if got is None:
            raise NoChip("benchmark: trainer gave no start-up line:\n"
                         + child.tail())
        t_startup, ident = got
        device = require_tpu(ident, cell.chips, child)
        # Set-up drives the one compiled step with its state from the seed
        # through its first steps; the window takes over the same object.
        got = child.wait_json(lambda o: o.get("step") == warm
                              and "loss" in o, RUN_DEADLINE_S - 300)
        if got is None:
            raise NoChip(f"benchmark: trainer never reached step {warm}:\n"
                         + child.tail())
        t0 = got[0]
        t1 = t0 + args.seconds
        time.sleep(max(0.0, t1 - time.monotonic()))
        # One more line may be in flight; steps that end after t1 are out.
        time.sleep(0.2)
    finally:
        stop_evt.set()
        rc = child.stop(120)
    for th in copiers:
        th.join(5)
    steps = [(t, o) for t, o in child.json_lines()
             if "step" in o and "loss" in o]
    ends = [t for t, _ in steps]
    first = next((o for _, o in steps if o["step"] == 1), {})
    parts = {"start_to_spawn_s": t_spawn - T_PROCESS,
             "tokenizer_and_data_s": t_spawn - t_data0,
             "spawn_to_startup_line_s": t_startup - t_spawn,
             "startup_line_to_first_step_s":
                 (steps[0][0] - t_startup) if steps else None,
             "first_step_compile_s": first.get("compile_time_s"),
             "warm_steps_s": (t0 - steps[0][0]) if steps else None}
    say("setup parts " + json.dumps(
        {k: (round(v, 3) if isinstance(v, float) else v)
         for k, v in parts.items()}))
    say(f"trainer exit code {rc} (42 = stopped by SIGTERM after its "
        "emergency checkpoint)")
    tokens_per_step = int(job["batch_size"]) * int(job["seq_len"])
    in_window = [o for t, o in steps if t0 < t <= t1]
    n_whole, span = arith.whole_steps(ends, t0, t1)
    nonfinite = [o for _, o in child.json_lines() if o.get("nonfinite")]
    say(f"whole steps in the window {n_whole} over {span:.3f} s | exit "
        f"code {rc}")
    import numpy as np
    all_rows = list(traffic_mod.pack_rows(docs, int(job["seq_len"])))
    rows = [{k: v.tolist() for k, v in row.items()}
            for row in all_rows[:n_check * int(job["batch_size"])]]
    verdict = run_checker(root, logs, {
        "kind": "train", "config": cfg, "seed": model_seed(args.seed),
        "chips": cell.chips, "control": bool(args.control),
        "limits": cfg.get("limits", {}).get("train", {}), "job": job,
        "rows": rows,
        "losses": [o["loss"] for _, o in steps[:n_check]],
        "checkpoints": kept, "steps": [1, n_check]}, timeout=900)
    numbers = [checker.number("nonfinite_steps", len(nonfinite), 0),
               checker.number("trainer_exit_code_off_42", abs(rc - 42), 0),
               checker.number("no_whole_step_in_window", n_whole == 0, 0),
               *verdict["numbers"]]
    correct = verdict["correct"] and all(n["ok"] for n in numbers)
    trace = None
    if args.trace:
        trace = reduce_trace(root, logs, os.path.join(
            content, "artifacts", "profiles", f"step{warm + 2}"))
    peak = max((o.get("hbm_used_bytes", 0) for _, o in steps), default=0)
    device = finish_device(device, peak, trace)
    pairs = [int(sum(n * (n + 1) // 2 for n in
                     np.bincount(r["segment_ids"])[1:])) for r in all_rows]
    ctx = {"cell": cell.name, "config": cfg, "traffic": mix,
           "attn_pairs_per_row": sum(pairs) / len(pairs),
           "params": params, "window": (t0, t1), "step_lines": in_window,
           "parts": parts, "trace": trace, "trace_steps": trace_steps,
           "peaks": spec.peaks_for(device["kind"]), "device": device}
    if args.trace:
        if trace is None:
            say("no trace was reduced: the traced run has no result")
            return 1
        metrics = layer_metrics(cell, ctx)
        breakdown = trace["breakdown"]
    else:
        e2e = {"train_tok_s": lambda: arith.train_tok_s(
                   ends, tokens_per_step, t0, t1),
               "setup_s": lambda: t0 - T_PROCESS}
        metrics = {m["name"]: {"value": e2e[m["name"]](), "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
        ck = [o.get("ckpt_s", 0.0) for o in in_window]
        say("beside the metrics: " + json.dumps({
            "steps_in_window": n_whole,
            "step_s_mean": sum(o.get("step_s", 0) for o in in_window)
            / max(len(in_window), 1),
            "ckpt_s_mean": sum(ck) / max(len(ck), 1),
            "data_wait_s_mean": sum(o.get("data_wait_s", 0)
                                    for o in in_window)
            / max(len(in_window), 1)}))
    result_line(correct, n_whole, len(nonfinite), metrics, device, numbers,
                breakdown)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates: find the knee of an "
                         "open-loop mix (no result line)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control's numbers "
                         "in the checker (the benchmark's own runs do not)")
    ap.add_argument("--bench-root", default=spec.ROOT,
                    help="directory holding BENCHMARK.json (tests use a "
                         "tiny copy); the program is taken from this "
                         "checkout either way")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, args.bench_root)
    watchdog = threading.Timer(RUN_DEADLINE_S, lambda: (
        print("bench: deadline passed", file=sys.stderr),
        procs.stop_all(), os._exit(1)))
    watchdog.daemon = True
    watchdog.start()
    try:
        if cell.traffic["kind"] == "train_job":
            return run_train(args, spec.ROOT, cell)
        return run_serve(args, spec.ROOT, cell)
    finally:
        procs.stop_all()
        watchdog.cancel()


if __name__ == "__main__":
    sys.exit(main())
