#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives both hot paths once through the entry points a user would call, at
the published widths of a model the repo lists (depth cut, seeded random
weights), and fails unless every phase ran on a TPU and came out right:

  agree        kernel agreement at width, outside any timing: flash vs XLA
               logits (training layout and the serve cached-prefill layout),
               ring vs off collective matmul on four chips, and the lowered
               prefill / train-step text containing ``tpu_custom_call``.
               Runs first: it is also the identity gate (no TPU -> exit).
  serve_dense  ``python -m runbooks_tpu.serve.api`` on a real port, warm-up
               on: concurrent + long + streamed /v1/completions, /metrics,
               /debug/memory, SIGTERM drain.
  serve_warm   the same server started a second time: the warm-up must be
               served from the persistent compilation cache.
  serve_paged  the same with ``kv_paging: paged``.
  train        ``python -m runbooks_tpu.train.trainer`` with LoRA, synthetic
               batches, one checkpoint save; then a second run that resumes
               from that checkpoint (proves it readable).

One process per chip: THIS PARENT IS STDLIB-ONLY AND NEVER IMPORTS JAX — a
parent that has touched JAX holds the chip and its children fail or hang.
Children run one at a time; every child is stopped before the next starts
and on every exit path. The four-chip run (``--chips 4``) is one process
driving four devices, not four processes.

What it prints besides pass/fail (warm-up program counts and seconds, peak
HBM, step times) are set-up facts for sizing later benchmarks — not metrics
and not claims; they go on a ``chip_smoke: summary {...}`` line and into
``chiprun_out/chip_smoke/summary_<n>chip.json``. The last stdout line,
printed only when every phase passed, is one JSON object of exactly this form:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--tiny`` is the CPU dry run of the same control flow at a toy size; the
"must be TPU" assertions are then the expected failures, and it exits 1.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")    # git-ignored, fixed
# Child logs and the summary: the directory the chip tool brings back.
LOGS = os.path.join(HERE, "chiprun_out", "chip_smoke")
DEADLINE_S = 1150          # the contract allows 1200 s, compilation included
PHASES = ("agree", "serve_dense", "serve_warm", "serve_paged", "train")
BF16 = {"param_dtype": "bfloat16"}

# Sizes. Widths are the published ones; only depth is cut (model_overrides
# num_layers) — one falcon-7b layer is 414 MB in bf16, all 32 do not fit
# beside anything on 16 GB. The dense server runs at its defaults (8 slots,
# context 2048: 19 warm-up programs, prefill [8, 2048] peaks at 14.75 of
# 15.75 GiB). The paged server's default warm-up is 145 programs at this
# context (ROADMAP S3), so it is sized down with parameters that exist. The
# trainer takes batch 2: batch 8 exceeds HBM by 2.58 GB (f32 [8, 2048,
# 65024] logits twice), batch 4 fits with 1.4 GiB to spare.
_TRAFFIC = dict(prompt_lens=(12, 40, 150, 300), max_tokens=16,
                paged_prompt_lens=(12, 40, 150, 200), agree_seq=2048)
_PAGED = {"max_slots": 8, "max_seq_len": 256, "page_size": 128}
_TP4 = {"mesh_tensor": 4, "collective_matmul": "auto"}
FULL = {
    1: dict(model="falcon-7b", layers=16, dense={"max_slots": 8},
            paged=_PAGED, train={"batch_size": 2, "seq_len": 2048},
            **_TRAFFIC),
    # 71 query heads on 1 kv head do not divide by 4 and the paged engine
    # rejects it, so four chips run falcon-40b (128 q / 8 kv heads), one
    # layer of which is 1.36 GB.
    4: dict(model="falcon-40b", layers=6,
            dense={"max_slots": 8, **_TP4}, paged={**_PAGED, **_TP4},
            train={"batch_size": 4, "seq_len": 2048, "mesh_fsdp": 2,
                   "mesh_tensor": 2}, **_TRAFFIC),
}
_TINY_OVERRIDES = {"vocab_size": 512, "hidden_size": 64,
                   "intermediate_size": 128, "num_heads": 8,
                   "num_kv_heads": 4, "head_dim": 8, "max_seq_len": 128,
                   "flash_block_q": 32, "flash_block_k": 32}


def sizes(tiny: bool, chips: int) -> dict:
    if not tiny:
        return FULL[chips]
    mesh_s = _TP4 if chips == 4 else {}
    mesh_t = {"mesh_fsdp": 2, "mesh_tensor": 2} if chips == 4 else {}
    return dict(model="debug", layers=2, overrides=_TINY_OVERRIDES,
                dense={"max_slots": 4, **mesh_s},
                paged={"max_slots": 4, "max_seq_len": 128, "page_size": 32,
                       **mesh_s},
                train={"batch_size": 4, "seq_len": 64, **mesh_t},
                prompt_lens=(5, 12, 40, 70),
                paged_prompt_lens=(5, 12, 40, 70), max_tokens=6,
                agree_seq=64)


def model_overrides(sz: dict) -> dict:
    return {"num_layers": sz["layers"], **BF16, **sz.get("overrides", {})}


class Failed(Exception):
    """A phase's check did not hold."""


_DEFERRED: list = []


def check(cond, message: str, tpu_only: bool = False) -> None:
    """Fail the phase. A ``tpu_only`` assertion (one a CPU dry run cannot
    meet) is recorded and raised at the end of the phase instead, so the
    dry run still exercises everything after it."""
    if cond:
        return
    if not tpu_only:
        raise Failed(message)
    _DEFERRED.append(message)


# ---------------------------------------------------------------------------
# Parent side: children, HTTP, phases
# ---------------------------------------------------------------------------

_CHILDREN: list = []


def spawn(name: str, argv: list, env: dict) -> subprocess.Popen:
    """Start one child in its own process group, output to LOGS/<name>.log."""
    log = open(os.path.join(LOGS, f"{name}.log"), "w")
    proc = subprocess.Popen(argv, env=env, cwd=HERE, stdout=log,
                            stderr=subprocess.STDOUT, start_new_session=True)
    proc.log_path = log.name
    log.close()
    _CHILDREN.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace_s: float = 60.0) -> int:
    """SIGTERM, wait, SIGKILL the whole group if it lingers."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(grace_s)
        except subprocess.TimeoutExpired:
            pass
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(30)
    return proc.returncode


def stop_all() -> None:
    for proc in _CHILDREN:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(30)


def log_tail(path: str, lines: int = 30) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-lines:])


def json_lines(path: str) -> list:
    out = []
    with open(path, errors="replace") as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return out


# Loopback only: never through a proxy the environment may name.
_OPENER = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def http(method: str, url: str, body=None, timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with _OPENER.open(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def metric(text: str, name: str) -> float:
    """Sum of a metric family's samples in a Prometheus exposition."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    check(seen, f"/metrics has no {name}")
    return total


def child_env(chips: int, tiny: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if tiny:  # the CPU dry run needs its virtual devices
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{chips}")
    return env


def content_dir(name: str, params: dict) -> str:
    """A fixed RBT_CONTENT_DIR inside the checkout holding params.json."""
    path = os.path.join(WORK, name)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "params.json"), "w") as f:
        json.dump(params, f, indent=1)
    return path


def check_identity(ident: dict, chips: int, hw) -> None:
    """The run was on a TPU that utils/hw.py knows (its own lookup decides),
    driving the expected number of devices."""
    check(ident.get("platform") == "tpu" and ident.get("backend") == "tpu",
          f"ran on {ident.get('platform')!r}, not a TPU", tpu_only=True)
    try:
        known = hw.chip_peaks(argparse.Namespace(
            platform="tpu", device_kind=str(ident.get("device_kind"))))
    except ValueError:
        known = None
    check(known is not None,
          f"device_kind {ident.get('device_kind')!r} is not in "
          "runbooks_tpu/utils/hw.py CHIP_PEAKS", tpu_only=True)
    check(ident.get("device_count") == chips,
          f"{ident.get('device_count')} devices, expected {chips}")


def serve_phase(name: str, sz: dict, chips: int, tiny: bool, port: int,
                paged: bool, traffic: bool, hw, facts: dict) -> None:
    params = {"model": sz["model"], "model_overrides": model_overrides(sz),
              "seed": 0, "port": port, "warmup": True,
              **(sz["paged"] if paged else sz["dense"])}
    if paged:
        params["kv_paging"] = "paged"
    env = child_env(chips, tiny)
    env["RBT_CONTENT_DIR"] = content_dir(name, params)
    t0 = time.monotonic()
    proc = spawn(name, [sys.executable, "-m", "runbooks_tpu.serve.api"], env)
    base = f"http://127.0.0.1:{port}"
    try:
        while True:  # readiness flips after warm-up
            check(proc.poll() is None,
                  f"server exited rc={proc.returncode} before ready:\n"
                  + log_tail(proc.log_path))
            try:
                if http("GET", base + "/", timeout=5)[0] == 200:
                    break
            except (urllib.error.URLError, OSError):
                pass
            check(time.monotonic() - t0 < 700, "server not ready in 700 s")
            time.sleep(1.0)
        ready_s = time.monotonic() - t0
        start = next((j for j in json_lines(proc.log_path)
                      if j.get("startup") == "serve"), None)
        check(start is not None, "no serve start-up line in the log")
        print(f"[{name}] startup {json.dumps(start)}", flush=True)
        census = json.loads(http("GET", base + "/debug/programs")[1])[
            "warmup_census"]
        fact = {"layers": sz["layers"], "ready_s": round(ready_s, 1),
                "warmup_programs": census["compiles"],
                "warmup_compile_s": census["compile_seconds"],
                "warmup_s": census["warmup_seconds"],
                "cache_hits": census["cache_hits"],
                "compile_cache_dir": start["compile_cache_dir"],
                "prefill_programs": census["prefill_programs"],
                "decode_views": census["decode_views"]}
        facts[name] = fact
        print(f"[{name}] warm-up {json.dumps(fact)}", flush=True)
        if traffic:
            drive_traffic(base, sz, paged)
        else:
            status, raw = http("POST", base + "/v1/completions", {
                "prompt": "warm start", "max_tokens": sz["max_tokens"],
                "temperature": 0.0})
            check(status == 200, f"HTTP {status}: {raw[:300]!r}")
        text = http("GET", base + "/metrics")[1].decode()
        check(metric(text, "serve_requests_failed_total") == 0,
              "requests_failed_total != 0")
        check(metric(text, "xla_unexpected_compiles_total") == 0,
              "xla_unexpected_compiles_total != 0 after warm-up")
        if traffic:
            want = (len(sz["prompt_lens"]) + 3) * sz["max_tokens"]
            got = metric(text, "serve_tokens_generated_total")
            check(got == want, f"{got} completion tokens, requested {want}")
        mem = json.loads(http("GET", base + "/debug/memory")[1])
        devs = mem["devices"]
        used = [d.get("bytes_in_use", 0) for d in devs]
        fact["peak_hbm_bytes"] = [d.get("peak_bytes_in_use") for d in devs]
        print(f"[{name}] memory platform={devs[0]['platform']} "
              f"in_use={used} peak={fact['peak_hbm_bytes']} live="
              f"{mem['live_arrays']['by_category']}", flush=True)
        check(devs[0]["platform"] == "tpu" and min(used) > 0,
              f"/debug/memory: platform {devs[0]['platform']!r}, "
              f"bytes_in_use {used}", tpu_only=True)
        if chips > 1:
            # Weights and the KV pool spread over the mesh, not on device 0
            # (single_device_mesh / an engine built with mesh=None), and no
            # second, unsharded copy of the weights left alive beside them.
            live = mem["live_arrays"]["by_category"]
            check(live["other"] < 0.5 * live["weights"],
                  f"live arrays outside weights/KV: {live}")
            check(len(used) == chips and max(used) < 1.5 * min(used),
                  f"HBM not spread over {chips} devices: {used}",
                  tpu_only=True)
            pool = metric(text, "serve_kv_pool_bytes")
            per = metric(text, "serve_kv_pool_bytes_per_device")
            check(per * chips == pool,
                  f"KV pool not sharded: {per} per device of {pool}")
        check_identity(start, chips, hw)
        if name == "serve_warm":
            check(census["cache_hits"] > 0,
                  "second start took nothing from the compilation cache")
    finally:
        rc = stop(proc)
    check(rc == 0, f"server exit code {rc} after SIGTERM:\n"
          + log_tail(proc.log_path, 15))
    check("draining" in log_tail(proc.log_path, 200),
          "no graceful drain in the server log")


def drive_traffic(base: str, sz: dict, paged: bool) -> None:
    """Concurrent mixed-length wave (batched decode; >= 128-token prompts
    take the cached-prefill flash path), one streamed, one repeat."""
    lens = sz["paged_prompt_lens" if paged else "prompt_lens"]
    n_tok = sz["max_tokens"]
    text = ("The quick brown fox jumps over the lazy dog. " * 16)
    results: dict = {}

    def one(i, n):
        results[i] = http("POST", base + "/v1/completions", {
            "prompt": text[:n], "max_tokens": n_tok, "temperature": 0.0})

    threads = [threading.Thread(target=one, args=(i, n))
               for i, n in enumerate(lens)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    # The longest prompt again: on the paged engine a radix hit, so the
    # shared-prefix prefill programs run too.
    one("repeat", lens[-1])
    one("short", 3)
    for key, (status, raw) in sorted(results.items(), key=str):
        check(status == 200, f"request {key}: HTTP {status}: {raw[:300]!r}")
        body = json.loads(raw)
        got = body["usage"]["completion_tokens"]
        check(got == n_tok and body["choices"][0]["finish_reason"]
              == "length", f"request {key}: {got} tokens "
              f"({body['choices'][0]['finish_reason']}), requested {n_tok}")
    check(len(results) == len(lens) + 2, "a request thread never finished")
    status, raw = http("POST", base + "/v1/completions", {
        "prompt": text[:60], "max_tokens": n_tok, "temperature": 0.0,
        "stream": True})
    events = [ln[len("data: "):] for ln in raw.decode().splitlines()
              if ln.startswith("data: ")]
    check(status == 200 and events and events[-1] == "[DONE]",
          f"stream: HTTP {status}, {len(events)} events")
    chunks = [json.loads(e) for e in events[:-1]]
    check(not any("error" in c for c in chunks), f"stream error: {chunks}")
    check(chunks[-1]["choices"][0]["finish_reason"] == "length",
          f"stream finished {chunks[-1]['choices'][0]['finish_reason']!r}")
    print(f"[serve] {len(results)} completions + 1 stream ok "
          f"(prompt lengths {list(lens)}, {n_tok} tokens each)", flush=True)


def train_phase(sz: dict, chips: int, tiny: bool, hw,
                facts: dict) -> None:
    steps = 6
    params = {"model": sz["model"], "model_overrides": model_overrides(sz),
              "lora": {"rank": 8}, "steps": steps, "log_every": 1,
              "checkpoint_every": steps, "warmup_steps": 2,
              "total_steps": 100, "seed": 0, "maintenance_poll_s": 0,
              **sz["train"]}
    env = child_env(chips, tiny)
    runs = []
    for name, n_steps in (("train", steps), ("train_resume", steps + 1)):
        env["RBT_CONTENT_DIR"] = content_dir("train",
                                             {**params, "steps": n_steps})
        proc = spawn(name, [sys.executable, "-m",
                            "runbooks_tpu.train.trainer"], env)
        try:
            rc = proc.wait(600)
        finally:
            stop(proc)
        check(rc == 0, f"{name} exit code {rc}:\n" + log_tail(proc.log_path))
        lines = json_lines(proc.log_path)
        start = next((j for j in lines if j.get("startup") == "train"), None)
        done = next((j for j in lines if j.get("done")), None)
        check(start is not None and done is not None,
              f"{name}: no start-up or summary line")
        print(f"[{name}] startup {json.dumps(start)}", flush=True)
        runs.append((start, done, [j for j in lines if "loss" in j
                                   and "step" in j]))
    (start, done, hist), (_, redone, rehist) = runs
    check(len(hist) == steps, f"{len(hist)} logged steps, expected {steps}")
    check(all(j["loss"] == j["loss"] and abs(j["loss"]) < 1e9 for j in hist),
          f"non-finite loss: {[j['loss'] for j in hist]}")
    check(done["nonfinite_steps"] == 0, "non-finite steps")
    first_s, second_s = done["compile_time_s"], hist[1]["step_s"]
    check(second_s < first_s,
          f"second step {second_s}s not faster than the first {first_s}s")
    check(done["device_obs"]["unexpected_compiles"] == 0,
          "unexpected compiles in the step loop")
    # The resumed run restored the checkpoint the first one wrote.
    check(redone["restore_time_s"] is not None and len(rehist) == 1
          and rehist[0]["step"] == steps + 1,
          f"resume did not continue from step {steps}: {rehist}")
    fact = {"layers": sz["layers"], "losses": [j["loss"] for j in hist],
            "first_step_s": first_s, "step_s": second_s,
            "resume_restore_s": redone["restore_time_s"],
            "resume_first_step_s": redone["compile_time_s"],
            "peak_hbm_bytes": done["device_obs"]["hbm_peak_bytes"],
            "hbm_per_device": done["device_obs"].get(
                "hbm_bytes_in_use_per_device"),
            "mesh": start["mesh"], "attention_impl": start["attention_impl"]}
    facts["train"] = fact
    print(f"[train] {json.dumps(fact)}", flush=True)
    check(start["attention_impl"] == "flash",
          f"attention_impl resolved to {start['attention_impl']!r}",
          tpu_only=True)
    if chips > 1:
        used = fact["hbm_per_device"] or []
        check(len(used) == chips and max(used) < 1.5 * min(used),
              f"trainer HBM not spread over {chips} devices: {used}",
              tpu_only=True)
    check_identity(done, chips, hw)


def agree_phase(sz: dict, chips: int, tiny: bool, hw,
                facts: dict) -> None:
    proc = spawn("agree", [sys.executable, os.path.abspath(__file__),
                           "--child", "agree", "--chips", str(chips)]
                 + (["--tiny"] if tiny else []), child_env(chips, tiny))
    try:
        rc = proc.wait(900)
    finally:
        stop(proc)
    lines = json_lines(proc.log_path)
    ident = next((j for j in lines if "device_kind" in j), None)
    check(ident is not None, "agree child printed no identity:\n"
          + log_tail(proc.log_path))
    print(f"[agree] identity {json.dumps(ident)}", flush=True)
    facts["device"] = {"platform": ident.get("platform"),
                       "kind": ident.get("device_kind"),
                       "count": ident.get("device_count")}
    check_identity(ident, chips, hw)
    if _DEFERRED and not tiny:
        # No accelerator: nothing else is worth starting at full width.
        raise SystemExit(f"chip_smoke: {'; '.join(_DEFERRED)}")
    check(rc == 0, f"agree child exit code {rc}:\n"
          + log_tail(proc.log_path))
    result = next((j for j in lines if "agree" in j), None)
    check(result is not None, "agree child printed no result")
    facts["agree"] = result["agree"]
    print(f"[agree] {json.dumps(result['agree'])}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="devices the one process drives (4: falcon-40b "
                         "widths, mesh_tensor 4 server, fsdp2 x tensor2 "
                         "trainer)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run of the control flow at a toy size; "
                         "the must-be-TPU assertions fail as expected")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {','.join(PHASES)}; "
                         "a skipped phase fails the smoke")
    ap.add_argument("--child", choices=("agree",), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return agree_child(sizes(args.tiny, args.chips), args.chips,
                           args.tiny)

    # The peak table, read without importing the package (or JAX): it is
    # what "a known TPU" means. Absent file = not a checkout = fail.
    spec = importlib.util.spec_from_file_location(
        "_hw", os.path.join(HERE, "runbooks_tpu", "utils", "hw.py"))
    hw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hw)

    wanted = [p for p in args.phases.split(",") if p]
    unknown = set(wanted) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    sz = sizes(args.tiny, args.chips)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(LOGS, exist_ok=True)
    print(f"chip_smoke: model {sz['model']} at published widths, "
          f"{sz['layers']} layers, {args.chips} chip(s), phases {wanted}"
          + (" [TINY CPU DRY RUN]" if args.tiny else ""), flush=True)

    t_start = time.monotonic()
    watchdog = threading.Timer(DEADLINE_S, lambda: (
        print(f"chip_smoke: deadline {DEADLINE_S}s passed", file=sys.stderr),
        stop_all(), os._exit(1)))
    watchdog.daemon = True
    watchdog.start()
    facts: dict = {}
    runners = {
        "agree": lambda: agree_phase(sz, args.chips, args.tiny, hw, facts),
        "serve_dense": lambda: serve_phase(
            "serve_dense", sz, args.chips, args.tiny, 18080, False, True,
            hw, facts),
        "serve_warm": lambda: serve_phase(
            "serve_warm", sz, args.chips, args.tiny, 18080, False, False,
            hw, facts),
        "serve_paged": lambda: serve_phase(
            "serve_paged", sz, args.chips, args.tiny, 18081, True, True,
            hw, facts),
        "train": lambda: train_phase(sz, args.chips, args.tiny, hw, facts),
    }
    failures = [f"{p}: skipped" for p in PHASES if p not in wanted]
    try:
        for phase in PHASES:
            if phase not in wanted:
                continue
            t0 = time.monotonic()
            del _DEFERRED[:]
            try:
                runners[phase]()
                check(not _DEFERRED, "; ".join(_DEFERRED))
                print(f"[{phase}] PASS in {time.monotonic() - t0:.0f}s",
                      flush=True)
            except Failed as exc:
                failures.append(f"{phase}: {exc}")
                print(f"[{phase}] FAIL in {time.monotonic() - t0:.0f}s: "
                      f"{exc}", flush=True)
    finally:
        stop_all()
        watchdog.cancel()
    result = {"ok": not failures, "device": facts.pop("device", None)}
    summary = {**result, "chips": args.chips, "model": sz["model"],
               "layers": sz["layers"],
               "wall_s": round(time.monotonic() - t_start, 1),
               "setup_facts": facts}
    with open(os.path.join(LOGS, f"summary_{args.chips}chip.json"), "w") as f:
        json.dump({**summary, "failures": failures}, f, indent=1)
    if failures:
        print("chip_smoke: FAILED\n  " + "\n  ".join(failures),
              file=sys.stderr, flush=True)
        return 1
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)
    # The result line: exactly these two keys, last on stdout, only on a pass.
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Child side (imports JAX): kernel agreement at width
# ---------------------------------------------------------------------------

def agree_child(sz: dict, chips: int, tiny: bool) -> int:
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import (
        KVCache,
        forward,
        init_params,
        param_logical_axes,
    )
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
    from runbooks_tpu.parallel.sharding import tree_shardings
    from runbooks_tpu.utils.hw import device_identity
    from runbooks_tpu.utils.jax_cache import enable_compilation_cache

    ident = device_identity()
    print(json.dumps(ident), flush=True)
    if ident["platform"] != "tpu" and not tiny:
        return 1
    enable_compilation_cache()
    cfg = get_config(sz["model"], **model_overrides(sz))
    seq = sz["agree_seq"]
    mesh = make_mesh(MeshConfig(fsdp=1, tensor=chips) if chips > 1
                     else MeshConfig(fsdp=1), devices=jax.devices()[:chips])
    out: dict = {}

    def rel_l2(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.isfinite(b).all()
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    def logits(c, params, tokens, **kw):
        with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: forward(c, p, t, **kw)[0])(
                params, tokens)

    def sharded_params(c):
        shapes = jax.eval_shape(functools.partial(init_params, c),
                                jax.random.key(0))
        with jax.set_mesh(mesh):
            return jax.jit(
                functools.partial(init_params, c),
                out_shardings=tree_shardings(
                    shapes, param_logical_axes(c), mesh))(jax.random.key(0))

    tokens = jax.random.randint(jax.random.key(1), (1, seq), 0,
                                cfg.vocab_size)
    variant = lambda c, **kw: dataclasses.replace(c, **kw)  # noqa: E731

    # (1) The smoke's own config (bf16): each layer's attention output is
    # rounded to bf16 (eps 2^-8) on both paths from differently ordered
    # f32 sums, so the logits drift by about eps * sqrt(layers); allow
    # twice that. A tolerance this loose cannot see one wrong head in 71,
    # which is what (2) is for.
    params = sharded_params(cfg)
    tol_bf16 = 2 * 2.0 ** -8 * cfg.num_layers ** 0.5
    ref = logits(variant(cfg, attention_impl="xla"), params, tokens)
    got = logits(variant(cfg, attention_impl="flash"), params, tokens)
    assert got.shape == (1, seq, cfg.vocab_size), got.shape
    out["flash_vs_xla_bf16"] = {"rel_l2": rel_l2(got, ref), "tol": tol_bf16,
                                "layers": cfg.num_layers}
    if chips > 1:
        # Ring vs GSPMD tensor parallelism: same arithmetic, partial sums
        # in another order — the same bf16 budget.
        ring = logits(variant(cfg, attention_impl="xla",
                              collective_matmul="ring"), params, tokens)
        out["ring_vs_off_bf16"] = {"rel_l2": rel_l2(ring, ref),
                                   "tol": tol_bf16}
    del params, ref, got

    # (2) Same widths in float32 at depth 2, where flash and XLA must agree
    # to float32 round-off of the softmax sums (1e-3 leaves room for the
    # TPU's multi-pass f32 matmul): the training layout and the serve
    # layout (queries at an offset into a longer, unaligned KV view); the
    # forward works out from the positions which kv blocks to visit.
    c32 = variant(cfg, num_layers=2, dtype="float32", param_dtype="float32")
    p32 = sharded_params(c32)
    ref = logits(variant(c32, attention_impl="xla"), p32, tokens)
    got = logits(variant(c32, attention_impl="flash"), p32, tokens)
    out["flash_vs_xla_f32"] = {"rel_l2": rel_l2(got, ref), "tol": 1e-3}
    if chips > 1:
        ring = logits(variant(c32, attention_impl="xla",
                              collective_matmul="ring"), p32, tokens)
        out["ring_vs_off_f32"] = {"rel_l2": rel_l2(ring, ref), "tol": 1e-3}
    q_len = min(128, seq // 2)
    cache = KVCache.create(c32, 2, 2 * q_len, trash_slot=True,
                           quantize_kv=False)
    pos = jnp.broadcast_to(jnp.arange(q_len, dtype=jnp.int32), (2, q_len))
    ref = logits(variant(c32, attention_impl="xla"), p32,
                 tokens[:, :2 * q_len].reshape(2, q_len), positions=pos,
                 cache=cache)
    got = logits(variant(c32, attention_impl="flash"), p32,
                 tokens[:, :2 * q_len].reshape(2, q_len), positions=pos,
                 cache=cache)
    out["cached_prefill_flash_vs_xla_f32"] = {"rel_l2": rel_l2(got, ref),
                                              "tol": 1e-3}
    del p32, ref, got

    # (3) What the entry points compile really is the Mosaic kernel: the
    # lowered serve prefill and LoRA train step carry tpu_custom_call.
    from runbooks_tpu.serve.engine import make_prefill_fn
    from runbooks_tpu.train.lora import (
        LoraConfig,
        create_lora_train_state,
        make_lora_train_step,
    )
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer

    key = jax.random.key(0)
    base = jax.eval_shape(functools.partial(init_params, cfg), key)
    rows, bucket, cache_len = 2, q_len, 2 * q_len + 1
    pool = jax.eval_shape(lambda: KVCache.create(
        cfg, rows, cache_len - 1, trash_slot=True, quantize_kv=False))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    with jax.set_mesh(mesh):
        prefill_txt = jax.jit(make_prefill_fn(cfg, cache_len)).lower(
            base, pool, i32(rows, bucket), i32(rows, bucket), i32(rows),
            i32(rows), key, f32(rows), i32(rows), f32(rows)).as_text()
        opt = make_optimizer(OptimizerConfig())
        lcfg = LoraConfig()
        state, shardings = create_lora_train_state(cfg, lcfg, base, opt,
                                                   mesh, key)
        base_sh = tree_shardings(base, param_logical_axes(cfg), mesh)
        step_txt = make_lora_train_step(
            cfg, lcfg, opt, mesh, shardings, base_sh).lower(
            state, base, {"tokens": i32(chips, seq),
                          "targets": i32(chips, seq),
                          "loss_mask": f32(chips, seq)}).as_text()
    out["tpu_custom_call"] = {"prefill": "tpu_custom_call" in prefill_txt,
                              "train_step": "tpu_custom_call" in step_txt}

    # (3b) The gated delta rule's prefill kernel (ops/gated_delta.py) at
    # olmo-hybrid-7b's widths, in float32 where it must agree with the
    # recurrence to round-off. One chip's check: the kernel is the same
    # on every shard of a mesh.
    if chips == 1:
        out.update(gated_delta_agreement(tiny, rel_l2))

    # (4) block_until_ready really waits here (the benches sync by pulling
    # a scalar; both must see the same wall time for the same work).
    n = 512 if tiny else 8192
    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, 16, lambda _, y: (y @ x) * (1.0 / n), x)

    float(chain(x)[0, 0])  # compile both the chain and the scalar slice
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    float(chain(x)[0, 0])
    t3 = time.perf_counter()
    out["block_until_ready"] = {"dispatch_s": round(t1 - t0, 5),
                                "block_s": round(t2 - t0, 5),
                                "pull_scalar_s": round(t3 - t2, 5)}
    out["peak_hbm_bytes"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:chips]]
    print(json.dumps({"agree": out}), flush=True)
    bad = [k for k, v in out.items() if isinstance(v, dict) and "tol" in v
           and not v["rel_l2"] <= v["tol"]]
    bad += [f"no tpu_custom_call in lowered {k}"
            for k, v in out["tpu_custom_call"].items() if not v]
    sync = out["block_until_ready"]
    if not tiny and sync["block_s"] < 0.5 * sync["pull_scalar_s"]:
        bad.append(f"block_until_ready returned early: {sync}")
    if bad:
        print(f"agree: FAILED {bad}", flush=True)
        return 1
    return 0


def gated_delta_agreement(tiny: bool, rel_l2) -> dict:
    """{check: {"rel_l2", "tol"}}: the chunked kernel against the
    token-by-token recurrence (30 heads of 96 x 192, 2048 tokens, an
    initial state, a ragged mask), and olmo-hybrid-7b at one period of
    its pattern, prefill then decode through the cache against the whole
    forward without one."""
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import KVCache, forward, init_params
    from runbooks_tpu.ops.gated_delta import (
        gated_delta_chunked,
        gated_delta_reference,
        l2_normalize,
    )

    heads, dk, dv, s = (3, 24, 40, 130) if tiny else (30, 96, 192, 2048)
    ks = jax.random.split(jax.random.key(5), 6)
    q = l2_normalize(jax.nn.silu(
        jax.random.normal(ks[0], (2, s, heads, dk)) + 0.5)) * dk ** -0.5
    k = l2_normalize(jax.nn.silu(
        jax.random.normal(ks[1], (2, s, heads, dk)) + 0.5))
    v = jax.random.normal(ks[2], (2, s, heads, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (2, s, heads), minval=-6.0,
                                    maxval=0.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (2, s, heads)))
    state = jax.random.normal(ks[5], (2, heads, dk, dv))
    mask = jnp.arange(s)[None, :] < jnp.array([s, (5 * s) // 8 + 3])[:, None]
    with jax.default_matmul_precision("highest"):
        got_o, got_s = jax.jit(gated_delta_chunked)(q, k, v, g, beta, state,
                                                    mask)
        want_o, want_s = jax.jit(gated_delta_reference)(q, k, v, g, beta,
                                                        state, mask)
    seen = mask[..., None, None]
    out = {"gated_delta_kernel_vs_recurrence_f32": {
        "rel_l2": max(rel_l2(jnp.where(seen, got_o, 0.0),
                             jnp.where(seen, want_o, 0.0)),
                      rel_l2(got_s, want_s)), "tol": 1e-4}}

    cfg = get_config("debug-hybrid" if tiny else "olmo-hybrid-7b",
                     num_layers=4, dtype="float32", param_dtype="float32")
    prompt, steps = (40, 3) if tiny else (1000, 4)
    params = jax.jit(lambda key: init_params(cfg, key))(jax.random.key(6))
    toks = jax.random.randint(jax.random.key(7), (1, prompt + steps), 1,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p, t: forward(cfg, p, t)[0])(params, toks)
        step = jax.jit(lambda p, t, c: forward(cfg, p, t, cache=c))
        cache = KVCache.create(cfg, 1, 2 * prompt)
        parts = []
        for lo, hi in ((0, prompt),) + tuple(
                (i, i + 1) for i in range(prompt, prompt + steps)):
            logits, cache = step(params, toks[:, lo:hi], cache)
            parts.append(logits)
    out["gated_delta_prefill_then_decode_f32"] = {
        "rel_l2": rel_l2(jnp.concatenate(parts, 1)[:, prompt - 1:],
                         whole[:, prompt - 1:]), "tol": 1e-3}
    return out


if __name__ == "__main__":
    try:
        sys.exit(main())
    finally:
        stop_all()
