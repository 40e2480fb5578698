"""Fleet-scrape smoke: the controller scrape loop against live replica
endpoints, end to end, with one JSON line for the sweep table.

Spins N fake Server replicas (real HTTP /metrics endpoints rendering
real registries with latency histograms), registers them as Running
pods in the in-memory cluster, runs `FleetScraper.scrape_once`, and
verifies the controller-side exposition carries every replica's series
plus the freshness gauges. The printed value is the sweep wall time, so
a scrape sweep that starts taking seconds (it must stay tens of ms at this
scale) is visible.

Run: ``python tools/fleet_smoke.py [replicas]``
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")  # repo-root invocation, like bench.py


def main() -> int:
    replicas = int(sys.argv[1]) if len(sys.argv) > 1 else 4

    from runbooks_tpu.api.types import Server
    from runbooks_tpu.controller.fleet import FleetScraper, FleetState
    from runbooks_tpu.controller.manager import Ctx
    from runbooks_tpu.k8s.fake import FakeCluster
    from runbooks_tpu.obs.metrics import Registry, serve_metrics

    cluster = FakeCluster()
    cluster.create(Server.new("smoke", spec={"image": "x"}).obj)
    servers = []
    for i in range(replicas):
        reg = Registry()
        reg.set_counter("serve_requests_total", 100 + i)
        reg.set_counter("serve_tokens_generated_total", 1000 * (i + 1))
        reg.set_gauge("serve_active_slots", i % 4)
        for v in (0.02, 0.05, 0.1, 0.4):
            reg.observe("serve_ttft_seconds", v)
            reg.observe("serve_queue_wait_seconds", v / 10)
        httpd = serve_metrics(0, reg)
        servers.append(httpd)
        cluster.create({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {
                "name": f"smoke-{i}", "namespace": "default",
                "labels": {"server": "smoke", "role": "run"},
                "annotations": {"runbooks-tpu.dev/metrics-port":
                                str(httpd.server_address[1])},
            },
            "spec": {"containers": [{"name": "serve"}]},
            "status": {"phase": "Running", "podIP": "127.0.0.1"},
        })

    registry, fleet_state = Registry(), FleetState()
    scraper = FleetScraper(Ctx(client=cluster, cloud=None, sci=None),
                           state=fleet_state, registry=registry)
    t0 = time.perf_counter()
    ok = scraper.scrape_once()
    sweep_ms = (time.perf_counter() - t0) * 1000.0
    text = registry.render()
    errors = []
    if ok != replicas:
        errors.append(f"scraped {ok}/{replicas} replicas")
    for i in range(replicas):
        if f'replica="smoke-{i}"' not in text:
            errors.append(f"replica smoke-{i} missing from exposition")
    summary = fleet_state.server_summary("default", "smoke") or {}
    if summary.get("replicasUp") != replicas:
        errors.append(f"summary replicasUp={summary.get('replicasUp')}")
    if "ttftP99Ms" not in summary:
        errors.append("no merged TTFT histogram in summary")
    for httpd in servers:
        httpd.shutdown()
        httpd.server_close()

    print(json.dumps({
        "metric": f"fleet scrape sweep ({replicas} replicas)",
        "value": round(sweep_ms, 1),
        "unit": "ms",
        # Acceptance: a sweep at smoke scale stays under 1 s.
        "vs_baseline": round(1000.0 / max(sweep_ms, 1e-9), 2),
        "replicas_scraped": ok,
        "summary": summary,
        "bench_errors": errors,
    }))
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
