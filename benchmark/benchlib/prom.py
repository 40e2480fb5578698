"""Read a Prometheus text exposition: sums of families across labels."""

from __future__ import annotations


def parse(text: str) -> dict:
    """{sample_name: sum over label sets}. Histogram buckets are skipped:
    their percentiles are too coarse; only _sum and _count are read."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        if name.endswith("_bucket"):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def mean_ms(d: dict, family: str, per: float = 1.0):
    """Delta-sum / delta-count of a histogram family, in ms; None if the
    family saw nothing in the window."""
    n = d.get(family + "_count", 0.0)
    if n <= 0:
        return None
    return d[family + "_sum"] / (n * per) * 1e3
