"""What the process runs on: the one "is this a TPU" probe, the per-chip
peak table, and the backend-dependent serving defaults."""

from __future__ import annotations

# Per-chip peaks keyed by a substring of jax.Device.device_kind:
# (dense bf16 FLOP/s, HBM bytes/s). Source: Google Cloud TPU documentation,
# system-architecture pages per generation. libtpu 0.0.34 reports a v5e as
# "TPU v5 lite".
CHIP_PEAKS = {
    "v5 lite": (197e12, 819e9),   # v5e
    "v5litepod": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6e": (918e12, 1640e9),
}


def on_tpu() -> bool:
    """The single answer to "is this a TPU": the default JAX backend's
    name. Kernel dispatch (Mosaic vs the Pallas interpreter), the
    attention_impl auto rule, the serving defaults and the benches all ask
    here, so they cannot disagree."""
    import jax

    return jax.default_backend() == "tpu"


def chip_peaks(device) -> tuple[float, float] | None:
    """(peak bf16 FLOP/s, HBM bytes/s) of one chip. None off-TPU: a CPU run
    has no device peak, so every field derived from one (MFU, roofline
    bound, minimum time) is absent there rather than computed from a
    stand-in. A TPU whose device_kind is not in CHIP_PEAKS raises — an
    unknown chip must be added to the table, not defaulted."""
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key, peaks in CHIP_PEAKS.items():
        if key in kind:
            return peaks
    raise ValueError(
        f"TPU device_kind {device.device_kind!r} is not in "
        "runbooks_tpu.utils.hw.CHIP_PEAKS; add its published peaks")


def backend_tuning() -> dict:
    """Backend-dependent serving defaults.

    - ``decode_chunk``: decode steps per host round-trip. 8 on TPU — a
      per-step host sync dominates small-batch inter-token latency
      there; 1 elsewhere (CPU dispatch is cheap and tests want
      step-at-a-time).
    - ``draft_tokens``: default speculative draft window K
      (docs/speculative-decoding.md). 4 on every backend today.
    """
    return {"decode_chunk": 8 if on_tpu() else 1,
            "draft_tokens": 4}


def device_identity(mesh=None) -> dict:
    """platform / device_kind / count as JAX reports them (plus the mesh's
    axes larger than 1, when one is given) — the identity fields every
    entry point's start-up line and every summary carries. Raises on a TPU
    that is not in CHIP_PEAKS, so no entry point starts on a chip whose
    peaks nobody wrote down."""
    import jax

    devices = jax.devices()
    chip_peaks(devices[0])
    ident = {"backend": jax.default_backend(),
             "platform": devices[0].platform,
             "device_kind": devices[0].device_kind,
             "device_count": len(devices)}
    if mesh is not None:
        ident["mesh"] = {a: n for a, n in mesh.shape.items() if n > 1}
    return ident
