"""The serving entry point with its timed path broken underneath: every
token is altered where it is produced (the sampler's answer plus one).
The harness test puts this in the place of `-m runbooks_tpu.serve.api`
and must see `correct` come out false."""

import runbooks_tpu.serve.engine as engine

_sample = engine.sample


def _altered(logits, *args, **kwargs):
    return (_sample(logits, *args, **kwargs) + 1) % logits.shape[-1]


engine.sample = _altered

from runbooks_tpu.serve import api  # noqa: E402

raise SystemExit(api.main())
