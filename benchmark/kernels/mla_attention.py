"""Latent attention's core (scope `mla.core`): the operations and bytes the
ALGORITHM needs, from shapes, for a roofline share (kept with the
benchmark, not read from the program). H heads, key width d_n + d_r, value
width d_v, latent width r + d_r cached a token with no head axis.

prefill (expanded)   per visible (query, key) pair and head: the score over
                     d_n + d_r and the weighted sum over d_v, one
                     multiply-add an element: 2 (d_n + d_r + d_v). Traffic:
                     q read, per-head k and v read, o written, once.
decode (absorbed)    per cached token, row and head: the score over the
                     latent (r + d_r) and the weighted sum of c (r):
                     2 (2 r + d_r). Traffic: the cached token's latent read
                     once a row and layer, (r + d_r) elements.

Expanding k and v from the latent, absorbing the query and the output,
the rotary and the norms lie under other scopes and are not counted.
"""


def prefill_operations(pairs: float, heads: int, dq: int, dv: int) -> float:
    return 2.0 * heads * (dq + dv) * pairs


def prefill_bytes(tokens: float, heads: int, dq: int, dv: int,
                  elem_bytes: int = 2) -> float:
    """q and k at dq, v and o at dv, a token and head."""
    return tokens * heads * 2 * (dq + dv) * elem_bytes


def decode_operations(cached: float, heads: int, rank: int,
                      rope: int) -> float:
    """cached: cached tokens read, summed over decoded tokens (a decoded
    token at context n reads n)."""
    return 2.0 * heads * (2 * rank + rope) * cached


def decode_bytes(cached: float, rank: int, rope: int,
                 elem_bytes: int = 2) -> float:
    return cached * (rank + rope) * elem_bytes


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    t_ops, t_mem = ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
