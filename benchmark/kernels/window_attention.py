"""A window layer's attention core (scope `swa.core`): the operations and
bytes the ALGORITHM needs, from shapes, for a roofline share (kept with
the benchmark, not read from the program). H query heads on n KV heads,
key width d, value width d_v, a window of W keys with the query's own.

prefill   a token at position t sees min(t + 1, W) keys; per visible
          (query, key) pair and head the score over d and the weighted sum
          over d_v, one multiply-add an element: 2 (d + d_v). Traffic: q
          read and o written a query head, k and v read a KV head, once.
decode    a row at context c (c tokens cached, the new one among them)
          reads its live keys, min(c, W): 2 (d + d_v) operations a key and
          query head, (d + d_v) elements a key and KV head.

The sink is one more exponential a head and query: not counted. The
projections, the rotary and the ring's write lie under other scopes.
"""


def prefill_pairs(prompt_tokens: int, window: int) -> float:
    """Sum over t < n of min(t + 1, W)."""
    n, w = int(prompt_tokens), int(window)
    full = max(n - w, 0)
    ramp = min(n, w)
    return full * w + ramp * (ramp + 1) / 2.0


def prefill_operations(pairs: float, heads: int, d: int, dv: int) -> float:
    return 2.0 * heads * (d + dv) * pairs


def prefill_bytes(tokens: float, heads: int, kv_heads: int, d: int, dv: int,
                  elem_bytes: int = 2) -> float:
    return tokens * (heads + kv_heads) * (d + dv) * elem_bytes


def decode_live(context: int, window: int) -> int:
    """Keys a row at `context` cached tokens reads."""
    return min(int(context), int(window))


def decode_operations(live: float, heads: int, d: int, dv: int) -> float:
    """live: live keys read, summed over decoded tokens."""
    return 2.0 * heads * (d + dv) * live


def decode_bytes(live: float, kv_heads: int, d: int, dv: int,
                 elem_bytes: int = 2) -> float:
    return live * kv_heads * (d + dv) * elem_bytes


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    t_ops, t_mem = ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
