"""Lightning attention (the token mixer of a `lightning-attn` layer, scope
`lightning.core`): the operations and bytes the ALGORITHM needs, from
shapes, for a roofline share (kept with the benchmark, not read from the
program).

Per token and head, with key size d_k and value size d_v, the recurrence
  S <- lambda S + k v^T,   o = S^T q / sqrt(d_k)
needs the rank-one update and the read, one multiply-add an element of the
d_k x d_v state each: 2 x 2 d_k d_v = 4 d_k d_v operations. The decay's
elementwise scaling, the norms, the rotary and the chunked form's products
inside a chunk (Q K^T, its decay mask, the product with V) are the
implementation's cost, not needed work.

Least traffic: q, k, v read and o written once a token, in the activation
type; the float32 state read and written once for each pass over it: once
a sequence in prefill, once a step in decode.
"""

OPS_PER_STATE_ELEMENT = 4     # per token and head: 2 multiply-adds


def operations(tokens: float, heads: int, dk: int, dv: int) -> float:
    return OPS_PER_STATE_ELEMENT * dk * dv * heads * tokens


def bytes_moved(tokens: float, state_passes: float, heads: int, dk: int,
                dv: int, elem_bytes: int = 2) -> float:
    """tokens: tokens mixed, summed over rows. state_passes: how often a
    row's whole state is read and written (sequences prefilled, or live
    rows x decode steps)."""
    per_token = heads * (2 * dk + 2 * dv) * elem_bytes
    per_pass = heads * dk * dv * 4 * 2
    return tokens * per_token + state_passes * per_pass


def least_seconds(tokens: float, state_passes: float, heads: int, dk: int,
                  dv: int, peaks: dict) -> tuple:
    """(seconds, which bound) one layer could do it in at the chip's
    peaks."""
    t_ops = operations(tokens, heads, dk, dv) / peaks["bf16_flops"]
    t_mem = bytes_moved(tokens, state_passes, heads, dk, dv) \
        / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
