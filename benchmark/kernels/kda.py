"""Kimi Delta Attention (the token mixer of a KDA layer, scope `kda.core`):
the operations and bytes the ALGORITHM needs, from shapes, for a roofline
share (kept with the benchmark, not read from the program).

Per token and head, with key size d_k and value size d_v, the recurrence
  S' = Diag(alpha) S,  S <- S' - beta k (k^T S') + beta k v^T,  o = S^T q
needs three products of a d_k vector with the d_k x d_v state, one
multiply-add an element each (k^T S', the rank-one update, S^T q: 3 x 2 d_k
d_v = 6 d_k d_v operations), and the decay's multiply an element of the
state, d_k d_v more: the decay is a channel's, so it cannot be folded into
a number a head. 7 d_k d_v in all. The norms, the exponentials and the
chunked form's extra products (A, the inverse of I + A, U, W, the decays
inside the contraction) are the implementation's cost, not needed work.

Least traffic: q, k, v read and o written once a token in the activation
type, the log-decay g [d_k] and beta read once a token and head in
float32; the float32 state read and written once for each pass over it:
once a sequence in prefill, once a step in decode.
"""

OPS_PER_STATE_ELEMENT = 7     # per token and head: 3 multiply-adds + decay


def operations(tokens: float, heads: int, dk: int, dv: int) -> float:
    return OPS_PER_STATE_ELEMENT * dk * dv * heads * tokens


def bytes_moved(tokens: float, state_passes: float, heads: int, dk: int,
                dv: int, elem_bytes: int = 2) -> float:
    """tokens: tokens mixed, summed over rows. state_passes: how often a
    row's whole state is read and written (sequences prefilled, or live
    rows x decode steps)."""
    per_token = heads * ((2 * dk + 2 * dv) * elem_bytes + (dk + 1) * 4)
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
