"""The sparse read of a `minicpm4` layer (scopes `bsa.select`, `bsa.core`):
the operations and bytes the ALGORITHM needs, from shapes, for a roofline
share (kept with the benchmark, not read from the program). H query heads
on n KV heads of width d; `sp` is the configuration's `sparse_config`
(block_size, topk, window_size, init_blocks, kernel_size, kernel_stride,
dense_len).

keys read   a token at position t of a row shorter than dense_len reads
            every key, t + 1. Else its window (min(t + 1, window_size)),
            the initial blocks' keys before the window and block_size keys
            a block it chooses beside them: topk - init_blocks blocks, or
            every candidate while there are fewer (a block is a candidate
            while it starts before the window); never more than t + 1. The
            one block that may straddle the window's start is counted
            whole (at most block_size - 1 keys a token too many). How
            many keys a token reads does not depend on WHICH blocks it
            chooses, so the count is from positions alone.
core        per (query, key read) pair and query head the score over d and
            the weighted sum over d: 4 d operations.
select      per query head and WHOLE compressed key (kernel_stride j +
            kernel_size <= t + 1) one score over d: 2 d operations. The
            softmax over them, the maximum a block and the ranking are the
            choice's bookkeeping, not counted.
traffic     prefill: q read and o written a query head and token; k and v
            a KV head, for every block of block_size queries what its last
            token reads (a query block shares its reads at best); the
            compressed keys (float32) once a query block. Decode: what the
            token reads of k and v, and the whole compressed keys, a row.

A prompt is prefilled whole: its tokens are read by the PROMPT's length
(dense below dense_len). A decoded token's row is t + 1 long.
"""


def keys_read(t: int, row_len: int, sp: dict) -> int:
    """Keys the query at position t reads of one KV head, in a row that
    is row_len long when it is computed."""
    if row_len < sp["dense_len"]:
        return t + 1
    lo = max(t - sp["window_size"] + 1, 0)
    candidates = max(-(-lo // sp["block_size"]) - sp["init_blocks"], 0)
    read = (min(t + 1, sp["window_size"])
            + min(sp["init_blocks"] * sp["block_size"], lo)
            + sp["block_size"] * min(candidates,
                                     sp["topk"] - sp["init_blocks"]))
    return min(read, t + 1)


def whole_kernels(t: int, sp: dict) -> int:
    """Compressed keys that are whole for the query at position t."""
    return max((t + 1 - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0)


def prompt_pairs(n: int, sp: dict) -> float:
    """Score pairs a query head needs for a prompt of n tokens."""
    return float(sum(keys_read(t, n, sp) for t in range(int(n))))


def decode_pairs(context: int, sp: dict) -> float:
    """... for the token decoded at position `context` (that many tokens
    cached before it)."""
    return float(keys_read(int(context), int(context) + 1, sp))


def core_operations(pairs: float, heads: int, d: int) -> float:
    return 4.0 * d * heads * pairs


def select_operations(positions, row_len, heads: int, d: int,
                      sp: dict) -> float:
    """The compressed scores of the queries at `positions` (none for a row
    below dense_len: it chooses nothing)."""
    if row_len < sp["dense_len"]:
        return 0.0
    return 2.0 * d * heads * sum(whole_kernels(t, sp) for t in positions)


def prefill_bytes(n: int, heads: int, kv_heads: int, d: int, sp: dict,
                  elem_bytes: int = 2) -> float:
    n, block = int(n), sp["block_size"]
    q_and_o = n * heads * 2 * d * elem_bytes
    shared = sum(keys_read(min(first + block, n) - 1, n, sp)
                 for first in range(0, n, block))
    kv = shared * kv_heads * 2 * d * elem_bytes
    compressed = sum(whole_kernels(min(first + block, n) - 1, sp)
                     for first in range(0, n, block)) * kv_heads * d * 4 \
        if n >= sp["dense_len"] else 0
    return float(q_and_o + kv + compressed)


def decode_bytes(pairs: float, contexts, heads: int, kv_heads: int, d: int,
                 sp: dict, elem_bytes: int = 2) -> float:
    """pairs: keys read, summed over decoded tokens (decode_pairs);
    contexts: the position each was decoded at."""
    compressed = sum(whole_kernels(c, sp) for c in contexts
                     if c + 1 >= sp["dense_len"]) * kv_heads * d * 4
    return float(pairs * kv_heads * 2 * d * elem_bytes + compressed
                 + len(contexts) * heads * 2 * d * elem_bytes)


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    t_ops, t_mem = ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
