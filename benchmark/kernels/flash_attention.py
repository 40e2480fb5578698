"""Flash attention: the operations and bytes the ALGORITHM needs, from
shapes, for a roofline share (kept with the benchmark, not read from the
program). `pairs` is the number of (query, key) pairs that are not masked:
causal and, in packed rows, inside one document. Padding, masked blocks
the kernel visits anyway, and recomputation inside a kernel are the
kernel's cost, not needed work.

Per unmasked pair and head, with head size d: one multiply-add per
element of a d-long dot product is 2d operations.
  forward   S = QK^T, O = PV                       2 products: 4d
  dq        S again, dP = dO V^T, dQ = dS K        3 products: 6d
  dkv       S again, dP, dV = P^T dO, dK = dS^T Q  4 products: 8d
"""

PRODUCTS = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}


def causal_pairs(lengths) -> int:
    """Unmasked pairs of causal sequences of these lengths."""
    return sum(n * (n + 1) // 2 for n in lengths)


def operations(kernel: str, pairs: float, q_heads: int, head_dim: int):
    return PRODUCTS[kernel] * 2 * head_dim * q_heads * pairs


def bytes_moved(kernel: str, q_tokens: float, kv_tokens: float,
                q_heads: int, kv_heads: int, head_dim: int,
                elem_bytes: int = 2) -> float:
    """Least traffic: every operand read once, every result written once.
    q_tokens / kv_tokens are summed over the batch."""
    q = q_tokens * q_heads * head_dim * elem_bytes
    kv = 2 * kv_tokens * kv_heads * head_dim * elem_bytes
    stats = q_tokens * q_heads * 4
    if kernel == "flash_fwd":       # read q, k, v; write o, lse
        return 2 * q + kv + stats
    if kernel == "flash_dq":        # read q, k, v, do, lse, delta; write dq
        return 3 * q + kv + 2 * stats
    # dkv: read q, k, v, do, lse, delta; write dk, dv per query head
    return 2 * q + kv + 2 * stats + 2 * q


def least_seconds(kernel, pairs, q_tokens, kv_tokens, q_heads, kv_heads,
                  head_dim, peaks) -> tuple:
    """(seconds, which bound) the chip could do it in at its peaks."""
    t_ops = operations(kernel, pairs, q_heads, head_dim) / peaks["bf16_flops"]
    t_mem = bytes_moved(kernel, q_tokens, kv_tokens, q_heads, kv_heads,
                        head_dim) / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
