"""The routed experts of a sparse FFN (the grouped product of
models/moe.py, scope `moe.experts`): the operations and bytes the
ALGORITHM needs, from shapes and from what the router chose, for a
roofline share (kept with the benchmark, not read from the program).

An assignment is one (token, expert) pair whose expert is held here. A
gated expert of width f on a hidden size h is three matrices of h x f:
gate, up, down: one multiply-add an element each, 6 h f operations an
assignment. Sorting, gathering and the gate weights are the
implementation's cost, not needed work.

Least traffic: the three matrices of every (layer, expert) pair that got
at least one token in a forward, read ONCE that forward (`hits`: a pair
without a token needs no byte), plus each assignment's row in (h) and out
(h). Never the matrices of all held experts a forward: a decode step at 8
rows reaches 4 in 10 of them.
"""


def operations(assignments: float, hidden: int, width: int) -> float:
    return 6.0 * hidden * width * assignments


def bytes_moved(assignments: float, hits: float, hidden: int, width: int,
                elem_bytes: int = 2) -> float:
    """hits: (layer, expert) pairs with at least one token, summed over
    forwards."""
    return (hits * 3 * hidden * width + assignments * 2 * hidden) \
        * elem_bytes


def least_seconds(assignments: float, hits: float, hidden: int, width: int,
                  peaks: dict) -> tuple:
    """(seconds, which bound) the chip could do it in at its peaks."""
    t_ops = operations(assignments, hidden, width) / peaks["bf16_flops"]
    t_mem = bytes_moved(assignments, hits, hidden, width) \
        / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
