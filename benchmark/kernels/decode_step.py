"""One decode step of a dense decoder: the least bytes and operations it
needs, from shapes. A step reads every weight once (the batch shares the
read) and each slot's keys and values up to its context; it computes two
operations per weight and slot. At serving batches it is memory bound."""


def weight_params(as_run: dict) -> int:
    h, f = as_run["hidden_size"], as_run["ffn_hidden_size"]
    q = as_run["num_attention_heads"] * as_run["head_dim"]
    kv = as_run["num_kv_heads"] * as_run["head_dim"]
    per_layer = h * q + 2 * h * kv + q * h + 2 * h * f
    # The tied head reads the whole embedding matrix once a step.
    return as_run["num_hidden_layers"] * per_layer \
        + as_run["vocab_size"] * h


def least_seconds(as_run: dict, slots: float, context_tokens: float,
                  peaks: dict, chips: int = 1, elem_bytes: int = 2) -> tuple:
    """(seconds, which bound). context_tokens: keys held, summed over the
    active slots. Weights and cache are split over `chips`."""
    params = weight_params(as_run)
    kv = as_run["num_kv_heads"] * as_run["head_dim"]
    kv_bytes = (context_tokens * as_run["num_hidden_layers"] * 2 * kv
                * elem_bytes)
    t_mem = (params * elem_bytes + kv_bytes) / chips \
        / peaks["hbm_bytes_per_s"]
    t_ops = 2.0 * params * slots / chips / peaks["bf16_flops"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
