"""FLOPs a dense decoder's training step requires, per token.

Required means what forward and backward must compute: 6 FLOPs per token
for every matmul parameter (2 forward, 4 backward), plus causal attention's
score and value products over the positions each query sees.  Recomputation
(remat, the blocked loss's second head pass) and the masked half of blocked
attention are not counted.  The head is counted over the real vocabulary,
not the padded one, and a tied embedding is counted once, as the head.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Matmul parameters of the configuration file ``c`` (HF keys)."""
    D, F = c["hidden_size"], c["intermediate_size"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    head = c["vocab_size"] * D  # the input embedding is a lookup
    return c["num_hidden_layers"] * per_layer + head


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward of QK^T and PV under a causal mask: a query at
    position i sees i + 1 keys, so on average (seq + 1) / 2; each product
    is 2 FLOPs per key per head dim forward and twice that backward."""
    H, hd, L = c["num_attention_heads"], c["head_dim"], c["num_hidden_layers"]
    mean_keys = (seq + 1) / 2
    return 3 * L * 2 * (2 * H * hd * mean_keys)


def train_flops_per_token(c: dict, seq: int) -> float:
    return 6 * matmul_params(c) + attention_flops_per_token(c, seq)
