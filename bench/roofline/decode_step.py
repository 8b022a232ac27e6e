"""Model FLOPs one decode step needs per live row, from the configuration's
shapes and the row's true KV length (a dense GQA decoder).

Per token: 2 FLOPs per weight of every matrix product (Q, K, V and output
projections, the MLP's up and down projections, the LM head), plus
attention's 4 x heads x head_dim x kv_len per layer.  The embedding lookup,
norms, rotary embedding and softmax are left out (they are not matrix
products and take a few per cent of the count at most).
"""


def matmul_weights(cfg: dict) -> int:
    d, hq, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    per_layer = d * (hq + 2 * hkv) * hd + hq * hd * d + 2 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def flops(cfg: dict, kv_lengths) -> int:
    """FLOPs of one token per entry of ``kv_lengths`` (the KV rows each
    token attends)."""
    dense = 2 * matmul_weights(cfg)
    attn = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])
    n = total = 0
    for kv in kv_lengths:
        n += 1
        total += kv
    return n * dense + attn * total
