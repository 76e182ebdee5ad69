"""What the attention projections of one device need a step, over
dims = (tokens a data-parallel replica holds a step, tensor-parallel
size): Q, K, V ([h, (q + 2 kv) d]) and the output projection ([q d, h]),
each three times (the forward product and the backward's two: gradient of
the input, gradient of the weight), every layer, nothing computed again;
tensor parallelism divides each matrix over its devices (under sequence
parallelism the matmuls still see every token of the replica). Bytes:
each product reads two of input, weight and output and writes the third,
so the three together move each of them three times. The region's class
`matmul` runs it (layer_metrics/attention_matmul_roofline_pct.py gives
the dims from the cell's traffic: no kernel, so no call's HLO text)."""


def needed(dims, itemsize, config):
    if len(dims) != 2:
        return None
    tokens, tp = dims
    h = config["hidden_size"]
    q = config["num_attention_heads"]
    kv = config.get("num_key_value_heads", q)
    d = config.get("head_dim") or h // q
    layers = config["num_hidden_layers"]
    qkv, out = h * (q + 2 * kv) * d, q * d * h       # multiply-adds a token
    flops = 3 * 2.0 * tokens * (qkv + out) / tp * layers
    moved = (tokens * (h + (q + 2 * kv) * d / tp) + qkv / tp
             + tokens * (q * d / tp + h) + out / tp)
    return flops, float(3 * moved * itemsize * layers)
