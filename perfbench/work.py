"""Peaks of the chips, and the work an algorithm needs, counted from shapes.

Nothing here looks at the program or at compiled code, so the counts are the
same whatever implements the work.  A share of a peak built on them can pass
100% only if a count is wrong.
"""

from perfbench.refs import inception_v3, transformer_decoder

# Published peaks per chip, keyed by jax's `device_kind`.
# TPU v5e: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; add it to PEAKS")
    return PEAKS[device_kind]


def least_time(flops, nbytes, peak):
    """The least time the chip could take: the larger of compute and traffic."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


# ---------------------------------------------------------------- Inception-v3

def inception_flops_per_row():
    """Multiply-adds x 2 of every convolution and of the head, per image."""
    layers, (_, _, feat) = inception_v3.conv_layers()
    conv = sum(2 * l["hout"] * l["wout"] * l["cout"] * l["kh"] * l["kw"] * l["cin"]
               for l in layers)
    return conv + 2 * feat * inception_v3.NUM_CLASSES


def inception_least_time(rows, peak, itemsize=2):
    """Least time for one block of `rows` images: each convolution (and the
    head) reads its input and weights and writes its output once, in the
    serving type, and is bound by the larger of that traffic and its FLOPs."""
    layers, (_, _, feat) = inception_v3.conv_layers()
    total = 0.0
    for l in layers:
        flops = rows * 2 * l["hout"] * l["wout"] * l["cout"] * l["kh"] * l["kw"] * l["cin"]
        acts = rows * (l["hin"] * l["win"] * l["cin"] + l["hout"] * l["wout"] * l["cout"])
        weights = l["kh"] * l["kw"] * l["cin"] * l["cout"]
        total += least_time(flops, (acts + weights) * itemsize, peak)
    n = inception_v3.NUM_CLASSES
    total += least_time(rows * 2 * feat * n, (rows * (feat + n) + feat * n) * itemsize, peak)
    return total


# ------------------------------------------------------ decoder-only transformer

def transformer_layer_params(cfg):
    s = transformer_decoder.dims(cfg)
    return s["d"] * (s["hd"] + 2 * s["kd"]) + s["hd"] * s["d"] + 3 * s["d"] * s["f"] + 2 * s["d"]


def transformer_matmul_params(cfg):
    """Parameters every token is multiplied with: the layers and the head
    (the embedding is a row gather)."""
    s = transformer_decoder.dims(cfg)
    return s["n"] * (transformer_layer_params(cfg) - 2 * s["d"]) + s["d"] * s["v"]


def transformer_kv_bytes_per_token(cfg, itemsize=2):
    s = transformer_decoder.dims(cfg)
    return s["n"] * 2 * s["kd"] * itemsize


def decode_least_time(cfg, steps, decode_tokens, context_tokens, peak, itemsize=2):
    """Least time for `steps` decode steps that emit `decode_tokens` tokens in
    all, whose streams hold `context_tokens` positions summed over every
    stream and step: the weights are read once a step, and K and V only of
    the tokens held (not of the capacity)."""
    s = transformer_decoder.dims(cfg)
    w = transformer_matmul_params(cfg)
    flops = 2 * w * decode_tokens + 4 * s["n"] * s["hd"] * context_tokens
    nbytes = steps * w * itemsize + transformer_kv_bytes_per_token(cfg, itemsize) * context_tokens
    return least_time(flops, nbytes, peak)


def prefill_least_time(cfg, batches, prompt_lengths, peak, itemsize=2):
    """Least time to prefill prompts of the given lengths in `batches`
    batches: causal attention over each prompt, weights read once a batch."""
    s = transformer_decoder.dims(cfg)
    w = transformer_matmul_params(cfg)
    tokens = sum(prompt_lengths)
    pairs = sum(p * (p + 1) // 2 for p in prompt_lengths)
    head = s["d"] * s["v"]  # only a prompt's last position needs logits
    flops = 2 * (w - head) * tokens + 2 * head * len(prompt_lengths) + 4 * s["n"] * s["hd"] * pairs
    nbytes = batches * w * itemsize + transformer_kv_bytes_per_token(cfg, itemsize) * tokens
    return least_time(flops, nbytes, peak)
