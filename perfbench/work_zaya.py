"""The work a ZAYA1 decoder (`refs/zaya_decoder.py`) needs, counted from
shapes and from how many experts got a token, beside `work.py` (whose peaks
and `least_time` it uses).  Nothing here looks at the program.

A decode step multiplies every token with the layer's attention, convolution
and router weights, with ONE expert, and with the tied head; it has to read
those weights once a step, but of the experts only the ones some token chose
(`moe_experts_touched`, summed over layers and steps), and K and V of the
tokens held.  So the least time follows the routing: a step whose 48 tokens
fall on 15 of 16 experts reads 15 experts' weights in that layer.
"""

from perfbench import work
from perfbench.refs import zaya_decoder


def expert_params(cfg):
    """One expert: gate, up and down."""
    s = zaya_decoder.dims(cfg)
    return 3 * s["d"] * s["f"]


def layer_other_params(cfg):
    """A layer without its experts: the four attention projections, the two
    convolutions with their biases, the key temperatures, the router (its
    down-projection, depth-averaging scale, norm, three matrices and bias)
    and the two norms."""
    s = zaya_decoder.dims(cfg)
    heads = s["h"] + s["kvh"]
    attention = s["d"] * (s["hd"] + 2 * s["kd"]) + s["hd"] * s["d"]
    convolutions = 3 * s["c"] + 2 * heads * s["dh"] * s["dh"] + heads * s["dh"] + s["kvh"]
    router = s["d"] * s["r"] + 1 + s["r"] + 2 * s["r"] * s["r"] + s["r"] * s["e"] + s["e"]
    return attention + convolutions + router + 2 * s["d"]


def head_params(cfg):
    """The tied head: the embedding table, read once a step."""
    s = zaya_decoder.dims(cfg)
    return s["v"] * s["d"]


def kv_bytes_per_token(cfg, itemsize=2):
    s = zaya_decoder.dims(cfg)
    return s["n"] * 2 * s["kd"] * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """The convolution state of one sequence, all layers: c, c1 and the
    shifted half of the value."""
    s = zaya_decoder.dims(cfg)
    return s["n"] * (2 * s["c"] + s["vs"]) * itemsize


def token_flops(cfg):
    """Multiply-adds x 2 a token needs outside attention's scores: the
    layer's own matrices, one expert, in every layer (the head is apart)."""
    s = zaya_decoder.dims(cfg)
    return 2 * s["n"] * (layer_other_params(cfg) + expert_params(cfg))


def decode_least_time(cfg, steps, decode_tokens, context_tokens, experts_touched, peak,
                      itemsize=2):
    """Least time for `steps` decode steps that emit `decode_tokens` tokens,
    whose streams hold `context_tokens` positions summed over streams and
    steps, and in which `experts_touched` (layer, step, expert) triples got
    at least one token."""
    s = zaya_decoder.dims(cfg)
    flops = (token_flops(cfg) + 2 * head_params(cfg)) * decode_tokens \
        + 4 * s["n"] * s["hd"] * context_tokens
    nbytes = (steps * (s["n"] * layer_other_params(cfg) + head_params(cfg))
              + experts_touched * expert_params(cfg)) * itemsize \
        + kv_bytes_per_token(cfg, itemsize) * context_tokens
    return work.least_time(flops, nbytes, peak)


def prefill_experts_touched_at_most(cfg, prompt_lengths):
    """The most (layer, expert) pairs prefills of these lengths can touch:
    a prompt of p tokens reaches at most min(p, experts) experts a layer."""
    s = zaya_decoder.dims(cfg)
    return sum(s["n"] * min(p, s["e"]) for p in prompt_lengths)


def prefill_least_time(cfg, prompt_lengths, experts_touched, peak, itemsize=2):
    """Least time to prefill prompts of the given lengths, one dispatch each:
    causal attention over each prompt, the head at one position, the layer
    weights read once a dispatch and the touched experts once."""
    s = zaya_decoder.dims(cfg)
    tokens = sum(prompt_lengths)
    pairs = sum(p * (p + 1) // 2 for p in prompt_lengths)
    flops = token_flops(cfg) * tokens + 2 * head_params(cfg) * len(prompt_lengths) \
        + 4 * s["n"] * s["hd"] * pairs
    nbytes = (len(prompt_lengths) * (s["n"] * layer_other_params(cfg) + head_params(cfg))
              + experts_touched * expert_params(cfg)) * itemsize \
        + kv_bytes_per_token(cfg, itemsize) * tokens
    return work.least_time(flops, nbytes, peak)
