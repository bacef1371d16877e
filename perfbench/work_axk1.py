"""The work an A.X-K1 decoder (`refs/axk1_decoder.py`) needs on the chip that
holds one share of it, counted from shapes, from how many held experts got a
row and from the tokens the steps held, beside `work.py` (whose peaks and
`least_time` it uses).  Nothing here looks at the program.

A decode step multiplies every token with each layer's attention projections,
with the leading dense layer's SwiGLU or an expert layer's router and shared
expert, with the held experts it was routed to, and with the head's slice; it
has to read those weights once a step, but of the held experts only the ones
some token chose (`moe_experts_touched`, summed over layers and steps), and the
latent rows of the tokens held (`decode_tokens_held`): `kv_lora_rank +
qk_rope_head_dim` values a token a layer, the published width and not a padded
one.  Attention against those rows is counted in the absorbed form, the one a
latent cache allows: a query head's 576-wide product for the score and its
512-wide product for the output, 2 x 64 x (576 + 512) operations a token held
a layer a row.
"""

from perfbench import work
from perfbench.refs import axk1_decoder


def attention_params(cfg):
    """One layer's attention: W_qa, W_qb, W_kva, W_kvb, W_o."""
    s = axk1_decoder.dims(cfg)
    return (s["d"] * s["qr"] + s["qr"] * s["h"] * (s["dn"] + s["dr"]) + s["d"] * (s["kr"] + s["dr"])
            + s["kr"] * s["h"] * (s["dn"] + s["dv"]) + s["h"] * s["dv"] * s["d"])


def norm_params(cfg):
    """A layer's gains: two on the residual, one on each latent."""
    s = axk1_decoder.dims(cfg)
    return 2 * s["d"] + s["qr"] + s["kr"]


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    s = axk1_decoder.dims(cfg)
    return 3 * s["d"] * s["f"]


def dense_layer_params(cfg):
    """A leading dense layer, whole."""
    s = axk1_decoder.dims(cfg)
    return attention_params(cfg) + norm_params(cfg) + 3 * s["d"] * s["fd"]


def expert_layer_own_params(cfg):
    """An expert layer without its routed experts, what every chip of a layer
    holds alike: attention, the gains, the router over ALL experts and the
    shared expert."""
    s = axk1_decoder.dims(cfg)
    return attention_params(cfg) + norm_params(cfg) + s["d"] * s["e_all"] + 3 * s["d"] * s["fs"]


def head_params(cfg):
    """The head's slice, read once a step (the embedding is a row gather)."""
    s = axk1_decoder.dims(cfg)
    return s["d"] * s["v"]


def held_params(cfg):
    """Every parameter this chip holds."""
    s = axk1_decoder.dims(cfg)
    n1 = s["n"] - s["n0"]
    return (s["n0"] * dense_layer_params(cfg)
            + n1 * (expert_layer_own_params(cfg) + s["e"] * expert_params(cfg))
            + 2 * head_params(cfg) + s["d"])


def step_own_params(cfg):
    """What a step reads whatever the routing: the layers' own and the head."""
    s = axk1_decoder.dims(cfg)
    return (s["n0"] * dense_layer_params(cfg) + (s["n"] - s["n0"]) * expert_layer_own_params(cfg)
            + head_params(cfg))


def latent_bytes_per_token(cfg, itemsize=2):
    """A token's rows in the pages of all layers."""
    s = axk1_decoder.dims(cfg)
    return s["n"] * (s["kr"] + s["dr"]) * itemsize


def attention_flops_per_token_held(cfg):
    """Absorbed attention of one row against one token held, all layers."""
    s = axk1_decoder.dims(cfg)
    return s["n"] * 2 * s["h"] * ((s["kr"] + s["dr"]) + s["kr"])


def decode_least_time(cfg, steps, decode_tokens, tokens_held, experts_touched, peak, itemsize=2):
    """Least time for `steps` decode steps that emit `decode_tokens` tokens,
    whose rows attended over `tokens_held` tokens summed over rows and steps,
    and in which `experts_touched` (layer, step, held expert) triples got at
    least one row.  A token's routed work is what the held experts did of it:
    on average `k x held / all` experts a layer."""
    s = axk1_decoder.dims(cfg)
    routed = (s["n"] - s["n0"]) * s["k"] * s["e"] / s["e_all"] * expert_params(cfg)
    flops = 2 * (step_own_params(cfg) + routed) * decode_tokens \
        + attention_flops_per_token_held(cfg) * tokens_held
    nbytes = (steps * step_own_params(cfg) + experts_touched * expert_params(cfg)) * itemsize \
        + latent_bytes_per_token(cfg, itemsize) * tokens_held
    return work.least_time(flops, nbytes, peak)


def prefill_experts_touched_at_most(cfg, prompt_lengths):
    """The most (layer, held expert) pairs prefills of these lengths can touch:
    a prompt of p tokens has p x k picks and reaches at most the held experts."""
    s = axk1_decoder.dims(cfg)
    return sum((s["n"] - s["n0"]) * min(p * s["k"], s["e"]) for p in prompt_lengths)


def prefill_least_time(cfg, prompt_lengths, experts_touched, peak, itemsize=2):
    """Least time to prefill prompts of the given lengths, one dispatch each:
    causal attention over each prompt in the expanded form (a head's 192-wide
    score and 128-wide value product a pair, its keys and values expanded from
    the latent once a token), the head at one position, the layers' own weights
    read once a dispatch and the touched experts once."""
    s = axk1_decoder.dims(cfg)
    tokens = sum(prompt_lengths)
    pairs = sum(p * (p + 1) // 2 for p in prompt_lengths)
    routed = (s["n"] - s["n0"]) * s["k"] * s["e"] / s["e_all"] * expert_params(cfg)
    flops = 2 * (step_own_params(cfg) - head_params(cfg) + routed) * tokens \
        + 2 * head_params(cfg) * len(prompt_lengths) \
        + s["n"] * 2 * s["h"] * (s["dn"] + s["dr"] + s["dv"]) * pairs
    nbytes = (len(prompt_lengths) * step_own_params(cfg)
              + experts_touched * expert_params(cfg)) * itemsize \
        + latent_bytes_per_token(cfg, itemsize) * tokens
    return work.least_time(flops, nbytes, peak)
