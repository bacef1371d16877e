"""The work a Trinity decoder (`refs/trinity_decoder.py`) needs on the chip
that holds one share of it, counted from shapes, from how many held experts
got a row and from the keys the steps read, beside `work.py` (whose peaks and
`least_time` it uses).  Nothing here looks at the program.

A decode step multiplies every live token with each layer's attention
projections (q, k, v, o and the output gate), with the leading dense layer's
SwiGLU or an expert layer's router and shared expert, with the held experts it
was routed to, and with the head's slice; it has to read those weights once a
step, but of the held experts only the ones some token chose
(`moe_experts_touched`, summed over layers and steps).  It reads K and V of
the keys each kind of layer attends over, once: the full layers' every key a
row holds (`decode_tokens_held`), the window layers' at most the window's
(`decode_window_tokens_held`), 2 x 8 heads x 128 x 2 B = 4,096 B a key a layer.
That K and V is the least time of the paged-attention kernel, which moves
nothing else of size (`kernel_least_time`).

A prefill's attention is counted apart by kind: a full layer's causal pairs,
p (p + 1) / 2 for a prompt of p, a window layer's at most `window` keys a
query; each pair 2 x 48 heads x 128 x 2 operations (score and value).
"""

from perfbench import work
from perfbench.refs import trinity_decoder


def _dims(cfg):
    return trinity_decoder.dims(cfg)


def attention_params(cfg):
    """One layer's attention: W_q, W_k, W_v, W_o and the gate's W_g."""
    s = _dims(cfg)
    return 3 * s["d"] * s["hd"] + 2 * s["d"] * s["kd"]


def norm_params(cfg):
    """A layer's gains: four on the residual, the q and k norms'."""
    s = _dims(cfg)
    return 4 * s["d"] + 2 * s["dh"]


def expert_params(cfg):
    """One routed expert (or the shared one): gate, up and down."""
    s = _dims(cfg)
    return 3 * s["d"] * s["f"]


def router_params(cfg):
    """The router over ALL experts, with its selection bias."""
    s = _dims(cfg)
    return s["d"] * s["e_all"] + s["e_all"]


def dense_layer_params(cfg):
    """A leading dense layer, whole."""
    s = _dims(cfg)
    return attention_params(cfg) + norm_params(cfg) + 3 * s["d"] * s["fd"]


def expert_layer_own_params(cfg):
    """An expert layer without its routed experts, what every chip of a layer
    holds alike: attention, the gains, the router and the shared expert."""
    s = _dims(cfg)
    return attention_params(cfg) + norm_params(cfg) + router_params(cfg) \
        + s["fs"] // s["f"] * expert_params(cfg)


def expert_layer_params(cfg):
    """An expert layer as this chip holds it: its own and the held experts."""
    return expert_layer_own_params(cfg) + _dims(cfg)["e"] * expert_params(cfg)


def head_params(cfg):
    """The head's slice, read once a step (the embedding, as large, is a row
    gather)."""
    s = _dims(cfg)
    return s["d"] * s["v"]


def held_params(cfg):
    """Every parameter this chip holds: the layers, the embedding and head
    slices and the final norm."""
    s = _dims(cfg)
    return (s["n0"] * dense_layer_params(cfg) + (s["n"] - s["n0"]) * expert_layer_params(cfg)
            + 2 * head_params(cfg) + s["d"])


def step_own_params(cfg):
    """What a step reads whatever the routing: the layers' own and the head."""
    s = _dims(cfg)
    return (s["n0"] * dense_layer_params(cfg) + (s["n"] - s["n0"]) * expert_layer_own_params(cfg)
            + head_params(cfg))


def layers_of(cfg):
    """(full layers, window layers) among those held."""
    types = trinity_decoder.kinds(cfg)
    return types.count("full"), types.count("window")


def kv_bytes_per_key(cfg, itemsize=2):
    """K and V of one key in one layer."""
    s = _dims(cfg)
    return 2 * s["kd"] * itemsize


def kernel_bytes(cfg, tokens_held, window_tokens_held, itemsize=2):
    """K and V the steps' attention reads once: the full layers' keys held,
    the window layers' keys in their windows."""
    full, win = layers_of(cfg)
    return kv_bytes_per_key(cfg, itemsize) * (full * tokens_held + win * window_tokens_held)


def kernel_least_time(cfg, tokens_held, window_tokens_held, peak, itemsize=2):
    """The paged-attention kernel's least time over the steps: its K and V,
    once, at the chip's bandwidth (its few FLOPs a byte bound nothing)."""
    return kernel_bytes(cfg, tokens_held, window_tokens_held, itemsize) / peak["bytes_per_s"]


def decode_least_time(cfg, steps, decode_tokens, tokens_held, window_tokens_held,
                      experts_touched, peak, itemsize=2):
    """Least time for `steps` decode steps that emit `decode_tokens` tokens,
    whose rows attended over `tokens_held` keys in each full layer and
    `window_tokens_held` in each window layer, summed over rows and steps,
    and in which `experts_touched` (layer, step, held expert) triples got at
    least one row.  A token's routed work is what the held experts did of it:
    on average `k x held / all` experts a layer."""
    s = _dims(cfg)
    full, win = layers_of(cfg)
    routed = (s["n"] - s["n0"]) * s["k"] * s["e"] / s["e_all"] * expert_params(cfg)
    flops = 2 * (step_own_params(cfg) + routed) * decode_tokens \
        + 4 * s["hd"] * (full * tokens_held + win * window_tokens_held)
    nbytes = (steps * step_own_params(cfg) + experts_touched * expert_params(cfg)) * itemsize \
        + kernel_bytes(cfg, tokens_held, window_tokens_held, itemsize)
    return work.least_time(flops, nbytes, peak)


def window_keys(p, window):
    """Keys the queries of a p-token prompt see under a window: sum over t
    of min(t + 1, window)."""
    if p <= window:
        return p * (p + 1) // 2
    return window * (window + 1) // 2 + (p - window) * window


def prefill_attention_flops(cfg, prompt_lengths):
    """(window layers', full layers') attention operations of prefills of
    these lengths: score and value products, 4 x heads x head_dim a pair."""
    s = _dims(cfg)
    full, win = layers_of(cfg)
    per_pair = 4 * s["hd"]
    windowed = win * per_pair * sum(window_keys(p, s["w"]) for p in prompt_lengths)
    causal = full * per_pair * sum(p * (p + 1) // 2 for p in prompt_lengths)
    return windowed, causal


def prefill_experts_touched_at_most(cfg, prompt_lengths):
    """The most (layer, held expert) pairs prefills of these lengths can touch:
    a prompt of p tokens has p x k picks and reaches at most the held experts."""
    s = _dims(cfg)
    return sum((s["n"] - s["n0"]) * min(p * s["k"], s["e"]) for p in prompt_lengths)


def prefill_least_time(cfg, prompt_lengths, experts_touched, peak, itemsize=2):
    """Least time to prefill prompts of the given lengths, one dispatch each:
    attention by kind (`prefill_attention_flops`), the head at one position,
    the layers' own weights read once a dispatch and the touched experts
    once, each prompt's K and V written once."""
    s = _dims(cfg)
    tokens = sum(prompt_lengths)
    full, win = layers_of(cfg)
    routed = (s["n"] - s["n0"]) * s["k"] * s["e"] / s["e_all"] * expert_params(cfg)
    flops = 2 * (step_own_params(cfg) - head_params(cfg) + routed) * tokens \
        + 2 * head_params(cfg) * len(prompt_lengths) + sum(prefill_attention_flops(cfg, prompt_lengths))
    nbytes = (len(prompt_lengths) * step_own_params(cfg) + experts_touched * expert_params(cfg)) * itemsize \
        + kv_bytes_per_key(cfg, itemsize) * (full * tokens + win * sum(min(p, s["w"]) for p in prompt_lengths))
    return work.least_time(flops, nbytes, peak)


def kernel_roofline(obs, kernel, least):
    """Reader of `paged_kernel_roofline.decode`: the least time of the
    kernel's runs in the traced window (`least`, the driver's) over the
    device time of the operations named `kernel` among the trace's largest
    operations.  None where the trace holds no such operation."""
    secs = sum(t for name, t in obs.get("trace.device_ops") or [] if kernel in name)
    floor = obs.get(least)
    if not secs or floor is None:
        return None
    return 100.0 * floor / secs


def flops_roofline(obs, kernel, flops):
    """Reader of `prefill_attention_roofline`: the attention operations the
    traced window's prefills needed (`flops`, the driver's) over what the
    chip's peak does in the device time of the operations named `kernel`
    among the trace's largest operations.  None where there is none."""
    secs = sum(t for name, t in obs.get("trace.device_ops") or [] if kernel in name)
    need, peak = obs.get(flops), obs.get("peak.flops_per_s")
    if not secs or not need or not peak:
        return None
    return 100.0 * need / (secs * peak)
