"""The work a Falcon-H1 decoder (`refs/falcon_h1_decoder.py`) needs, counted
from shapes and from what a step held, beside `work.py` (whose peaks and
`least_time` it uses).  Nothing here looks at the program.

Every layer is grouped-query attention beside a Mamba-2 mixer, then a
SwiGLU.  A decode step multiplies every live token with the layers' weights
and the head, which it has to read once a step; reads the K and V of the
tokens the live sequences hold; and reads AND writes every live sequence's
mixer state, 4 MB of float32 a layer at Falcon-H1-34B's widths whatever the
length, and its convolution tail.  The state's traffic alone is the least
time of the step kernel (`tfs_ssm_step`), which moves the state and nothing
else of size.
"""

from perfbench import work
from perfbench.refs import falcon_h1_decoder
from perfbench.work_brumby import kernel_roofline  # noqa: F401 — the reader of `ssm_step_roofline`


def _dims(cfg):
    return falcon_h1_decoder.dims(cfg)


def attention_params(cfg):
    s = _dims(cfg)
    return s["d"] * (s["hd"] + 2 * s["kd"]) + s["hd"] * s["d"]


def mixer_params(cfg):
    """in_proj, the convolution's weights and bias, dt_bias, A_log, D, the
    gated norm's gain and out_proj."""
    s = _dims(cfg)
    return (s["d"] * s["proj"] + (s["mk"] + 1) * s["conv"] + 3 * s["mh"] + s["ssm"]
            + s["ssm"] * s["d"])


def mlp_params(cfg):
    s = _dims(cfg)
    return 3 * s["d"] * s["f"]


def layer_params(cfg):
    """A layer whole: attention, the mixer, the SwiGLU and the two norms."""
    return attention_params(cfg) + mixer_params(cfg) + mlp_params(cfg) + 2 * _dims(cfg)["d"]


def layer_matmul_params(cfg):
    """What every token is multiplied with in a layer."""
    s = _dims(cfg)
    return attention_params(cfg) + s["d"] * s["proj"] + s["ssm"] * s["d"] + mlp_params(cfg)


def head_params(cfg):
    """The head, read once a step (the embedding, as large, is a row gather)."""
    s = _dims(cfg)
    return s["d"] * s["v"]


def params(cfg):
    """Every parameter this chip holds."""
    s = _dims(cfg)
    return s["n"] * layer_params(cfg) + 2 * head_params(cfg) + s["d"]


def ssm_state_bytes_per_slot_layer(cfg):
    """One sequence's SSM state in one layer, float32: a head's P x N."""
    s = _dims(cfg)
    return 4 * s["mh"] * s["mp"] * s["mn"]


def tail_bytes_per_slot_layer(cfg, itemsize=2):
    """One sequence's convolution tail in one layer: the last d_conv - 1
    inputs of x, B and C."""
    s = _dims(cfg)
    return itemsize * (s["mk"] - 1) * s["conv"]


def kv_bytes_per_token(cfg, itemsize=2):
    s = _dims(cfg)
    return s["n"] * 2 * s["kd"] * itemsize


def kernel_least_time(cfg, slot_steps, peak):
    """Least time of the step kernel's runs in all layers over steps that
    held `slot_steps` sequences, summed over the steps: the SSM state read
    once and written once."""
    s = _dims(cfg)
    return 2 * s["n"] * ssm_state_bytes_per_slot_layer(cfg) * slot_steps / peak["bytes_per_s"]


def state_bytes(cfg, slot_steps):
    """Bytes of state that steps which held `slot_steps` sequences, summed
    over the steps, have to move: every layer's SSM state and tail, read
    once and written once."""
    s = _dims(cfg)
    per = ssm_state_bytes_per_slot_layer(cfg) + tail_bytes_per_slot_layer(cfg)
    return 2 * s["n"] * per * slot_steps


def step_bytes(cfg, steps, slot_steps, tokens_held, itemsize=2):
    """Bytes `steps` decode steps have to move: the weights once a step, the
    state of the sequences held twice, the K and V of the tokens held."""
    s = _dims(cfg)
    return (steps * (s["n"] * layer_params(cfg) + head_params(cfg)) * itemsize
            + state_bytes(cfg, slot_steps) + kv_bytes_per_token(cfg, itemsize) * tokens_held)


def token_flops(cfg):
    """Multiply-adds x 2 a token needs in the layers apart from attention
    over its context: the matrices, and the state's update and read-out
    (2 P N a head each)."""
    s = _dims(cfg)
    return s["n"] * (2 * layer_matmul_params(cfg) + 4 * s["mh"] * s["mp"] * s["mn"])


def decode_least_time(cfg, steps, slot_steps, tokens_held, peak, itemsize=2):
    """Least time for `steps` decode steps that held `slot_steps` sequences
    and `tokens_held` positions, each summed over the steps (a live
    sequence emits a token a step)."""
    s = _dims(cfg)
    flops = ((token_flops(cfg) + 2 * head_params(cfg)) * slot_steps
             + 4 * s["n"] * s["hd"] * tokens_held)
    return work.least_time(flops, step_bytes(cfg, steps, slot_steps, tokens_held, itemsize), peak)


def prefill_least_time(cfg, dispatches, prompt_lengths, peak, itemsize=2):
    """Least time to prefill prompts of the given lengths in `dispatches`
    dispatches: every token through the layers (the state's part in SSD's
    chunked form, counted as the recurrence's), causal attention over each
    prompt, the head at a prompt's last position, the weights read once a
    dispatch and each prompt's K and V and state written once."""
    s = _dims(cfg)
    tokens = sum(prompt_lengths)
    pairs = sum(p * (p + 1) // 2 for p in prompt_lengths)
    flops = (token_flops(cfg) * tokens + 2 * head_params(cfg) * len(prompt_lengths)
             + 4 * s["n"] * s["hd"] * pairs)
    per_slot = s["n"] * (ssm_state_bytes_per_slot_layer(cfg) + tail_bytes_per_slot_layer(cfg))
    nbytes = (dispatches * (s["n"] * layer_params(cfg) + head_params(cfg)) * itemsize
              + kv_bytes_per_token(cfg, itemsize) * tokens + per_slot * len(prompt_lengths))
    return work.least_time(flops, nbytes, peak)
