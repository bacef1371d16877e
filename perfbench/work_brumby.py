"""The work a Brumby decoder (`refs/brumby_decoder.py`) needs, counted from
shapes and from how many sequences a step held, beside `work.py` (whose peaks
and `least_time` it uses).  Nothing here looks at the program.

A sequence's whole past is a float32 state of fixed size: per KV head the
symmetric square of a key, D = dh (dh + 1) / 2 entries (8,256 at heads of
128), by dh values, and D more for the normaliser.  A decode step multiplies
every token with the layers' weights and the head, which it has to read once a
step, and has to read AND write the state of every sequence it holds, whatever
their lengths: that is most of a step's bytes, and the least time of the
step's kernel alone (`tfs_retention_step`).  Work is counted on the D entries
of the symmetric form, not on the places a layout stores them on.
"""

from perfbench import work
from perfbench.refs import brumby_decoder


def phi_dim(cfg):
    """D: the entries of the symmetric square of a head's vector."""
    s = brumby_decoder.dims(cfg)
    return s["dh"] * (s["dh"] + 1) // 2


def layer_matmul_params(cfg):
    """What every token is multiplied with in a layer: q, k, v, o, the gate
    and the SwiGLU's three."""
    s = brumby_decoder.dims(cfg)
    return s["d"] * (s["hd"] + 2 * s["kd"]) + s["hd"] * s["d"] + s["d"] * s["kvh"] + 3 * s["d"] * s["f"]


def layer_params(cfg):
    """A layer whole: with the gate's bias and the gains of its four norms."""
    s = brumby_decoder.dims(cfg)
    return layer_matmul_params(cfg) + s["kvh"] + 2 * s["d"] + 2 * s["dh"]


def head_params(cfg):
    """The head, read once a step (the embedding, as large, is a row gather)."""
    s = brumby_decoder.dims(cfg)
    return s["d"] * s["v"]


def params(cfg):
    """Every parameter this chip holds."""
    s = brumby_decoder.dims(cfg)
    return s["n"] * layer_params(cfg) + 2 * head_params(cfg) + s["d"]


def state_bytes_per_slot_layer(cfg):
    """One sequence's state in one layer, float32: S and the normaliser."""
    s = brumby_decoder.dims(cfg)
    return 4 * s["kvh"] * phi_dim(cfg) * (s["dh"] + 1)


def retention_flops_per_token_layer(cfg):
    """A token's retention in one layer: its key into the state and its
    queries out of it, 2 x D x dh a head either way (the normaliser apart)."""
    s = brumby_decoder.dims(cfg)
    return 2 * (s["kvh"] + s["h"]) * phi_dim(cfg) * s["dh"]


def token_flops(cfg):
    """Multiply-adds x 2 a token needs in the layers (the head is apart)."""
    s = brumby_decoder.dims(cfg)
    return s["n"] * (2 * layer_matmul_params(cfg) + retention_flops_per_token_layer(cfg))


def kernel_least_time(cfg, slot_steps, peak):
    """Least time of the step kernel's runs in all layers over steps that
    held `slot_steps` sequences, summed over the steps: the state read once
    and written once."""
    return state_bytes(cfg, slot_steps) / peak["bytes_per_s"]


def state_bytes(cfg, slot_steps):
    """Bytes of state that steps which held `slot_steps` sequences, summed
    over the steps, have to move: every layer's, read once and written once."""
    s = brumby_decoder.dims(cfg)
    return 2 * s["n"] * state_bytes_per_slot_layer(cfg) * slot_steps


def step_bytes(cfg, steps, slot_steps, itemsize=2):
    """Bytes `steps` decode steps have to move: the weights once a step, the
    state of the sequences held twice."""
    s = brumby_decoder.dims(cfg)
    return (steps * (s["n"] * layer_matmul_params(cfg) + head_params(cfg)) * itemsize
            + state_bytes(cfg, slot_steps))


def decode_least_time(cfg, steps, slot_steps, peak, itemsize=2):
    """Least time for `steps` decode steps that held `slot_steps` sequences,
    summed over the steps (each emits a token a step)."""
    flops = (token_flops(cfg) + 2 * head_params(cfg)) * slot_steps
    return work.least_time(flops, step_bytes(cfg, steps, slot_steps, itemsize), peak)


def prefill_least_time(cfg, dispatches, prompt_lengths, peak, itemsize=2):
    """Least time to prefill prompts of the given lengths in `dispatches`
    dispatches: every token through the layers, retention's part in its
    chunked form (each token read out of a state and added to one), the head
    at a prompt's last position, the weights read once a dispatch and the
    slot's state read and written once."""
    s = brumby_decoder.dims(cfg)
    tokens = sum(prompt_lengths)
    flops = token_flops(cfg) * tokens + 2 * head_params(cfg) * len(prompt_lengths)
    nbytes = dispatches * ((s["n"] * layer_matmul_params(cfg) + head_params(cfg)) * itemsize
                           + 2 * s["n"] * state_bytes_per_slot_layer(cfg))
    return work.least_time(flops, nbytes, peak)


def kernel_roofline(obs, kernel, least):
    """Reader of `retention_step_roofline`: the least time of the kernel's
    runs in the traced window (`least`, the driver's, from
    `kernel_least_time`) over the device time of the operations named
    `kernel` in the trace's largest operations.  None where the trace holds
    no such operation."""
    secs = sum(t for name, t in obs.get("trace.device_ops") or [] if kernel in name)
    floor = obs.get(least)
    if not secs or floor is None:
        return None
    return 100.0 * floor / secs
