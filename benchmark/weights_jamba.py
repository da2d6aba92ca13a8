"""The benchmark's own weights for Jamba2-3B (``model_type: jamba``): one
jitted call, on the device, from the seed, **bfloat16 from creation** (the
configuration's 3,029.3 M parameters are 6.06 GB so). The tree is built here
from the configuration file's keys; the program is handed the finished tree
and has to accept it (the driver fails loudly if the program's own tree has
other names or shapes).

As the family initialises them, so that the recurrence neither forgets at
once nor never: ``a_log`` = log(1..d_state) a channel (``A = -exp(a_log)`` =
-1..-16), ``d_skip`` = 1, ``b_dt`` the inverse softplus of a step drawn
log-uniform on [0.001, 0.1]. Matrices normal with deviation fan_in^-0.5 (the
depthwise convolution's fan-in is its ``mamba_d_conv`` taps); the
convolution's bias 0.1 N(0, 1); RMSNorm gains 1 + 0.1 N(0, 1) (gains of
exactly 1 would hide a gain the program forgot); embedding rows normal with
deviation hidden^-0.5 — the matrix is the head too (``tie_word_embeddings``),
and a normed state times such rows gives logits of unit size. Mamba leaves
lie with d_inner LAST (``conv_w`` (taps, d_inner), ``a_log`` (d_state,
d_inner)): the transposes of the published tensors, which with weights from
a seed is the same model."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EOS_ID = 1    # the ids the slot engine's beams treat specially (PAD 0,
START_ID = 2  # EOS 1, START 2): prompts draw from FIRST_ID up
FIRST_ID = 4
DT_MIN, DT_MAX = 1e-3, 1e-1


def head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg: Dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def is_attention(cfg: Dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def param_shapes(cfg: Dict) -> Dict:
    d, di, N, R = (cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"],
                   cfg["mamba_dt_rank"])
    H, KV, hd, I = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    head_dim(cfg), cfg["intermediate_size"])
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        p = {"mixer_norm": (d,), "mlp_norm": (d,), "w_gate": (d, I),
             "w_up": (d, I), "w_down": (I, d)}
        if is_attention(cfg, i):
            p.update(w_q=(d, H * hd), w_k=(d, KV * hd), w_v=(d, KV * hd),
                     w_o=(H * hd, d))
        else:
            p.update(w_in=(d, 2 * di), conv_w=(cfg["mamba_d_conv"], di),
                     conv_b=(di,), w_x=(di, R + 2 * N), dt_norm=(R,),
                     b_norm=(N,), c_norm=(N,), w_dt=(R, di), b_dt=(di,),
                     a_log=(N, di), d_skip=(di,), w_out=(di, d))
        layers.append(p)
    return {"embed": (cfg["vocab_size"], d), "layers": layers,
            "final_norm": (d,)}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple)


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=_is_leaf))


def _inverse_softplus_of_a_step(key, shape):
    """``b_dt``: softplus^-1 of a step drawn log-uniform on [DT_MIN,
    DT_MAX]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
    return dt + jnp.log(-jnp.expm1(-dt))


def _leaf(name: str, shape, key, hidden: int):
    if name == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "b_dt":
        return _inverse_softplus_of_a_step(key, shape)
    w = jax.random.normal(key, shape, jnp.float32)
    if name == "conv_b":
        return 0.1 * w
    if len(shape) == 1:
        return 1.0 + 0.1 * w
    if name == "embed":
        return w * (hidden ** -0.5)
    return w * (shape[-2] ** -0.5)


def make_params(cfg: Dict, seed: int, dtype=jnp.bfloat16):
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_leaf)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(path[-1].key, shape, jax.random.fold_in(key, i),
                  cfg["hidden_size"]).astype(dtype)
            for i, (path, shape) in enumerate(paths)])

    # a seed may exceed 32 signed bits: fold it into a 64-bit-safe key
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                             int(seed) // (2 ** 31))
    return jax.jit(build)(key)
