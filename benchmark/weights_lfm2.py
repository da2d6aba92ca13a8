"""The benchmark's own weights for LFM2-8B-A1B (``model_type: lfm2_moe``): one
jitted call, on the device, from the seed, **bfloat16 from creation** (the
configuration's 3.93 B parameters are 7.86 GB so). The tree is built here
from the configuration file's keys; the program is handed the finished tree
and has to accept it (the driver fails loudly if the program's own tree has
other names or shapes).

Matrices are normal with deviation fan_in^-0.5 (the depthwise convolution's
fan-in is its ``conv_L_cache`` taps); RMSNorm gains 1 + 0.1 N(0, 1) (gains
of exactly 1 would hide a gain the program forgot); the expert bias N(0,
``expert_bias_std``^2), the file's own key, so that it changes the chosen
set of some rows and not of all (``assumed`` (g)); the embedding normal with
deviation hidden^-0.5 — it is the head too, and a normed state times such
rows gives logits of unit size. No bias anywhere else."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EOS_ID = 1    # the ids the slot engine's beams treat specially (PAD 0,
START_ID = 2  # EOS 1, START 2): prompts draw from FIRST_ID up
FIRST_ID = 4
CONV = "conv"


def head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg: Dict) -> Dict:
    d, H, KV, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], head_dim(cfg))
    m, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    layers = []
    for i, kind in enumerate(cfg["layer_types"]):
        p = {"op_norm": (d,), "ffn_norm": (d,)}
        if kind == CONV:
            p.update(conv_in=(d, 3 * d), conv_w=(cfg["conv_L_cache"], d),
                     conv_out=(d, d))
        else:
            p.update(w_q=(d, H * hd), w_k=(d, KV * hd), w_v=(d, KV * hd),
                     w_o=(H * hd, d), q_norm=(hd,), k_norm=(hd,))
        if i < cfg["num_dense_layers"]:
            I = cfg["intermediate_size"]
            p.update(w_gate=(d, I), w_up=(d, I), w_down=(I, d))
        else:
            p.update(router=(d, E), expert_bias=(E,), experts_gate=(E, d, m),
                     experts_up=(E, d, m), experts_down=(E, m, d))
        layers.append(p)
    return {"embed": (cfg["vocab_size"], d), "layers": layers,
            "final_norm": (d,)}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple)


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=_is_leaf))


def make_params(cfg: Dict, seed: int, dtype=jnp.bfloat16):
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_leaf)

    def build(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name == "expert_bias":
                w = cfg["expert_bias_std"] * w
            elif len(shape) == 1:
                w = 1.0 + 0.1 * w
            elif name == "embed":
                w = w * (cfg["hidden_size"] ** -0.5)
            else:
                w = w * (shape[-2] ** -0.5)
            out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # a seed may exceed 32 signed bits: fold it into a 64-bit-safe key
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                             int(seed) // (2 ** 31))
    return jax.jit(build)(key)
