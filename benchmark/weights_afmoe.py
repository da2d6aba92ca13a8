"""The benchmark's own weights for Trinity-Mini (``model_type: afmoe``): one
jitted call, on the device, from the seed, **bfloat16 from creation** (the
configuration's 4.24 B parameters are 8.48 GB so). The tree is built here
from the configuration file's keys; the program is handed the finished tree
and has to accept it (the driver fails loudly if the program's own tree has
other names or shapes).

Matrices are normal with deviation fan_in^-0.5; RMSNorm gains 1 + 0.1 N(0,1)
(gains of exactly 1 would hide a gain the program forgot); the router's
selection bias N(0, 0.02^2), so that it moves some picks and never all of
them; the embedding normal with deviation hidden^-0.5, so that after the
``mup_enabled`` scaling by sqrt(hidden) the residual stream starts at unit
size (a deviation of 1 would start it at 45, and nothing the layers add
would survive bfloat16 beside it). No bias anywhere else, the head
included."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EOS_ID = 1    # the ids the slot engine's beams treat specially (PAD 0,
START_ID = 2  # EOS 1, START 2): prompts draw from FIRST_ID up
FIRST_ID = 4
ROUTER_BIAS_STD = 0.02


def experts_held(cfg: Dict) -> int:
    """Experts whose weights are here (all of ``num_experts`` unless the
    file says it holds a share)."""
    return int(cfg.get("experts_held", cfg["num_experts"]))


def param_shapes(cfg: Dict) -> Dict:
    d, H, KV, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    m, E = cfg["moe_intermediate_size"], experts_held(cfg)
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        p = {"attn_norm": (d,), "post_attn_norm": (d,), "mlp_norm": (d,),
             "post_mlp_norm": (d,), "w_q": (d, H * hd), "w_k": (d, KV * hd),
             "w_v": (d, KV * hd), "w_g": (d, H * hd), "w_o": (H * hd, d),
             "q_norm": (hd,), "k_norm": (hd,)}
        if i < cfg["num_dense_layers"]:
            I = cfg["intermediate_size"]
            p.update(w_gate=(d, I), w_up=(d, I), w_down=(I, d))
        else:
            ms = m * cfg["num_shared_experts"]
            p.update(router=(d, cfg["num_experts"]),
                     router_bias=(cfg["num_experts"],),
                     shared_gate=(d, ms), shared_up=(d, ms),
                     shared_down=(ms, d), experts_gate=(E, d, m),
                     experts_up=(E, d, m), experts_down=(E, m, d))
        layers.append(p)
    return {"embed": (cfg["vocab_size"], d), "layers": layers,
            "final_norm": (d,), "head": (d, cfg["vocab_size"])}


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
            if name == "router_bias":
                w = ROUTER_BIAS_STD * w
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
