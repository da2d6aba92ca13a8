"""The benchmark's own weights for A.X-K1: one jitted call, on the device,
from the seed, **bfloat16 from creation** (the configuration's 4.8 B
parameters are 9.68 GB so; a float32 copy is 19.4 GB and cannot exist on the
chip). The tree is built here from the configuration file's keys; the
program is handed the finished tree and has to accept it (the driver fails
loudly if the program's own tree has other names or shapes).

Matrices are normal with deviation fan_in^-0.5, RMSNorm gains 1 + 0.1 N(0,1)
(gains of exactly 1 would hide a gain the program forgot), the embedding
normal with deviation 1. There is no bias anywhere, the head included: no
EOS bias is added (benchmark/traffic/drain-diffs.json says why none is
needed)."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EOS_ID = 1    # the ids the slot engine's beams treat specially (PAD 0,
START_ID = 2  # EOS 1, START 2): prompts draw from FIRST_ID up
FIRST_ID = 4


def router_width(cfg: Dict) -> int:
    """The router's outputs: the published expert count, whatever is held."""
    return int(cfg["published"]["n_routed_experts"])


def param_shapes(cfg: Dict) -> Dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    m, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        p = {"attn_norm": (d,), "w_dq": (d, rq), "q_norm": (rq,),
             "w_uq": (rq, H * (dn + dr)), "w_dkv": (d, r + dr),
             "kv_norm": (r,), "w_ukv": (r, H * (dn + dv)),
             "w_o": (H * dv, d), "mlp_norm": (d,)}
        if i < cfg["first_k_dense_replace"]:
            I = cfg["intermediate_size"]
            p.update(w_gate=(d, I), w_up=(d, I), w_down=(I, d))
        else:
            ms = m * cfg["n_shared_experts"]
            p.update(router=(d, router_width(cfg)), shared_gate=(d, ms),
                     shared_up=(d, ms), shared_down=(ms, d),
                     experts_gate=(E, d, m), experts_up=(E, d, m),
                     experts_down=(E, m, d))
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
            k = jax.random.fold_in(key, i)
            w = jax.random.normal(k, shape, jnp.float32)
            if len(shape) == 1:
                w = 1.0 + 0.1 * w
            elif jax.tree_util.keystr(path) != "['embed']":
                w = w * (shape[-2] ** -0.5)
            out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # a seed may exceed 32 signed bits: fold it into a 64-bit-safe key
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                             int(seed) // (2 ** 31))
    return jax.jit(build)(key)
