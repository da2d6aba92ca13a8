"""The benchmark's own weights for Brumby-14B-Base (``model_type: brumby``):
one jitted call, on the device, from the seed, **bfloat16 from creation**
(the configuration's 2,877.2 M parameters are 5.75 GB so). The tree is built
here from the configuration file's keys; the program is handed the finished
tree and has to accept it (``drivers/drain_tokens.py`` fails loudly if the
program's own tree has other names or shapes).

Drawn so that a 16,384-token prompt is neither forgotten at once nor never:
the retention gates' biases ``b_g`` put ``1 - sigmoid(b_g)`` from 1/64 to
1/8,192, geometric over the key/value heads (half-lives of ~44 to ~5,700
tokens); the gates' projection ``w_g`` is a matrix like any other, so a
token moves its gate's logit by about one. Matrices, the untied head among
them, normal with deviation fan_in^-0.5; RMSNorm gains 1 + 0.1 N(0, 1)
(gains of exactly 1 would hide a gain the program forgot); embedding rows
normal with deviation hidden^-0.5. The head lies (hidden, vocab): the
transpose of the published tensor, which with weights from a seed is the
same model."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EOS_ID = 1    # the ids the slot engine's beams treat specially (PAD 0,
START_ID = 2  # EOS 1, START 2): prompts draw from FIRST_ID up
FIRST_ID = 4


def param_shapes(cfg: Dict) -> Dict:
    d, H, KV, hd, I = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    layer = {"attn_norm": (d,), "w_q": (d, H * hd), "w_k": (d, KV * hd),
             "w_v": (d, KV * hd), "w_o": (H * hd, d), "q_norm": (hd,),
             "k_norm": (hd,), "w_ret_gate": (d, KV), "b_ret_gate": (KV,),
             "mlp_norm": (d,), "w_gate": (d, I), "w_up": (d, I),
             "w_down": (I, d)}
    return {"embed": (cfg["vocab_size"], d),
            "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])],
            "final_norm": (d,), "head": (d, cfg["vocab_size"])}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple)


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=_is_leaf))


def gate_bias(heads: int) -> np.ndarray:
    e = 2.0 ** -np.linspace(6.0, 13.0, heads)
    return np.log((1.0 - e) / e).astype(np.float32)


def _leaf(name: str, shape, key, hidden: int):
    if name == "b_ret_gate":
        return jnp.asarray(gate_bias(shape[0]))
    w = jax.random.normal(key, shape, jnp.float32)
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
