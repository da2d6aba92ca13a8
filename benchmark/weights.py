"""The benchmark's own weights: one jitted call, on the device, from the seed.

The tree is built here from the configuration's sizes, under the checkpoint
names of the paper's modules (``encoder/gcn_3/fc1/kernel`` ...): nothing is
taken from the program, which is handed the finished tree and has to accept
it (``run.py`` fails loudly if the program's own parameter tree has another
structure). Distributions follow PyTorch's defaults, as the reference code
does: Linear weights and biases U(+-1/sqrt(fan_in)), embeddings N(0, 1),
LayerNorm (1, 0).

``eos_bias`` adds a constant to the generation head's EOS logit: with random
weights no beam would ever emit EOS and every message would cost the full
``tar_len - 1`` positions; the bias gives served messages a mixed length. It
is a parameter of the traffic mix.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EOS_ID = 1  # <eos> in the corpus vocabulary (PAD 0, EOS 1, START 2, UNK 3)


def _linear(i: int, o: int, bias: bool = True) -> Dict:
    out = {"kernel": ("uniform", (i, o), i)}
    if bias:
        out["bias"] = ("uniform", (o,), i)
    return out


def _norm(d: int) -> Dict:
    return {"scale": ("ones", (d,), 0), "bias": ("zeros", (d,), 0)}


def _embed(n: int, d: int) -> Dict:
    return {"embedding": ("normal", (n, d), 0)}


def _attn(d: int) -> Dict:
    return {"q_proj": _linear(d, d), "k_proj": _linear(d, d),
            "v_proj": _linear(d, d), "out_proj": _linear(d, d),
            "norm": _norm(d)}


def param_spec(cfg: Dict) -> Dict:
    """Nested dict of (distribution, shape, fan_in) leaves."""
    d, L, V = cfg["embedding_dim"], cfg["num_layers"], cfg["vocab_size"]
    f = cfg.get("ffn_mult", 4)
    enc = {"word_embed": _embed(V, d), "mark_embed": _embed(4, d),
           "ast_change_embed": _embed(cfg["ast_change_vocab_size"], d)}
    dec = {"embed": _embed(V, d)}
    for i in range(L):
        enc[f"combination_{i}"] = _attn(d)
        enc[f"gcn_{i}"] = {"fc1": _linear(d, d), "fc2": _linear(d, d),
                           "norm": _norm(d)}
        dec[f"self_attn_{i}"] = _attn(d)
        dec[f"cross_attn_{i}"] = _attn(d)
        dec[f"ffn_{i}"] = {"fc1": _linear(d, f * d), "fc2": _linear(f * d, d),
                           "norm": _norm(d)}
    copy = {"src_proj": _linear(d, d, bias=False),
            "tgt_proj": _linear(d, d, bias=False),
            "score": _linear(d, 1), "gate": _linear(d, 2)}
    return {"encoder": enc, "decoder": dec, "copy_net": copy,
            "out_fc": _linear(d, V)}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple)


def param_shapes(cfg: Dict) -> Dict:
    return jax.tree_util.tree_map(lambda s: tuple(s[1]), param_spec(cfg),
                                  is_leaf=_is_leaf)


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s[1])) for s in jax.tree_util.tree_leaves(
        param_spec(cfg), is_leaf=_is_leaf))


def make_params(cfg: Dict, seed: int, eos_bias: float = 0.0):
    """All leaves in one jitted program, float32, on the default device."""
    spec = param_spec(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)

    def build(key):
        out = []
        for i, (dist, shape, fan_in) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if dist == "normal":
                out.append(jax.random.normal(k, shape, jnp.float32))
            elif dist == "uniform":
                b = 1.0 / np.sqrt(fan_in)
                out.append(jax.random.uniform(k, shape, jnp.float32, -b, b))
            elif dist == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(jnp.zeros(shape, jnp.float32))
        params = jax.tree_util.tree_unflatten(treedef, out)
        if eos_bias:
            params["out_fc"]["bias"] = params["out_fc"]["bias"].at[
                EOS_ID].add(jnp.float32(eos_bias))
        return params

    # a seed may exceed 32 signed bits: fold it into a 64-bit-safe key
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                             int(seed) // (2 ** 31))
    return jax.jit(build)(key)
