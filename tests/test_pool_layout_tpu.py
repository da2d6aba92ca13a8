"""FIRA's paged pools are stored in the layout the engine's step computes in
(decode/slot_model.FiraSlotModel.leaves; docs/DECODE_ENGINE.md "Paged KV
arena").

A pool is (L*P, G, H*d_head): a block a (layer, pool block), a row a (beam
lane, position), a position's heads side by side, G = K*BS rounded up to
whole sublane tiles. Row-major pads nothing, so the runtime lays the pool
out row-major, and the step's scan appends rows and gathers whole blocks in
that layout: the donated arena aliases the scan's carry as it is. A pool
stored with its heads apart, (L, P, K, H, BS, d_head), has minor dims (BS,
d_head) that pad badly in the chip's (8, 128) tiles: the runtime put P
minor, the scan computed with the heads minor, and the step converted both
pools in at its entry and back at its exit, four whole-pool copies a
dispatch. A pool of single rows, (L*P*K*BS, H*d_head), needs no copy
either, but its gather moves a row at a time, a sublane of four tiles, and
the step ran slower than with the copies.

Pinned here by compiling the engine's real step program for a DESCRIBED TPU
v5e from ``jax.ShapeDtypeStruct``s (nothing is allocated, nothing runs), at
fira-tiny's depth and vocabulary and each FIRA decode cell's width, beam,
position budget and slots, and at fira-tiny's own: no ``copy`` in the optimized program has a
pool's element count, and every gather from a pool takes whole blocks.

The same described chip pins the grouped expert products' tiling
(model/axk1.routed_experts): LFM2-8B-A1B's expert layer at its published
widths (top-4 of 32 experts, 2,048 -> 1,792) compiled at a decode position's
192 rows holds no ``ragged-dot`` (expert-major: batched products that read
each expert's matrices once), and at a prefill dispatch's 16,384 rows keeps
it (row-major) as one branch of a ``conditional`` whose other is
expert-major at a prefill capacity of 1,024 rows an expert (the loads
choose on the device). Skipped where the TPU compiler cannot be loaded.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fira_tpu.config import fira_tiny, get_config
from fira_tpu.data.batching import make_batch
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.model import lfm2
from fira_tpu.model.model import FiraModel

# fira-tiny's depth and vocabulary, at each decode cell's width, heads,
# beam, position budget and slots (benchmark/configs, benchmark/traffic),
# and fira-tiny as it is (d 64: half a lane row a position)
CELLS = {
    "fira-tiny": dict(),
    "fira-large.drain": dict(embedding_dim=512, num_head=8, beam_size=8,
                             tar_len=30, engine_slots=85),
    "fira-full.serve": dict(embedding_dim=256, num_head=8, beam_size=3,
                            tar_len=30, engine_slots=550),
}

# an op's result: name, element type, dims, layout, opcode
_OP = re.compile(r"%(\S+) = \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w-]+)\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


_COMPILED = {}


def _compiled_step(one_chip, **geometry):
    """The step program of a fira-tiny engine at ``geometry``, compiled
    for one described v5e -> (optimized HLO text, pool shape); once a
    geometry."""
    key = tuple(sorted(geometry.items()))
    if key not in _COMPILED:
        _COMPILED[key] = _compile_step(one_chip, **geometry)
    return _COMPILED[key]


def _compile_step(one_chip, **geometry):
    cfg = fira_tiny(decode_engine=True, test_batch_size=4,
                    engine_harvest_every=4, **geometry)
    cfg, split, _vocab = make_memory_split(cfg, 8, seed=3)
    model = FiraModel(cfg)
    wire = {k: v for k, v in make_batch(
        split, np.arange(0), cfg, batch_size=cfg.test_batch_size).items()
        if not k.startswith("_")}
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {k: v[:1] for k, v in wire.items()},
        deterministic=True))["params"]
    eng = SlotEngine(model, params, cfg)
    chunk = jax.eval_shape(eng._prefill_fn, params, wire)
    arena = eng.arena_shapes(chunk)
    text = eng._step.lower(_placed(params, one_chip),
                           _placed(arena, one_chip)).compile().as_text()
    return text, arena["k_pool"].shape


def _pool_copies(text: str, pool: int):
    """Layout copies in the program (``copy`` ops, and fusions XLA names
    after one) whose result has ``pool`` elements. ``copy-start`` /
    ``copy-done`` pairs are not among them: they move an array between
    memory spaces in its own layout (at fira-tiny's sizes the compiler
    prefetches whole pools into fast memory that way)."""
    hits = []
    for name, dims, opcode in _OP.findall(text):
        n = int(np.prod([int(d) for d in dims.split(",") if d]))
        if n == pool and (opcode == "copy" or (
                opcode == "fusion" and name.startswith("copy"))):
            hits.append(name)
    return hits


def _block_gathers(text: str, rows: int, width: int) -> int:
    """Gathers in the program that take slices of ``rows`` x ``width``:
    one whole block of a pool each."""
    return len(re.findall(r" gather\([^\n]*slice_sizes=\{1,%d,%d\}"
                          % (rows, width), text))


@pytest.mark.parametrize("geometry", CELLS.values(), ids=CELLS.keys())
def test_step_holds_no_whole_pool_copy(one_chip, geometry):
    text, pool = _compiled_step(one_chip, **geometry)
    assert "while" in text                      # the 4-position scan
    assert _pool_copies(text, int(np.prod(pool))) == []


@pytest.mark.parametrize("geometry", CELLS.values(), ids=CELLS.keys())
def test_step_gathers_whole_blocks_of_the_pool(one_chip, geometry):
    """Each layer reads K and V of every slot's blocks: slices of one
    block's G rows x H*d_head, whole (8, 128) tiles, never a row alone."""
    text, (_LP, G, HD) = _compiled_step(one_chip, **geometry)
    assert _block_gathers(text, G, HD) == 2 * fira_tiny().num_layers


def test_the_count_sees_a_pool_copy(one_chip):
    """The detector counts what it guards against: compiled for the same
    chip, a program that lays a pool-sized operand out anew (a transpose)
    holds one copy of that size."""
    x = jax.ShapeDtypeStruct((256, 512), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda a: a.T * 2).lower(x).compile().as_text()
    assert len(_pool_copies(text, x.size)) == 1


def _compiled_expert_layer(one_chip, rows: int) -> str:
    """LFM2-8B-A1B's first expert layer (router, bias, 32 experts) over
    ``rows`` normed rows in bfloat16, compiled for one described v5e ->
    the optimized HLO text."""
    lm = get_config("lfm2-8b-a1b-l12").lm
    layer = lm.num_dense_layers
    p = {k: jax.ShapeDtypeStruct(v, jnp.bfloat16, sharding=one_chip)
         for k, v in lfm2.param_shapes(lm)["layers"][layer].items()}
    x = jax.ShapeDtypeStruct((rows, lm.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    valid = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    return jax.jit(lambda p, x, v: lfm2.moe_layer(
        p, x, v, lm, jnp.bfloat16)).lower(p, x, valid).compile().as_text()


def test_decode_expert_products_are_expert_major(one_chip):
    """192 rows (64 slots x 3 beams) x top-4 of 32: ~24 rows an expert.
    No ragged-dot; gate and up come out (experts, capacity 64, 1,792)."""
    text = _compiled_expert_layer(one_chip, 192)
    assert "ragged-dot" not in text
    assert re.search(r"bf16\[32,64,1792\]", text)      # (E, C, m): gate/up


def test_prefill_expert_products_keep_ragged_dot(one_chip):
    """16,384 rows: 1,024 expected rows an expert. Row-major passes stay,
    beside expert-major passes of 1,024 rows an expert, (experts, 1,024,
    1,792) gate and up; a ``conditional`` on the loads picks one."""
    text = _compiled_expert_layer(one_chip, 16384)
    assert "ragged-dot" in text
    assert re.search(r"bf16\[32,1024,1792\]", text)    # (E, C_p, m)
    assert "conditional" in text
