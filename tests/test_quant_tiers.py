"""Low-precision serving tiers (decode/quant.py).

Pins the tier contract (docs/DECODE_ENGINE.md "Low-precision tiers"):

- per-channel symmetric int8: quantize -> dequantize error bounded by
  scale/2 per element, zero columns exact;
- parse-time validation: named-knob messages, CLI exit 2, engine path
  required, training path rejects armed tiers outright;
- program labels carry the tier suffix; the f32/f32 default leaves the
  label set, digests, and output bytes untouched;
- prefix-cache digests carry the tier namespace: a cached f32 artifact
  can never seat a bf16 slot (a tier change is a MISS, never a wrong
  answer);
- ``kv_bytes_per_slot`` derives from the arena's ACTUAL dtype (stats
  stamp ``kv_dtype``/``serve_precision``), halving under the bf16 arena;
- within a tier, output bytes stay a pure function of the stream —
  repeat runs, slot count, harvest cadence, replica count — and
  a fleet respawn re-quantizes by construction.

Engine-driving legs are slow-marked per the PR-15 rig note (tier-1 wall
budget); check.sh's quant smoke enforces the serve-path tier contract
(per-tier byte-stability + measured BLEU bound + zero retraces) on every
CI run.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fira_tpu.config import fira_tiny
from fira_tpu.decode import paging
from fira_tpu.decode import quant
from fira_tpu.decode.prefix_cache import payload_digests


@pytest.fixture(scope="module")
def corpus_dir(tmp_path_factory):
    from fira_tpu.data.synthetic import write_corpus_dir

    d = str(tmp_path_factory.mktemp("quant_corpus"))
    write_corpus_dir(d, n_commits=12, seed=13)
    return d


@pytest.fixture(scope="module")
def engine_setup(tmp_path_factory):
    """Corpus + tiny params for the slow engine-driving legs."""
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.decode.beam import eos_biased_params
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train.state import init_state

    d = str(tmp_path_factory.mktemp("quant_engine_corpus"))
    write_corpus_dir(d, n_commits=24, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=6)
    dataset = FiraDataset(d, cfg)
    cfg = dataset.cfg
    batch = make_batch(dataset.splits["train"], np.arange(6), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    return cfg, dataset, d, eos_biased_params(params, delta=4.0)


def _engine_outputs(params, cfg, dataset):
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.decode.runner import _decode_tasks
    from fira_tpu.model.model import FiraModel

    eng = engine_lib.SlotEngine(FiraModel(cfg), params, cfg)
    tasks, _ = _decode_tasks(dataset.splits["train"], cfg)
    out = {}
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for it in eng.run(feed):
            out[it.position] = (np.asarray(it.tokens), np.asarray(it.probs))
    return out, eng


# --------------------------------------------------------------------------
# int8 quantizer units
# --------------------------------------------------------------------------

def test_quantize_int8_roundtrip_bound():
    """|w - dq(q(w))| <= scale/2 per element: symmetric scaling means the
    clip never binds, so rounding's half-step is the whole error."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((7, 5, 16)).astype(np.float32) * 3.0
    q, scale = quant.quantize_int8(w)
    assert q.dtype == np.int8 and scale.shape == (16,)
    assert int(np.max(np.abs(q))) <= 127
    back = np.asarray(quant.dequantize_int8(jnp.asarray(q),
                                            jnp.asarray(scale)))
    assert np.all(np.abs(w - back) <= scale / 2 + 1e-9)
    # extreme columns hit the endpoints exactly
    col_max = np.max(np.abs(w), axis=(0, 1))
    hit = np.abs(w) == col_max
    np.testing.assert_allclose(np.abs(back)[hit], np.abs(w)[hit], rtol=1e-6)


def test_quantize_int8_zero_column_exact():
    w = np.zeros((4, 3), np.float32)
    w[:, 1] = np.linspace(-2, 2, 4)
    q, scale = quant.quantize_int8(w)
    assert scale[0] == 1.0 and scale[2] == 1.0  # sentinel, not 0-divide
    back = np.asarray(quant.dequantize_int8(jnp.asarray(q),
                                            jnp.asarray(scale)))
    assert np.all(back[:, 0] == 0.0) and np.all(back[:, 2] == 0.0)


def test_quantize_decode_params_scopes_and_identity():
    """f32 is the IDENTITY (same object — the byte-identity contract);
    int8w rewrites only eligible leaves under the decode scopes, with a
    structure-aligned full-mirror scales tree."""
    params = {
        "encoder": {"k": np.ones((4, 4), np.float32)},
        "decoder": {"k": np.full((4, 6), 0.5, np.float32),
                    "b": np.zeros((6,), np.float32)},
        "out_fc": {"k": np.eye(4, dtype=np.float32)},
    }
    cfg = fira_tiny().replace(decode_engine=True)
    same, scales = quant.quantize_decode_params(params, cfg)
    assert same is params and scales is None

    qp, scales = quant.quantize_decode_params(
        params, cfg.replace(serve_precision="int8w"))
    assert qp["encoder"]["k"] is params["encoder"]["k"]  # prefill scope
    assert qp["decoder"]["k"].dtype == np.int8
    assert qp["decoder"]["b"] is params["decoder"]["b"]  # 1-D stays f32
    assert qp["out_fc"]["k"].dtype == np.int8
    # dequant_tree reconstructs within the per-channel bound, passes
    # through everything unquantized
    back = quant.dequant_tree(qp, scales)
    np.testing.assert_allclose(np.asarray(back["decoder"]["k"]), 0.5,
                               atol=np.max(scales["decoder"]["k"]) / 2)
    assert back["decoder"]["b"] is qp["decoder"]["b"]
    assert back["encoder"]["k"] is params["encoder"]["k"]

    bp, bscales = quant.quantize_decode_params(
        params, cfg.replace(serve_precision="bf16"))
    assert bscales is None
    assert bp["decoder"]["k"].dtype == jnp.bfloat16
    assert bp["decoder"]["b"] is params["decoder"]["b"]
    assert quant.dequant_tree(bp, None) is bp


# --------------------------------------------------------------------------
# knob resolution: tags, namespaces, parse-time validation
# --------------------------------------------------------------------------

def test_tier_tag_and_namespace():
    cfg = fira_tiny().replace(decode_engine=True)
    assert quant.tier_tag(cfg) == ""               # default: labels untouched
    assert quant.tier_namespace(cfg) == b""        # default: digests untouched
    assert quant.tier_tag(cfg.replace(kv_dtype="bf16")) == "bf16kv"
    assert quant.tier_tag(cfg.replace(serve_precision="int8w")) == "int8w"
    assert quant.tier_tag(cfg.replace(serve_precision="bf16")) == "bf16w"
    assert quant.tier_tag(cfg.replace(kv_dtype="bf16",
                                      serve_precision="int8w")) \
        == "bf16kv.int8w"
    assert quant.tier_namespace(cfg.replace(kv_dtype="bf16")) == b"bf16kv"


def test_kv_seed_dtype_and_itemsize():
    cfg = fira_tiny().replace(decode_engine=True)
    assert quant.kv_seed_dtype(cfg, jnp.float32) == jnp.float32
    # f32 keeps the historical rule: the compute dtype passes through
    assert quant.kv_seed_dtype(cfg, jnp.float64) == jnp.float64
    bf = cfg.replace(kv_dtype="bf16")
    assert quant.kv_seed_dtype(bf, jnp.float32) == jnp.bfloat16
    assert paging.kv_itemsize(cfg) == 4
    assert paging.kv_itemsize(bf) == 2


def test_quant_errors_named_knob_messages():
    base = fira_tiny().replace(decode_engine=True)
    assert quant.quant_errors(base) == []
    assert quant.quant_errors(
        base.replace(kv_dtype="bf16", serve_precision="int8w")) == []

    errs = quant.quant_errors(base.replace(kv_dtype="fp8"))
    assert len(errs) == 1 and "kv_dtype 'fp8'" in errs[0]
    errs = quant.quant_errors(base.replace(serve_precision="int4"))
    assert len(errs) == 1 and "serve_precision 'int4'" in errs[0]

    # engine path required: the arena/program family being tiered IS the
    # engine's
    off = fira_tiny()
    errs = quant.quant_errors(off.replace(kv_dtype="bf16"))
    assert len(errs) == 1 and "requires the slot engine" in errs[0]
    errs = quant.quant_errors(off.replace(serve_precision="int8w"))
    assert len(errs) == 1 and "requires the slot engine" in errs[0]

    # training path: armed tiers rejected outright, even with the engine
    errs = quant.quant_errors(base.replace(kv_dtype="bf16"), train=True)
    assert len(errs) == 1 and "training path" in errs[0]
    assert quant.quant_errors(base, train=True) == []


def test_engine_build_rejects_bad_tier(engine_setup):
    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.model.model import FiraModel

    cfg0, _dataset, _dir, params = engine_setup
    cfg = dataclasses.replace(cfg0, decode_engine=True, kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype 'fp8'"):
        engine_lib.SlotEngine(FiraModel(cfg), params, cfg)


def test_cli_exits_2_on_tier_knobs(corpus_dir, tmp_path):
    """Parse-time rejection with named-knob messages — not a mid-run
    dtype surprise (the exit-2 contract of paging/spec/fleet)."""
    from fira_tpu import cli

    base = ["test", "--data-dir", corpus_dir, "--config", "fira-tiny",
            "--out-dir", str(tmp_path / "o")]
    # tier knobs without the engine: named message, exit 2
    assert cli.main(base + ["--kv-dtype", "bf16"]) == 2
    assert cli.main(base + ["--serve-precision", "int8w"]) == 2
    # training path rejects armed tiers outright
    assert cli.main(["train", "--data-dir", corpus_dir, "--config",
                     "fira-tiny", "--out-dir", str(tmp_path / "t"),
                     "--kv-dtype", "bf16"]) == 2
    # with the engine the knobs admit: the run gets PAST parse-time
    # validation and fails on the missing checkpoint instead (rc 1)
    assert cli.main(base + ["--engine", "--kv-dtype", "bf16",
                            "--serve-precision", "int8w"]) == 1


# --------------------------------------------------------------------------
# digest tier namespace
# --------------------------------------------------------------------------

def test_digest_namespace_isolates_tiers():
    """The SAME payload digests differently under different tiers, and
    identically under the same tier — so a cached f32 artifact can never
    seat a bf16 slot, while the f32 default digest is unchanged (empty
    namespace == the historical digest)."""
    host = {"src": np.arange(12, dtype=np.int32).reshape(3, 4),
            "mask": np.ones((3,), np.float32),
            "valid": np.array([True, True, True])}
    d_default = payload_digests(dict(host))
    d_f32 = payload_digests(dict(host), b"")
    d_bf16 = payload_digests(dict(host), b"bf16kv")
    d_int8 = payload_digests(dict(host), b"bf16kv.int8w")
    assert d_default == d_f32
    assert d_bf16 == payload_digests(dict(host), b"bf16kv")
    assert len({tuple(d_f32), tuple(d_bf16), tuple(d_int8)}) == 3


# --------------------------------------------------------------------------
# engine-driving legs (slow: tier-1 wall budget; check.sh quant smoke
# covers the serve-path contract on every CI run)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_bf16_arena_halves_kv_bytes_and_stamps_stats(engine_setup):
    cfg0, dataset, _dir, params = engine_setup
    cfg = dataclasses.replace(cfg0, decode_engine=True, kv_dtype="bf16",
                              serve_precision="int8w")
    out, eng = _engine_outputs(params, cfg, dataset)
    s = eng.stats.summary()
    assert s["kv_dtype"] == "bf16" and s["serve_precision"] == "int8w"
    # the machine-recorded bytes come from the arena's ACTUAL dtype:
    # exactly the host-side mirror's accounting at itemsize 2 — half the
    # f32 figure
    bs = paging.resolve_block_size(cfg)
    expect = paging.kv_bytes_per_slot(
        cfg, block_size=bs,
        pool_blocks=paging.auto_pool_blocks(cfg, eng.slots),
        slots=eng.slots, itemsize=paging.kv_itemsize(cfg))
    assert s["kv_bytes_per_slot"] == expect
    assert expect * 2 == paging.kv_bytes_per_slot(
        cfg, block_size=bs,
        pool_blocks=paging.auto_pool_blocks(cfg, eng.slots),
        slots=eng.slots, itemsize=4)
    # labels carry the tier suffix (new program family, compile-guarded)
    assert eng.label("engine_step") == "engine_step[bf16kv.int8w]"
    assert eng._tier_ns == b"bf16kv.int8w"


@pytest.mark.slow
def test_within_tier_byte_stability(engine_setup):
    """Within a tier, (tokens, probs) are a pure function of the stream:
    repeat runs and another slot count agree bitwise. (Cross-tier drift is
    allowed — and MEASURED, by the bench's bleu_delta_vs_f32.)"""
    cfg0, dataset, _dir, params = engine_setup
    tier = dataclasses.replace(cfg0, decode_engine=True, kv_dtype="bf16",
                               serve_precision="int8w")
    a, _ = _engine_outputs(params, tier, dataset)
    b, _ = _engine_outputs(params, tier, dataset)
    c, _ = _engine_outputs(
        params, dataclasses.replace(tier, engine_slots=4), dataset)
    assert set(a) == set(b) == set(c)
    for p in a:
        np.testing.assert_array_equal(a[p][0], b[p][0])
        np.testing.assert_array_equal(a[p][1], b[p][1])
        np.testing.assert_array_equal(a[p][0], c[p][0])
        np.testing.assert_array_equal(a[p][1], c[p][1])


@pytest.mark.slow
def test_fleet_respawn_requantizes(engine_setup):
    """A replacement replica re-quantizes from the ORIGINAL params by
    construction — the spare/respawn path can never serve f32 weights
    under an int8w tier."""
    from fira_tpu.model.model import FiraModel
    from fira_tpu.parallel import fleet as fleet_lib

    cfg0, _dataset, _dir, params = engine_setup
    cfg = dataclasses.replace(cfg0, decode_engine=True,
                              serve_precision="int8w")
    fleet = fleet_lib.EngineFleet(FiraModel(cfg), params, cfg, replicas=2)
    for eng in fleet.engines:
        assert eng._wq_scales is not None
        assert any(l.dtype == jnp.int8
                   for l in jax.tree_util.tree_leaves(
                       eng._decode_params["decoder"]))
    spare = fleet._build_replacement(None, "r9")
    assert spare._wq_scales is not None
    assert any(l.dtype == jnp.int8
               for l in jax.tree_util.tree_leaves(
                   spare._decode_params["decoder"]))
    assert spare.label("engine_step") == "engine_step[int8w.r9]"


@pytest.mark.slow
def test_spec_accepted_prefix_bit_identical_within_tier(engine_setup):
    """Speculative decode under an armed tier: accepted output stays
    bit-exact vs that tier's plain decode (the spec exactness argument is
    tier-internal — the verify body IS the tier's own step program)."""
    cfg0, dataset, _dir, params = engine_setup
    tier = dataclasses.replace(cfg0, decode_engine=True, kv_dtype="bf16",
                               serve_precision="int8w")
    ref, _ = _engine_outputs(params, tier, dataset)
    got, eng = _engine_outputs(
        params, dataclasses.replace(tier, spec_decode="draft",
                                    engine_spec_k=4), dataset)
    assert set(got) == set(ref)
    for p in ref:
        np.testing.assert_array_equal(got[p][0], ref[p][0])
        np.testing.assert_array_equal(got[p][1], ref[p][1])
    assert eng.stats.verify_dispatches > 0
