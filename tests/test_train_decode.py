"""Training-loop, mesh-parallel, checkpoint, and beam-search tests.

The beam test differentially validates the jitted fixed-shape beam against a
faithful Python re-implementation of the reference's loop
(/root/reference/run_model.py:187-341), run on the same Flax params — the
same oracle strategy SURVEY.md §7 prescribes for the native astdiff.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fira_tpu.config import fira_tiny
from fira_tpu.data.batching import make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.data.vocab import EOS_ID, PAD_ID, START_ID
from fira_tpu.decode.beam import beam_search, beam_search_cached, make_beam_search
from fira_tpu.model.model import FiraModel
from fira_tpu.parallel import mesh as pmesh
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import CheckpointManager, init_state
from fira_tpu.train.loop import run_dev, train
from fira_tpu.decode.runner import run_test


@pytest.fixture(scope="module")
def tiny_setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("corpus"))
    write_corpus_dir(data_dir, n_commits=48, seed=7)
    cfg = fira_tiny(epochs=2, batch_size=8, test_batch_size=4,
                    dev_start_epoch=1, dev_every_batches=8)
    dataset = FiraDataset(data_dir, cfg)
    return dataset


@pytest.fixture(scope="module")
def tiny_model_state(tiny_setup):
    dataset = tiny_setup
    cfg = dataset.cfg
    model = FiraModel(cfg)
    split = dataset.splits["train"]
    batch = make_batch(split, np.arange(cfg.batch_size), cfg)
    state = init_state(model, cfg, batch)
    return model, state, batch


def test_train_step_reduces_loss(tiny_setup, tiny_model_state):
    dataset = tiny_setup
    cfg = dataset.cfg
    model, state, batch = tiny_model_state
    train_step = jax.jit(step_lib.make_train_step(model, cfg))
    losses = []
    for _ in range(12):
        state, metrics = train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.5, losses


def test_mesh_train_step_and_tp_shardings(tiny_setup):
    dataset = tiny_setup
    cfg = dataset.cfg
    model = FiraModel(cfg)
    split = dataset.splits["train"]
    batch = make_batch(split, np.arange(cfg.batch_size), cfg)
    mesh = pmesh.make_mesh(n_data=4, n_model=2)
    state = init_state(model, cfg, batch)
    state = state.replace(params=pmesh.shard_params(state.params, mesh))

    # tensor-parallel layout actually applied: FFN fc1 kernel sharded on its
    # output dim over the model axis
    fc1 = state.params["decoder"]["ffn_0"]["fc1"]["kernel"]
    assert fc1.sharding.spec == pmesh.P(None, "model")

    train_step = step_lib.jit_train_step(model, cfg, mesh, state, batch)
    sbatch = pmesh.shard_batch(batch, mesh)
    l0 = l1 = None
    for i in range(4):
        state, metrics = train_step(state, sbatch)
        loss = float(jax.device_get(metrics["loss"]))
        assert np.isfinite(loss)
        l0 = loss if l0 is None else l0
        l1 = loss
    assert l1 < l0


@pytest.mark.parametrize("overrides", [
    {},  # parity defaults
    # production-config encoder: split buffer + sorted scatter — guards the
    # column-slab einsums' sharding propagation under the Megatron TP rules
    {"encoder_buffer": "split", "sort_edges": True},
    # the adjacency is one flat 1-D scatter: the lowering flattens the
    # batch axis into B*N*N, so its GSPMD propagation under the sorted
    # promise (the train cell's knobs) deserves its own mesh pin
    {"sort_edges": True},
], ids=["parity", "split_buffer", "sorted_scatter"])
def test_mesh_matches_single_device_loss(tiny_setup, overrides):
    """DP+TP sharded step computes the same loss as the unsharded step."""
    dataset = tiny_setup
    cfg = dataset.cfg.replace(**overrides)
    model = FiraModel(cfg)
    split = dataset.splits["train"]
    batch = make_batch(split, np.arange(cfg.batch_size), cfg)

    state_a = init_state(model, cfg, batch)
    step_a = jax.jit(step_lib.make_train_step(model, cfg))
    _, m_a = step_a(state_a, batch)

    mesh = pmesh.make_mesh(n_data=4, n_model=2)
    state_b = init_state(model, cfg, batch)
    state_b = state_b.replace(params=pmesh.shard_params(state_b.params, mesh))
    step_b = step_lib.jit_train_step(model, cfg, mesh, state_b, batch)
    _, m_b = step_b(state_b, pmesh.shard_batch(batch, mesh))

    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                               rtol=2e-5)


def test_checkpoint_roundtrip(tmp_path, tiny_setup, tiny_model_state):
    model, state, batch = tiny_model_state
    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save_latest(state, best_bleu=0.25, epoch=3)
    ckpt.save_best(state.params)
    restored, meta = ckpt.restore_latest(state)
    assert meta["best_bleu"] == 0.25 and meta["epoch"] == 3
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(state.params), restored.params,
    )
    best = ckpt.restore_best(state.params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(state.params), best,
    )


def _reference_beam(model, params, batch, cfg):
    """Python transliteration of run_model.py:202-341 (prob-space beams,
    finished-beam sentinels, global sort, copy resolution at extension)."""
    B = batch["diff"].shape[0]
    K, T = cfg.beam_size, cfg.tar_len
    V_out = cfg.output_vocab_size
    states, mask = model.apply({"params": params}, batch,
                               method=FiraModel.encode)
    gen = [[[START_ID] for _ in range(K)] for _ in range(B)]
    prob = [[1.0 if j == 0 else 0.0 for j in range(K)] for _ in range(B)]

    whole_input = np.asarray(batch["diff"])
    sub_input = np.asarray(batch["sub_token"])

    for step in range(T - 1):
        output_nexts = []
        cal_beam = 0
        uncomplete = []
        for j in range(K):
            batch_mask = np.ones(B)
            test_batch = np.zeros((B, T), np.int32)
            test_prob = np.zeros(B)
            for i in range(B):
                cur = gen[i][j]
                if cur[-1] == EOS_ID:
                    batch_mask[i] = 0
                test_batch[i, : len(cur)] = cur
                test_prob[i] = prob[i][j]
            if batch_mask.sum() == 0:
                continue
            uncomplete.append(j)
            cal_beam += 1
            fused = model.apply(
                {"params": params}, states, mask,
                jnp.asarray(test_batch), jnp.asarray(test_batch != PAD_ID),
                method=FiraModel.fused_probs,
            )
            out = np.asarray(fused)[:, step, :] * test_prob[:, None]
            out[batch_mask == 0] = -1.0
            output_nexts.append(out)
        if cal_beam == 0:
            break
        combine = np.concatenate(output_nexts, axis=-1)
        ends, prob_ends = [], []
        for i in range(B):
            be, bp = [], []
            for j in range(K):
                if gen[i][j][-1] == EOS_ID:
                    be.append(j)
                    bp.append(prob[i][j])
            bp = bp + [-1.0] * (K - len(bp))
            ends.append(be)
            prob_ends.append(bp)
        combine = np.concatenate([combine, np.asarray(prob_ends)], axis=-1)
        order = np.argsort(-combine, axis=-1, kind="stable")[:, :K]
        vals = np.take_along_axis(combine, order, axis=-1)
        gen_old, prob_old = gen, prob
        gen, prob = [], vals.tolist()
        for i in range(B):
            gen_beam = []
            for j in range(K):
                idx = order[i][j]
                which_beam, which_token = idx // V_out, idx % V_out
                if which_beam == cal_beam:
                    gen_beam.append(list(gen_old[i][ends[i][which_token]]))
                else:
                    if which_token >= cfg.vocab_size + cfg.sou_len:
                        which_token = int(
                            sub_input[i][which_token - cfg.vocab_size - cfg.sou_len])
                    elif which_token >= cfg.vocab_size:
                        which_token = int(whole_input[i][which_token - cfg.vocab_size])
                    gen_beam.append(
                        list(gen_old[i][uncomplete[which_beam]]) + [int(which_token)])
            gen.append(gen_beam)
    return gen, np.asarray(prob)


def test_beam_matches_reference_loop(tiny_setup, tiny_model_state):
    dataset = tiny_setup
    cfg = dataset.cfg
    model, state, _ = tiny_model_state
    test_split = dataset.splits["test"]
    batch = make_batch(test_split, np.arange(min(4, len(test_split))), cfg)

    tokens, probs = jax.jit(
        lambda p, b: beam_search(model, p, b, cfg)
    )(state.params, batch)
    tokens = np.asarray(tokens)
    probs = np.asarray(probs)

    ref_gen, ref_prob = _reference_beam(model, state.params, batch, cfg)

    B = tokens.shape[0]
    for i in range(B):
        best_jit = int(np.argmax(probs[i]))
        best_ref = int(np.argmax(ref_prob[i]))
        jit_seq = tokens[i, best_jit].tolist()
        jit_seq = jit_seq[: len(ref_gen[i][best_ref])]
        assert jit_seq == ref_gen[i][best_ref], (
            i, jit_seq, ref_gen[i][best_ref])
        np.testing.assert_allclose(probs[i, best_jit],
                                   ref_prob[i][best_ref], rtol=1e-5)


def test_kv_cached_beam_matches_full_redecode(tiny_setup, tiny_model_state):
    """The KV-cached scan must reproduce the full-prefix re-decode beam
    exactly: same tokens, same scores (VERDICT r2 #3) — in the reference's
    prob-space compat mode AND in log-space mode."""
    import dataclasses

    dataset = tiny_setup
    model, state, _ = tiny_model_state
    test_split = dataset.splits["test"]

    for compat in (True, False):
        cfg = dataclasses.replace(dataset.cfg, beam_compat_prob_space=compat)
        batch = make_batch(test_split, np.arange(min(4, len(test_split))), cfg)
        # firacheck: allow[RETRACE] each iteration compiles a DIFFERENT cfg variant (prob/log space) for the equivalence check — test-only, off the hot path
        tok_full, p_full = jax.jit(
            lambda p, b: beam_search(model, p, b, cfg)
        )(state.params, batch)
        # firacheck: allow[RETRACE] same per-variant compile as above, kv-cached side of the equivalence pair
        tok_kv, p_kv = jax.jit(
            lambda p, b: beam_search_cached(model, p, b, cfg)
        )(state.params, batch)
        np.testing.assert_array_equal(np.asarray(tok_full), np.asarray(tok_kv))
        np.testing.assert_allclose(np.asarray(p_full), np.asarray(p_kv),
                                   rtol=2e-5, atol=1e-7)


def test_factored_topk_beam_matches_fused(tiny_setup, tiny_model_state):
    """cfg.beam_factored_topk selects from per-side top-ks (2K candidates
    per beam) instead of the assembled 25,020-way fused tensor. The
    selection is exact for the top-k values, so tokens and scores must
    match the fused path — in both prob modes and both cache modes."""
    import dataclasses

    dataset = tiny_setup
    model, state, _ = tiny_model_state
    test_split = dataset.splits["test"]

    for compat in (True, False):
        for impl in (beam_search, beam_search_cached):
            cfg = dataclasses.replace(dataset.cfg,
                                      beam_compat_prob_space=compat)
            cfg_f = dataclasses.replace(cfg, beam_factored_topk=True)
            batch = make_batch(test_split,
                               np.arange(min(4, len(test_split))), cfg)
            # firacheck: allow[RETRACE] compiles a distinct (prob-mode, cache-impl) variant per iteration for the factored-topk equivalence matrix — test-only
            tok_a, p_a = jax.jit(
                lambda p, b: impl(model, p, b, cfg))(state.params, batch)
            # firacheck: allow[RETRACE] factored-topk side of the same per-variant equivalence pair
            tok_b, p_b = jax.jit(
                lambda p, b: impl(model, p, b, cfg_f))(state.params, batch)
            np.testing.assert_array_equal(np.asarray(tok_a),
                                          np.asarray(tok_b))
            np.testing.assert_allclose(np.asarray(p_a), np.asarray(p_b),
                                       rtol=2e-5, atol=1e-7)


def test_prefetch_to_device_matches_direct_feed(tiny_setup, tiny_model_state):
    """The double-buffered input pipeline must be semantics-free: same
    batches in the same order, host-computed n_valid, and step losses
    identical to feeding the numpy batches directly."""
    from fira_tpu.data.batching import epoch_batches, prefetch_to_device

    dataset = tiny_setup
    cfg = dataset.cfg
    model, state, _ = tiny_model_state
    split = dataset.splits["train"]

    direct = list(epoch_batches(split, cfg, shuffle=True, seed=3, epoch=1))
    pre = list(prefetch_to_device(
        epoch_batches(split, cfg, shuffle=True, seed=3, epoch=1)))
    assert len(pre) == len(direct)
    for (dev_b, n_valid), host_b in zip(pre, direct):
        assert n_valid == int(host_b["valid"].sum())
        for k in host_b:
            np.testing.assert_array_equal(np.asarray(dev_b[k]), host_b[k])

    train_step = jax.jit(step_lib.make_train_step(model, cfg))
    s1, s2 = state, state
    for host_b, (dev_b, _) in zip(direct, pre):
        s1, m1 = train_step(s1, host_b)
        s2, m2 = train_step(s2, dev_b)
        assert float(m1["loss"]) == float(m2["loss"])

    # in-flight depth larger than the stream: must drain cleanly
    one = [direct[0]]
    assert len(list(prefetch_to_device(iter(one), size=4))) == 1


def test_multi_step_matches_sequential_steps(tiny_setup, tiny_model_state):
    """make_multi_step (lax.scan device loop) must be step-for-step identical
    to dispatching make_train_step K times: same per-step losses, same final
    params."""
    from fira_tpu.train.step import make_multi_step, stack_batches

    dataset = tiny_setup
    cfg = dataset.cfg
    model, state, _ = tiny_model_state
    split = dataset.splits["train"]
    batches = [make_batch(split, np.arange(k, k + cfg.batch_size), cfg)
               for k in range(0, 4 * cfg.batch_size, cfg.batch_size)]

    step = jax.jit(step_lib.make_train_step(model, cfg))
    s_seq = state
    seq_losses = []
    for b in batches:
        s_seq, m = step(s_seq, b)
        seq_losses.append(float(m["loss"]))

    multi = jax.jit(make_multi_step(model, cfg))
    s_scan, m = multi(state, stack_batches(batches))
    np.testing.assert_allclose(np.asarray(m["loss"]), seq_losses, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        jax.device_get(s_seq.params), jax.device_get(s_scan.params))
    assert int(s_scan.step) == int(s_seq.step)


def test_rng_impl_rbg_same_init_different_dropout(tiny_setup):
    """cfg.rng_impl='rbg' must keep param init bit-identical to threefry
    (init always threefry), keep the threefry state_rng stream unchanged
    from the historical layout, and train finitely with a different
    dropout stream."""
    dataset = tiny_setup
    cfg = dataset.cfg
    split = dataset.splits["train"]
    batch = make_batch(split, np.arange(cfg.batch_size), cfg)

    model = FiraModel(cfg)
    s_tf = init_state(model, cfg, batch)
    np.testing.assert_array_equal(
        np.asarray(s_tf.rng),
        np.asarray(jax.random.split(jax.random.PRNGKey(cfg.seed))[1]))

    cfg_rbg = cfg.replace(rng_impl="rbg")
    s_rbg = init_state(FiraModel(cfg_rbg), cfg_rbg, batch)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(s_tf.params), jax.device_get(s_rbg.params))
    assert np.asarray(s_rbg.rng).shape != np.asarray(s_tf.rng).shape

    step_rbg = jax.jit(step_lib.make_train_step(FiraModel(cfg_rbg), cfg_rbg))
    s = s_rbg
    rbg_losses = []
    for _ in range(3):
        s, m = step_rbg(s, batch)
        rbg_losses.append(float(m["loss"]))
        assert np.isfinite(rbg_losses[-1])

    # the knob must actually change the dropout stream: same params, same
    # batch, different generator -> different stochastic loss
    step_tf = jax.jit(step_lib.make_train_step(model, cfg))
    _, m_tf = step_tf(s_tf, batch)
    assert float(m_tf["loss"]) != rbg_losses[0]

    # rng_impl mismatch on resume must fail with an actionable error
    import tempfile
    from fira_tpu.train.state import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d)
        ckpt.save_latest(s_rbg, best_bleu=0.1, epoch=1, rng_impl="rbg")
        with pytest.raises(ValueError, match="rng_impl"):
            ckpt.restore_latest(s_rbg, expect_rng_impl="threefry")


def test_fused_steps_training_matches_per_step(tmp_path, tiny_setup):
    """cfg.fused_steps>1 (lax.scan device loop with per-step tail) must
    reproduce the per-step loop's final params; the tiny split (5 batches,
    K=2) exercises both the stacked-group and the un-stacked-tail paths."""
    dataset = tiny_setup
    base = dataset.cfg.replace(dev_start_epoch=99)  # no gates mid-epoch

    results = {}
    for k in (1, 2):
        cfg_k = base.replace(fused_steps=k)
        out = str(tmp_path / f"out_{k}")
        results[k] = train(dataset, cfg=cfg_k, out_dir=out,
                           ckpt_dir=str(tmp_path / f"ckpt_{k}"), epochs=1)
        assert results[k].epochs_run == 1

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        jax.device_get(results[1].state.params),
        jax.device_get(results[2].state.params))


def test_accum_step_matches_big_batch_gradient(tiny_setup):
    """make_accum_step over A stacked micro-batches must produce the same
    optimizer step as one A*B batch: (sum nll grads)/(sum counts) — the
    reference's DataParallel global-batch normalization (run_model.py:
    102-105). Dropout rates are zeroed so both paths are deterministic."""
    from fira_tpu.train.step import make_accum_step, stack_batches

    dataset = tiny_setup
    cfg = dataset.cfg.replace(dropout_rate=0.0, gcn_dropout_rate=0.0)
    split = dataset.splits["train"]
    A, B = 4, cfg.batch_size
    micro = [make_batch(split, np.arange(a * B, (a + 1) * B), cfg)
             for a in range(A)]
    big = make_batch(split, np.arange(A * B), cfg)

    model = FiraModel(cfg)
    state = init_state(model, cfg, micro[0])

    accum = jax.jit(make_accum_step(model, cfg))
    s_accum, m_accum = accum(state, stack_batches(micro))

    big_step = jax.jit(step_lib.make_train_step(model, cfg))
    s_big, m_big = big_step(state, big)

    np.testing.assert_allclose(float(m_accum["loss"]), float(m_big["loss"]),
                               rtol=1e-6)
    # Adam's first step normalizes by sqrt(v) = |g|, so f32 reassociation
    # between the per-micro gradient sum and the one-big-batch sum is
    # amplified to the relative-gradient-error scale (~1e-3), not the
    # absolute one; the math itself is identical (loss above pins it).
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-3, atol=1e-5),
        jax.device_get(s_accum.params), jax.device_get(s_big.params))


def test_accum_tail_padding_matches_plain_step(tiny_setup):
    """The accum epoch tail is padded with all-zero micro-batches
    (loop.epoch_feed): zero rows have label==0 everywhere, so the padded
    group must take EXACTLY the optimizer step the plain program takes on
    the real tail batch alone — same normalization denominator."""
    from fira_tpu.train.step import make_accum_step, stack_batches

    dataset = tiny_setup
    cfg = dataset.cfg.replace(dropout_rate=0.0, gcn_dropout_rate=0.0)
    split = dataset.splits["train"]
    real = make_batch(split, np.arange(cfg.batch_size), cfg)
    pad = jax.tree_util.tree_map(np.zeros_like, real)

    model = FiraModel(cfg)
    state = init_state(model, cfg, real)

    accum = jax.jit(make_accum_step(model, cfg.replace(accum_steps=3)))
    s_tail, m_tail = accum(state, stack_batches([real, pad, pad]))

    plain = jax.jit(step_lib.make_train_step(model, cfg))
    s_plain, m_plain = plain(state, real)

    np.testing.assert_allclose(float(m_tail["loss"]), float(m_plain["loss"]),
                               rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-3, atol=1e-5),
        jax.device_get(s_tail.params), jax.device_get(s_plain.params))


def test_accum_steps_training_runs_and_counts_steps(tmp_path, tiny_setup):
    """Loop integration: accum groups make ONE optimizer step each; the
    5-batch tiny epoch with A=2 yields 2 accumulated + 1 tail = 3 steps."""
    dataset = tiny_setup
    cfg = dataset.cfg.replace(accum_steps=2, dev_start_epoch=99)
    result = train(dataset, cfg=cfg, out_dir=str(tmp_path / "out"),
                   ckpt_dir=str(tmp_path / "ckpt"), epochs=1)
    assert result.epochs_run == 1
    assert int(jax.device_get(result.state.step)) == 3

    with pytest.raises(ValueError, match="mutually"):
        train(dataset, cfg=dataset.cfg.replace(accum_steps=2, fused_steps=2),
              out_dir=str(tmp_path / "out2"),
              ckpt_dir=str(tmp_path / "ckpt2"), epochs=1)


@pytest.mark.parametrize("knob", ["fused_steps", "accum_steps"])
def test_grouped_steps_mesh_smoke(tiny_setup, tmp_path, knob):
    """Grouped device programs under a DP+TP mesh: stacked groups land
    pre-sharded (leading axis replicated, batch axis on data) and the run
    stays finite — for both the fused scan loop and the gradient-
    accumulation step (whose scan carries a sharded gradient pytree)."""
    dataset = tiny_setup
    cfg = dataset.cfg.replace(dev_start_epoch=99, **{knob: 2})
    mesh = pmesh.make_mesh(n_data=4, n_model=2)
    result = train(dataset, cfg=cfg, mesh=mesh,
                   out_dir=str(tmp_path / "out"),
                   ckpt_dir=str(tmp_path / "ckpt"), epochs=1)
    assert result.epochs_run == 1
    assert np.isfinite(
        float(jax.device_get(result.state.params["decoder"]["ffn_0"]["fc1"]
                             ["kernel"]).sum()))


def test_train_end_to_end_tiny(tmp_path, tiny_setup):
    """The FIRA-tiny milestone (SURVEY.md §7 step 4): train with dev gating,
    best-checkpoint save, then beam-decode the test split to an output file."""
    dataset = tiny_setup
    out_dir = str(tmp_path / "OUTPUT")
    var_maps = None
    result = train(dataset, out_dir=out_dir, epochs=2,
                   ckpt_dir=str(tmp_path / "ckpt"), var_maps=var_maps)
    assert result.epochs_run == 2
    assert os.path.exists(os.path.join(out_dir, "train_process"))
    assert result.commits_per_sec_per_chip > 0

    model = FiraModel(dataset.cfg)
    metrics = run_test(model, result.state.params, dataset,
                       out_dir=out_dir)
    out_file = os.path.join(out_dir, "output_fira")
    assert os.path.exists(out_file)
    n_lines = len(open(out_file).read().splitlines())
    assert n_lines == len(dataset.splits["test"])
    assert metrics["sentence_bleu"] >= 0.0


@pytest.mark.slow
def test_fira_large_mesh_step():
    """fira-large (d=512, 8 layers, beam 8 — the BASELINE.json v4-32 config)
    compiles and runs a DP x TP sharded train step. Sequence lengths are
    shrunk to keep the CPU test fast; the scaled axes under test are the
    wider d_model (TP-sharded matmuls) and the deeper stacks.

    slow-marked (Round 14): at 58 s of compile wall this single geometry
    smoke was the largest item in a tier-1 suite measured at ~834 s of
    the 870 s budget (PR 12); the mesh/TP contracts stay tier-1-covered
    at tiny geometry (test_multichip: n_data=1 bitwise + grouped-bucket
    zero-retrace legs) and the fira-large geometry still runs in the
    deep `-m slow` pass."""
    from fira_tpu.config import fira_large
    from fira_tpu.data.synthetic import make_memory_split

    cfg = fira_large(batch_size=8, sou_len=32, tar_len=12, att_len=8,
                     ast_change_len=28, sub_token_len=24, max_edges=256)
    cfg, split, _ = make_memory_split(cfg, 8, seed=1)
    batch = make_batch(split, np.arange(8), cfg)
    mesh = pmesh.make_mesh(n_data=4, n_model=2)
    model = FiraModel(cfg)
    state = init_state(model, cfg, batch)
    state = state.replace(params=pmesh.shard_params(state.params, mesh))
    train_step = step_lib.jit_train_step(model, cfg, mesh, state, batch)
    state, metrics = train_step(state, pmesh.shard_batch(batch, mesh))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    # beam-8 decode on the same params
    tokens, probs = jax.jit(
        lambda p, b: beam_search_cached(model, p, b, cfg)
    )(state.params, batch)
    assert tokens.shape == (8, 8, cfg.tar_len)
    assert np.isfinite(np.asarray(probs)).all()


@pytest.mark.parametrize("ablation", ["no_edit", "no_subtoken", "nothing"])
def test_ablation_configs_train_and_decode(tiny_setup, ablation):
    """The three paper Table 3 ablations run end-to-end: one train step
    (finite loss) and a beam decode at the ablated geometry."""
    from fira_tpu.config import apply_ablation

    dataset = tiny_setup
    cfg = apply_ablation(dataset.cfg, ablation)
    split = dataset.splits["train"]
    batch = make_batch(split, np.arange(cfg.batch_size), cfg)
    model = FiraModel(cfg)
    state = init_state(model, cfg, batch)
    train_step = jax.jit(step_lib.make_train_step(model, cfg))
    state, metrics = train_step(state, batch)
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    tokens, probs = jax.jit(
        lambda p, b: beam_search_cached(model, p, b, cfg)
    )(state.params, batch)
    assert tokens.shape == (cfg.batch_size, cfg.beam_size, cfg.tar_len)
    assert np.isfinite(np.asarray(probs)).all()


def test_f32_checkpoint_decodes_in_bf16(tmp_path, tiny_setup, tiny_model_state):
    """Params checkpointed in f32 restore into a bf16-compute model and beam
    decode (the --dtype bfloat16 test path: params stay f32, compute casts)."""
    model_f32, state, batch = tiny_model_state
    dataset = tiny_setup
    cfg = dataset.cfg
    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save_best(state.params)

    model_bf16 = FiraModel(cfg, dtype=jnp.bfloat16)
    template = init_state(model_bf16, cfg, batch)
    params = ckpt.restore_best(template.params)
    tokens, probs = jax.jit(
        lambda p, b: beam_search_cached(model_bf16, p, b, cfg)
    )(params, batch)
    assert tokens.shape == (cfg.batch_size, cfg.beam_size, cfg.tar_len)
    assert np.isfinite(np.asarray(probs, np.float32)).all()
