"""Model unit tests: shapes, jit-ability, gradients, dropout rng wiring,
and the dense-adjacency scatter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fira_tpu.config import fira_tiny
from fira_tpu.data import synthetic
from fira_tpu.data.batching import make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.model.model import FiraModel, dense_adjacency


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tiny_corpus"))
    synthetic.write_corpus_dir(d, n_commits=20, seed=5)
    cfg = fira_tiny(sou_len=64, ast_change_len=48, sub_token_len=48,
                    max_edges=1024, batch_size=4)
    ds = FiraDataset(d, cfg)
    batch = make_batch(ds.splits["train"], np.arange(4), ds.cfg)
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    model = FiraModel(ds.cfg)
    params = model.init(jax.random.PRNGKey(0), jbatch, deterministic=True)
    return ds.cfg, model, params, jbatch


def test_dense_adjacency_scatter():
    senders = jnp.asarray([[0, 1, 0, 0]])
    receivers = jnp.asarray([[1, 0, 0, 0]])
    values = jnp.asarray([[0.5, 0.5, 0.25, 0.0]])
    adj = dense_adjacency(senders, receivers, values, 3)
    expected = np.zeros((1, 3, 3), np.float32)
    expected[0, 0, 1] = 0.5
    expected[0, 1, 0] = 0.5
    expected[0, 0, 0] = 0.25  # real self-loop + zero pad entries accumulate
    np.testing.assert_allclose(np.asarray(adj), expected)


def test_forward_shapes_and_loss(tiny):
    cfg, model, params, jbatch = tiny
    loss, count = model.apply(params, jbatch, deterministic=True)
    assert np.isfinite(float(loss)) and int(count) > 0
    per_tok = float(loss) / int(count)
    # untrained model ~ uniform over the fused distribution
    assert 2.0 < per_tok < 25.0


def test_jit_and_grad(tiny):
    cfg, model, params, jbatch = tiny

    @jax.jit
    def loss_fn(p, b, rng):
        s, c = model.apply(p, b, deterministic=False, rngs={"dropout": rng})
        return s / c

    g = jax.grad(loss_fn)(params, jbatch, jax.random.PRNGKey(1))
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    # some gradient reaches the word embedding and the copy head
    flat = jax.tree_util.tree_flatten_with_path(g)[0]
    norms = {jax.tree_util.keystr(k): float(jnp.abs(v).sum()) for k, v in flat}
    assert any("word_embed" in k and n > 0 for k, n in norms.items())
    assert any("copy_net" in k and n > 0 for k, n in norms.items())


def test_dropout_changes_loss(tiny):
    cfg, model, params, jbatch = tiny
    l1, c = model.apply(params, jbatch, deterministic=False,
                        rngs={"dropout": jax.random.PRNGKey(1)})
    l2, _ = model.apply(params, jbatch, deterministic=False,
                        rngs={"dropout": jax.random.PRNGKey(2)})
    assert float(l1) != float(l2)


def test_dev_predict_shape(tiny):
    cfg, model, params, jbatch = tiny
    ids = model.apply(params, jbatch, method=FiraModel.dev_predict)
    assert ids.shape == jbatch["msg"].shape
    assert int(ids.max()) < cfg.output_vocab_size


class TestPerfKnobs:
    """stable_residual / copy_head_remat are perf knobs, not semantics:
    exactness pins for the cheap directions, tolerance for bf16."""

    def test_stable_residual_off_is_exact_in_f32(self, tiny):
        cfg, model, params, jbatch = tiny
        m2 = FiraModel(cfg.replace(stable_residual=False))
        a = model.apply(params, jbatch, deterministic=True)
        b = m2.apply(params, jbatch, deterministic=True)
        assert float(a[0]) == float(b[0])  # astype is a no-op in f32

    def test_stable_residual_off_close_in_bf16(self, tiny):
        cfg, _, params, jbatch = tiny
        ma = FiraModel(cfg.replace(compute_dtype="bfloat16"),
                       dtype=jnp.bfloat16)
        mb = FiraModel(cfg.replace(compute_dtype="bfloat16",
                                   stable_residual=False),
                       dtype=jnp.bfloat16)
        a = ma.apply(params, jbatch, deterministic=True)
        b = mb.apply(params, jbatch, deterministic=True)
        la, lb = float(a[0]) / float(a[1]), float(b[0]) / float(b[1])
        assert abs(la - lb) / abs(la) < 0.02, (la, lb)

    def test_copy_head_remat_off_identical_loss_and_grads(self, tiny):
        cfg, model, params, jbatch = tiny
        m2 = FiraModel(cfg.replace(copy_head_remat=False))

        def loss(m):
            def f(p):
                s, c = m.apply({"params": p["params"]}, jbatch,
                               deterministic=True)
                return s / c
            return f

        la, ga = jax.value_and_grad(loss(model))(params)
        lb, gb = jax.value_and_grad(loss(m2))(params)
        assert float(la) == float(lb)
        # the loss is bit-equal but the grads are NOT guaranteed to be:
        # remat-off re-derives the copy-head backward from a different
        # XLA graph, and the compiler is free to reassociate f32 sums
        # per graph. Bisected: max abs grad delta is single-digit-ulp
        # noise (3.7e-9 under the default threefry lowering, 2.2e-8
        # under the partitionable lowering mesh.py pins — the draws
        # differ, the reassociation noise floor doesn't move in kind);
        # atol=1e-7 stays ~4x above the observed floor and ~4 orders
        # below any real backward change (a dropped remat term shifts
        # grads at 1e-3+ on these magnitudes).
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=0, atol=1e-7), ga, gb)


class TestSplitEncoderBuffer:
    """cfg.encoder_buffer='split' keeps diff and [sub||ast] rows as two
    tensors with the GCN's A.x as two column-slab bmms — same parameters,
    same dropout RNG stream, outputs equal to the single-buffer path up to
    matmul reassociation (the 650-long contraction becomes two partial
    sums)."""

    def _pair(self, tiny):
        import dataclasses

        cfg, model, params, jbatch = tiny
        cfg_split = dataclasses.replace(cfg, encoder_buffer="split")
        return cfg, cfg_split, model, FiraModel(cfg_split), params, jbatch

    def test_param_tree_identical(self, tiny):
        cfg, cfg_split, _m, m_split, params, jbatch = self._pair(tiny)
        p2 = m_split.init(jax.random.PRNGKey(0), jbatch, deterministic=True)
        t1 = jax.tree_util.tree_structure(params)
        t2 = jax.tree_util.tree_structure(p2)
        assert t1 == t2
        # identical init draws: same scope names -> same keys
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_deterministic_loss_close(self, tiny):
        cfg, cfg_split, model, m_split, params, jbatch = self._pair(tiny)
        l1, c1 = model.apply(params, jbatch, deterministic=True)
        l2, c2 = m_split.apply(params, jbatch, deterministic=True)
        assert int(c1) == int(c2)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-5)

    def test_train_mode_same_rng_close(self, tiny):
        # the split path draws the SAME dropout masks (one full-width call
        # per GCN round, same module paths), so train losses agree too
        cfg, cfg_split, model, m_split, params, jbatch = self._pair(tiny)
        rng = {"dropout": jax.random.PRNGKey(7)}
        l1, c1 = model.apply(params, jbatch, deterministic=False, rngs=rng)
        l2, c2 = m_split.apply(params, jbatch, deterministic=False, rngs=rng)
        np.testing.assert_allclose(float(l1) / int(c1), float(l2) / int(c2),
                                   rtol=2e-5)

    def test_segment_path_is_rejected(self, tiny):
        import dataclasses

        cfg, _s, _m, _ms, params, jbatch = self._pair(tiny)
        cfg_bad = dataclasses.replace(cfg, encoder_buffer="split",
                                      adjacency_impl="segment")
        with pytest.raises(ValueError, match="dense adjacency"):
            FiraModel(cfg_bad).apply(params, jbatch, deterministic=True)


@pytest.mark.parametrize("promised", (False, True),
                         ids=("unsorted", "sorted"))
@pytest.mark.parametrize("batch", (1, 16, 170, 340))
def test_dense_adjacency_equals_a_numpy_scatter(batch, promised):
    """The one adjacency scatter against ``np.add.at`` of the same padded
    triplets, at the batch sizes the reference and the cells use (170 a
    chip, 340 and up where the deleted N-D form went wrong on the TPU),
    in raw order and host-sorted under the ``indices_sorted`` promise,
    float32 and bfloat16 targets: the same cells, the same values."""
    from fira_tpu.data.batching import sort_edge_rows

    N, E = 40, 96
    rng = np.random.default_rng(batch)
    s = np.zeros((batch, E), np.int16)
    r = np.zeros((batch, E), np.int16)
    v = np.zeros((batch, E), np.float32)
    for b in range(batch):
        # distinct cells a row, as graph_build's dedup guarantees; the
        # rest of the row is (0, 0, 0.0) padding
        n = int(rng.integers(0, E + 1))
        cells = rng.choice(N * N, size=n, replace=False)
        s[b, :n], r[b, :n] = cells // N, cells % N
        v[b, :n] = rng.uniform(0.05, 1.0, size=n)
    if promised:
        s, r, v, _ = sort_edge_rows(s, r, v, None, N)
    want = np.zeros((batch, N, N), np.float32)
    np.add.at(want, (np.arange(batch)[:, None], s.astype(np.int64),
                     r.astype(np.int64)), v)
    assert np.count_nonzero(want) == np.count_nonzero(v)
    for out_dtype in (jnp.float32, jnp.bfloat16):
        got = dense_adjacency(jnp.asarray(s), jnp.asarray(r), jnp.asarray(v),
                              N, indices_sorted=promised,
                              out_dtype=out_dtype)
        assert got.shape == (batch, N, N) and got.dtype == out_dtype
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(jnp.asarray(want).astype(out_dtype)
                       .astype(jnp.float32)))


def test_init_state_params_do_not_depend_on_batch_rows(tiny):
    # init_state initializes from ONE row of the sample batch (flax runs
    # init's forward eagerly on the default device; at a mesh's global
    # batch that put 10 GB on device 0 of a 4 x v5e host): the parameters
    # must be bit-identical to a full-batch init from the same seed.
    from fira_tpu.train.state import init_state

    cfg, model, params, jbatch = tiny
    batch = {k: np.asarray(v) for k, v in jbatch.items()}
    batch["_positions"] = np.arange(4)      # host-only fields are ignored
    state = init_state(model, cfg, batch, seed=0)
    full = model.init(jax.random.split(jax.random.PRNGKey(0))[0], jbatch,
                      deterministic=True)["params"]
    same = jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(a, b)), state.params, full)
    assert all(jax.tree_util.tree_leaves(same))
    assert (jax.tree_util.tree_structure(state.params)
            == jax.tree_util.tree_structure(full))
