"""The rest of a run with the timed path broken underneath: ``correct`` has
to come out false, once for each fault a cell can have. (One chip, so no
exchange between chips to leave out.)"""

import pytest

from bench_tiny import run_cell


def _unchanged(step):
    import jax

    def unchanged(state, batch):
        keep = jax.tree_util.tree_map(lambda x: x + 0, state)
        _new, metrics = step(state, batch)
        return keep, metrics
    return unchanged


def _half(step):
    def half(state, batch):
        n = batch["diff"].shape[1] // 2
        return step(state, {k: v[:, :n] for k, v in batch.items()})
    return half


def test_training_faults_come_out_not_correct(monkeypatch):
    """A step that returns its state unchanged; half of the batch left out,
    the mean taken over the rest."""
    from fira_tpu.train import step as step_lib

    real = step_lib.jit_multi_step
    for breaker in (_unchanged, _half):
        with monkeypatch.context() as m:
            m.setattr(step_lib, "jit_multi_step",
                      lambda *a, _b=breaker: _b(real(*a)))
            res = run_cell("train")
        assert res["correct"] is False, breaker.__name__
        if breaker is _unchanged:
            assert res["check"]["change_gap"]["value"] == \
                pytest.approx(1.0, abs=0.05)


def test_token_altered_where_it_is_produced(monkeypatch):
    from fira_tpu.decode.engine import SlotEngine

    real = SlotEngine.harvest

    def harvest(self):
        items = real(self)
        for it in items:
            it.tokens = it.tokens.copy()
            it.tokens[:, 2] = (it.tokens[:, 2] + 7) % 100 + 4   # another word
        return items
    monkeypatch.setattr(SlotEngine, "harvest", harvest)
    for traffic in ("drain", "serve"):
        assert run_cell(traffic)["correct"] is False, traffic
