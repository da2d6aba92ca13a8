"""Shared by the engine tests: the batched beam as the oracle. Every engine
test holds the slot engine (its one form) to what the batched beam — a
whole-sequence cache stripe a row, reordered by ``src_beam`` — returns for
the same packed batches."""

import numpy as np

from fira_tpu.data.feeder import Feeder
from fira_tpu.decode.beam import make_beam_search
from fira_tpu.decode.runner import _decode_tasks


def beam_outputs(model, params, data, cfg):
    """{split position: (tokens, probs)} from the batched beam over split
    ``data``, in the form ``cfg.beam_kv_cache`` / ``beam_factored_topk``
    choose."""
    beam = make_beam_search(model, cfg)
    tasks, _ = _decode_tasks(data, cfg)
    out = {}
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for item in feed:
            toks, probs = (np.asarray(a) for a in beam(params, item.device))
            C = item.host["valid"].shape[0]
            for i in np.flatnonzero(item.host["valid"]):
                out[item.index * C + int(i)] = (toks[i], probs[i])
    return out
