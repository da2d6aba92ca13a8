"""Corpus -> fixed-shape training examples (the reference's Dataset.py:96-334
pipeline, rebuilt around COO edge lists and ragged caching).

Per-commit processing order follows the reference exactly:
variable-placeholder substitution -> case normalization -> lemmatization (msg
only) -> id conversion -> <start>/<eos> wrapping -> padding -> sub-token dedup
-> copy labels -> adjacency assembly. Examples cache to a single compressed
.npz per split with ragged edge storage (concatenated COO + offsets) instead
of 90k scipy matrices pickled (Dataset.py:294,332) — one sequential read, a
fraction of the pickle's size.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
from typing import Dict, List, Optional

import numpy as np

from fira_tpu.config import FiraConfig
from fira_tpu.data import graph_build
from fira_tpu.data.schema import (
    AST_CHANGE_VOCAB_FILE,
    Corpus,
    CommitRecord,
    SPLIT_INDEX_FILE,
    WORD_VOCAB_FILE,
)
from fira_tpu.data.vocab import (
    EOS_ID,
    LEMMATIZATION,
    PAD_ID,
    START_ID,
    Vocab,
    normalize_token,
    pad_sequence,
)

ARRAY_FIELDS = ("diff", "msg", "msg_tar", "diff_mark", "ast_change", "sub_token")


@dataclasses.dataclass
class Example:
    """One tensorized commit. Shapes are config-fixed except the COO edges."""

    diff: np.ndarray        # int32 [sou_len]
    msg: np.ndarray         # int32 [tar_len] (decoder input ids)
    msg_tar: np.ndarray     # int32 [tar_len] (labels incl. copy ids)
    diff_mark: np.ndarray   # int32 [sou_len] (0 pad, 1 del, 2 ctx, 3 add)
    ast_change: np.ndarray  # int32 [ast_change_len]
    sub_token: np.ndarray   # int32 [sub_token_len]
    senders: np.ndarray     # int32 [n_edges] (ragged)
    receivers: np.ndarray   # int32 [n_edges]
    values: np.ndarray      # float32 [n_edges]
    kinds: np.ndarray       # int8 [n_edges] (graph_build.EDGE_KIND_*)


def _substitute(tokens: List[str], var_map: Dict[str, str]) -> List[str]:
    """Dataset.py:125-129: placeholder substitution then case-normalize,
    applied to the substituted value."""
    out = []
    for tok in tokens:
        if tok in var_map:
            tok = var_map[tok]
        out.append(normalize_token(tok))
    return out


def process_record(record: CommitRecord, word_vocab: Vocab,
                   ast_change_vocab: Vocab, cfg: FiraConfig) -> Example:
    """Tensorize one commit (Dataset.py:111-303 semantics)."""
    raw_diff = _substitute(record.diff_tokens, record.var_map)
    raw_msg = _substitute(record.msg_tokens, record.var_map)
    raw_msg = [LEMMATIZATION.get(t, t) for t in raw_msg]  # Dataset.py:136-137

    diff_ids = word_vocab.convert_tokens_to_ids(raw_diff)
    diff = pad_sequence([START_ID] + diff_ids + [EOS_ID], cfg.sou_len)

    msg_ids = word_vocab.convert_tokens_to_ids(raw_msg)
    msg = pad_sequence([START_ID] + msg_ids + [EOS_ID], cfg.tar_len)

    mark = pad_sequence([2] + list(record.diff_marks) + [2], cfg.sou_len, pad_id=0)

    # ast + change share one node sequence (Dataset.py:168-171); the no_edit
    # ablation drops the change (edit-op) nodes.
    change_labels = list(record.change_labels) if cfg.use_edit else []
    ast_change_ids = ast_change_vocab.convert_tokens_to_ids(
        list(record.ast_labels) + change_labels
    )
    ast_change = pad_sequence(ast_change_ids, cfg.ast_change_len)

    sub_tokens, edge_sub_token = graph_build.dedup_sub_tokens(
        raw_diff, record.diff_atts
    )
    sub_token_ids = pad_sequence(
        word_vocab.convert_tokens_to_ids(sub_tokens), cfg.sub_token_len
    )

    labels = graph_build.copy_labels(
        msg_ids, raw_msg, raw_diff, sub_tokens,
        vocab_size=len(word_vocab), sou_len=cfg.sou_len,
        use_subtoken_copy=cfg.use_subtoken_copy,
        sub_token_len=cfg.sub_token_len,
    )
    msg_tar = pad_sequence([START_ID] + labels + [EOS_ID], cfg.tar_len)

    adj = graph_build.build_adjacency(
        sou_len=cfg.sou_len,
        sub_token_len=cfg.sub_token_len,
        ast_change_len=cfg.ast_change_len,
        raw_diff_len=len(raw_diff),
        n_ast=len(record.ast_labels),
        edge_change_code=record.edge_change_code,
        edge_change_ast=record.edge_change_ast,
        edge_ast_code=record.edge_ast_code,
        edge_ast=record.edge_ast,
        edge_sub_token=edge_sub_token,
        use_edit=cfg.use_edit,
    )

    as_i32 = lambda x: np.asarray(x, dtype=np.int32)
    return Example(
        diff=as_i32(diff), msg=as_i32(msg), msg_tar=as_i32(msg_tar),
        diff_mark=as_i32(mark), ast_change=as_i32(ast_change),
        sub_token=as_i32(sub_token_ids),
        senders=adj.senders, receivers=adj.receivers, values=adj.values,
        kinds=adj.kinds,
    )


class ProcessedSplit:
    """A split's examples as stacked arrays + ragged COO storage."""

    def __init__(self, arrays: Dict[str, np.ndarray]):
        self.arrays = arrays
        self.n = arrays["diff"].shape[0]
        # what callers measured of the arrays and keep with them
        # (data/buckets.sample_extents); the arrays are not written again
        self.derived: Dict = {}

    def __len__(self) -> int:
        return self.n

    def edge_slice(self, i: int):
        lo, hi = self.arrays["edge_offsets"][i], self.arrays["edge_offsets"][i + 1]
        return (
            self.arrays["edge_senders"][lo:hi],
            self.arrays["edge_receivers"][lo:hi],
            self.arrays["edge_values"][lo:hi],
        )

    @classmethod
    def from_examples(cls, examples: List[Example]) -> "ProcessedSplit":
        arrays = {
            f: np.stack([getattr(e, f) for e in examples]) for f in ARRAY_FIELDS
        }
        offsets = np.zeros(len(examples) + 1, dtype=np.int64)
        for i, e in enumerate(examples):
            offsets[i + 1] = offsets[i] + e.senders.shape[0]
        arrays["edge_offsets"] = offsets
        arrays["edge_senders"] = np.concatenate([e.senders for e in examples])
        arrays["edge_receivers"] = np.concatenate([e.receivers for e in examples])
        arrays["edge_values"] = np.concatenate([e.values for e in examples])
        arrays["edge_kinds"] = np.concatenate([e.kinds for e in examples])
        return cls(arrays)

    def save(self, path: str) -> None:
        np.savez_compressed(path, **self.arrays)

    @classmethod
    def load(cls, path: str) -> "ProcessedSplit":
        with np.load(path) as z:
            return cls({k: z[k] for k in z.files})


class FiraDataset:
    """Corpus directory -> processed, cached, split dataset.

    Split indices honor an existing ``all_index`` file (the reference's frozen
    split, Dataset.py:305-313); otherwise a fresh shuffled split is drawn once
    and persisted, using the reference's 75000/8000/7661 proportions scaled to
    the corpus size.
    """

    SPLITS = ("train", "valid", "test")

    def __init__(self, data_dir: str, cfg: FiraConfig,
                 cache_dir: Optional[str] = None):
        self.data_dir = data_dir
        self.cache_dir = cache_dir or os.path.join(data_dir, "processed")
        self.word_vocab = Vocab.from_json(os.path.join(data_dir, WORD_VOCAB_FILE))
        ast_vocab_path = os.path.join(data_dir, AST_CHANGE_VOCAB_FILE)
        corpus = None
        if not os.path.exists(ast_vocab_path):
            corpus = Corpus.load(data_dir)
            Vocab.build_ast_change_vocab(corpus.streams["ast"]).to_json(ast_vocab_path)
        self.ast_change_vocab = Vocab.from_json(ast_vocab_path)
        self.cfg = cfg.replace(
            vocab_size=len(self.word_vocab),
            ast_change_vocab_size=len(self.ast_change_vocab),
        )

        self.split_indices = self._load_or_draw_split(corpus)
        self.splits: Dict[str, ProcessedSplit] = {}
        self._ensure_processed(corpus)

    # --- split bookkeeping ---

    def _load_or_draw_split(self, corpus: Optional[Corpus]) -> Dict[str, List[int]]:
        path = os.path.join(self.data_dir, SPLIT_INDEX_FILE)
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        corpus = corpus or Corpus.load(self.data_dir)
        n = len(corpus)
        # reference proportions 75000/8000/7661 of 90661 (Dataset.py:10-12)
        n_valid = max(1, round(n * 8000 / 90661))
        n_test = max(1, round(n * 7661 / 90661))
        n_train = n - n_valid - n_test
        index = list(range(n))
        random.Random(self.cfg.seed).shuffle(index)
        split = {
            "train": index[:n_train],
            "valid": index[n_train : n_train + n_valid],
            "test": index[n_train + n_valid :],
        }
        with open(path, "w") as f:
            json.dump(split, f)
        return split

    # --- processing / caching ---

    def _cache_path(self, split: str) -> str:
        tag = "full" if (self.cfg.use_edit and self.cfg.use_subtoken_copy) else (
            f"edit{int(self.cfg.use_edit)}_sub{int(self.cfg.use_subtoken_copy)}"
        )
        geom = f"{self.cfg.sou_len}x{self.cfg.tar_len}x{self.cfg.ast_change_len}x{self.cfg.sub_token_len}"
        # v2: edge_kinds added to the ragged edge storage (typed-edge opt-in)
        return os.path.join(self.cache_dir, f"{split}_{tag}_{geom}_v2.npz")

    def _ensure_processed(self, corpus: Optional[Corpus]) -> None:
        missing = [s for s in self.SPLITS if not os.path.exists(self._cache_path(s))]
        if missing:
            corpus = corpus or Corpus.load(self.data_dir)
            os.makedirs(self.cache_dir, exist_ok=True)
            for split in missing:
                examples = [
                    process_record(
                        corpus.record(i), self.word_vocab,
                        self.ast_change_vocab, self.cfg,
                    )
                    for i in self.split_indices[split]
                ]
                ProcessedSplit.from_examples(examples).save(self._cache_path(split))
        for split in self.SPLITS:
            self.splits[split] = ProcessedSplit.load(self._cache_path(split))
