"""Vocabulary: token <-> int mapping with frequency-based construction.

Equivalent of fastai's ``Vocab`` as used by the reference's DataBunch build
(`02_fastai_DataBunch.ipynb` cells 10-15; defaults max_vocab=60000,
min_freq=2). Serialized as plain JSON instead of a pickle so artifacts are
language-neutral (loadable from the C++ runtime and the Go control plane).
"""

from __future__ import annotations

import hashlib
import json
from collections import Counter
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Union

import numpy as np

from code_intelligence_tpu.text import rules as R

PathLike = Union[str, Path]


class Vocab:
    def __init__(self, itos: Sequence[str]):
        self.itos: List[str] = list(itos)
        self.stoi: Dict[str, int] = {tok: i for i, tok in enumerate(self.itos)}
        if R.TK_UNK not in self.stoi:
            raise ValueError(f"vocab must contain {R.TK_UNK!r}")
        self.unk_id = self.stoi[R.TK_UNK]
        self.pad_id = self.stoi.get(R.TK_PAD, self.unk_id)
        self.bos_id = self.stoi.get(R.TK_BOS, self.unk_id)
        self.eos_id = self.stoi.get(R.TK_EOS, self.unk_id)

    def __len__(self) -> int:
        return len(self.itos)

    @classmethod
    def build(
        cls,
        tokenized_docs: Iterable[Sequence[str]],
        max_vocab: int = 60000,
        min_freq: int = 2,
    ) -> "Vocab":
        counts: Counter = Counter()
        for doc in tokenized_docs:
            counts.update(doc)
        return cls.from_counts(counts, max_vocab=max_vocab, min_freq=min_freq)

    @classmethod
    def from_counts(
        cls,
        counts: "Counter[str]",
        max_vocab: int = 60000,
        min_freq: int = 2,
    ) -> "Vocab":
        """Most-frequent-first vocab with all special tokens pinned to the
        lowest ids (fastai semantics: specials first, then by frequency)."""
        itos = list(R.SPECIALS)
        seen = set(itos)
        for tok, c in counts.most_common():
            if len(itos) >= max_vocab:
                break
            if c < min_freq or tok in seen:
                continue
            itos.append(tok)
            seen.add(tok)
        return cls(itos)

    def numericalize(self, tokens: Sequence[str]) -> np.ndarray:
        unk = self.unk_id
        return np.asarray([self.stoi.get(t, unk) for t in tokens], dtype=np.int32)

    def textify(self, ids: Sequence[int]) -> List[str]:
        return [self.itos[int(i)] for i in ids]

    def content_hash(self) -> str:
        """Order-sensitive content hash of the id→token table. Two vocabs
        that numericalize ANY document differently hash differently, so
        the serving cache key (serving/embed_cache.py) can never alias
        token ids across exports — even when two exports carry identical
        ``version`` strings."""
        h = hashlib.blake2b(digest_size=8)
        for tok in self.itos:
            h.update(tok.encode("utf-8", "replace"))
            h.update(b"\x00")
        return h.hexdigest()

    # -- persistence --------------------------------------------------------

    def save(self, path: PathLike) -> None:
        Path(path).write_text(json.dumps({"itos": self.itos}))

    @classmethod
    def load(cls, path: PathLike) -> "Vocab":
        """The vocabulary ``save`` wrote: a table of tokens, or the byte
        vocabulary where the file says ``bytes``."""
        data = json.loads(Path(path).read_text())
        if "bytes" in data:
            return ByteVocab(**data["bytes"])
        return cls(data["itos"])


class ByteVocab:
    """A vocabulary that is not a table: a byte model reads the UTF-8
    bytes of the text, so there is nothing to look up and no file of
    merges. Ids ``0 .. n_special - 1`` are the model's specials (``<pad>``
    0, ``<bos>`` 1; a byte model spells every other mark of the product
    out), byte ``b`` is id ``b + n_special``.

    ``numericalize`` takes what ``Vocab``'s takes, the program's own
    token sequence (pre-rules, word split, post-rules), so the product's
    field and markdown marks stay what the encoder reads: ``xxbos``
    becomes ``<bos>``, every other token is spelled in UTF-8 with one
    0x20 between neighbours. A document is then about five positions a
    word."""

    pad_id, bos_id = 0, 1

    def __init__(self, n_special: int = 64):
        if n_special < 2:
            raise ValueError("<pad> and <bos> need two special ids")
        self.n_special = int(n_special)

    def __len__(self) -> int:
        return self.n_special + 256

    def numericalize(self, tokens: Sequence[str]) -> np.ndarray:
        out, run = [], []

        def spell():
            if run:
                text = np.frombuffer(" ".join(run).encode(
                    "utf-8", "replace"), np.uint8)
                out.append(text.astype(np.int32) + self.n_special)
                run.clear()

        for tok in tokens:
            if tok == R.TK_BOS:
                spell()
                out.append(np.asarray([self.bos_id], np.int32))
            else:
                run.append(tok)
        spell()
        return np.concatenate(out) if out else np.zeros((0,), np.int32)

    def content_hash(self) -> str:
        """As ``Vocab.content_hash``: differs from every table's (the
        first entry spells no token a table can hold) and between byte
        vocabularies of different offsets."""
        h = hashlib.blake2b(digest_size=8)
        h.update(b"\x00bytes\x00%d" % self.n_special)
        return h.hexdigest()

    def save(self, path: PathLike) -> None:
        Path(path).write_text(json.dumps(
            {"bytes": {"n_special": self.n_special}}))

    load = Vocab.load
