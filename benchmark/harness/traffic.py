"""The one traffic generator: every mix is a data file of parameters that
this module turns into inputs, from ``--seed``.

Two kinds of input exist so far, chosen by the mix's ``kind``:

* ``documents`` — GitHub-shaped issues (title + markdown body) written
  over a fixed pseudo-word vocabulary, so the program's own tokeniser
  does real work on them. Each call's documents have the SAME multiset of
  token lengths (the quantile grid of the mix's length distribution),
  in an order drawn from the seed: every seed does the same amount of
  work. Each document carries the token ids it must tokenise to, built
  here from the plan and not read back from the program, so the
  correctness check also covers the tokeniser.
* ``token_stream`` — a Zipf-distributed LM corpus cut into the
  ``(windows, rows, bptt)`` feed of the trainer.

The vocabulary is fixed (not seeded): the program's specials first (its
wire contract), then punctuation, then consonant-vowel pseudo-words that
survive the tokeniser's rules as one token each.
"""

from __future__ import annotations

import itertools
import math
import statistics
from typing import Dict, List, Sequence

import numpy as np

PUNCT = [".", ":", ","]
_CONS = "bcdfghjklmnprstvwz"
_VOWS = "aeiou"


def vocab_words(specials: Sequence[str], vocab_size: int) -> List[str]:
    """``vocab_size`` entries: specials, punctuation, pseudo-words."""
    syl = [c + v for c in _CONS for v in _VOWS]
    words: List[str] = list(specials) + PUNCT
    need = vocab_size - len(words)
    if need < 1:
        raise ValueError(f"vocab_size {vocab_size} leaves no room for words")
    for n in (2, 3, 4):
        for combo in itertools.product(syl, repeat=n):
            if need == 0:
                return words
            words.append("".join(combo))
            need -= 1
    raise ValueError(f"vocab_size {vocab_size} exceeds the word supply")


def _norm_ppf(q: float) -> float:
    return statistics.NormalDist().inv_cdf(q)


def length_grid(length: dict, n: int) -> np.ndarray:
    """The fixed multiset of ``n`` token lengths: the distribution's
    ``(i + 0.5) / n`` quantiles, clipped to ``[min, max]``."""
    if length["dist"] == "lognormal":
        vals = [length["median"] * math.exp(
            length["sigma"] * _norm_ppf((i + 0.5) / n)) for i in range(n)]
    elif length["dist"] == "uniform":
        lo, hi = length["min"], length["max"]
        vals = [lo + (hi - lo) * (i + 0.5) / n for i in range(n)]
    elif length["dist"] == "fixed":
        vals = [length["value"]] * n
    else:
        raise ValueError(f"unknown length distribution {length['dist']!r}")
    return np.clip(np.rint(vals), length["min"], length["max"]).astype(
        np.int64)


def _zipf_p(n: int, words: dict) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = 1.0 / np.power(ranks + words.get("zipf_shift", 2.7),
                       words.get("zipf_exponent", 1.07))
    return p / p.sum()


def _no_adjacent_repeats(ids: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Bump any id equal to its predecessor (the tokeniser folds a word
    said three times into ``xxwrep``; no run may start)."""
    ids = ids.copy()
    while True:
        dup = np.flatnonzero(ids[1:] == ids[:-1]) + 1
        if dup.size == 0:
            return ids
        ids[dup] = lo + (ids[dup] - lo + 1) % (hi - lo)


class DocumentPlan:
    """Turns a target token length into text and the ids it must
    tokenise to. The structure is a function of the length alone (title
    size, sentences, list items, inline code, links, one fenced block),
    so a length always costs the tokeniser the same; only the words are
    drawn from the seed."""

    def __init__(self, vocab: Sequence[str]):
        self.vocab = list(vocab)
        self.stoi = {w: i for i, w in enumerate(self.vocab)}
        self.first_word = self.stoi[PUNCT[-1]] + 1
        self.bos = self.stoi["xxbos"]
        self.fld_title = self.stoi["xxxfldtitle"]
        self.fld_body = self.stoi["xxxfldbody"]
        self.lst = self.stoi["xxlst"]
        self.cdi = self.stoi["xxcdi"]
        self.lnk = self.stoi["xxlnk"]
        self.cdb = self.stoi["xxcdb"]
        self.dot = self.stoi["."]
        self.colon = self.stoi[":"]

    @staticmethod
    def title_len(length: int) -> int:
        return max(0, min(4 + length % 9, (length - 3) // 2))

    def build(self, length: int, word_ids: np.ndarray) -> dict:
        """``word_ids``: at least ``length`` ids of words to draw on."""
        if length < 3:
            raise ValueError("a document is at least bos + two field marks")
        w = iter(word_ids.tolist())
        voc = self.vocab
        ids = [self.bos, self.fld_title]
        n_title = self.title_len(length)
        title_ids = [next(w) for _ in range(n_title)]
        ids += title_ids
        ids.append(self.fld_body)
        left = length - 3 - n_title
        parts: List[str] = []
        k = 0
        while left > 0:
            if k == 3 and left >= 8:
                # one fenced block: its payload is dropped by the rules
                payload = " ".join(voc[next(w)] for _ in range(6))
                parts.append(f"\n\n```\n{payload}\n```\n\n")
                ids.append(self.cdb)
                left -= 1
            elif k % 6 == 4 and left >= 3:
                n = min(6, left - 1)
                item = [next(w) for _ in range(n)]
                parts.append("\n- " + " ".join(voc[i] for i in item) + "\n")
                ids.append(self.lst)
                ids += item
                left -= n + 1
            elif k % 6 == 2 and left >= 6:
                n = min(8, left - 2)
                sent = [next(w) for _ in range(n)]
                parts.append(" ".join(voc[i] for i in sent)
                             + f" `{voc[next(w)]}` . ")
                ids += sent + [self.cdi, self.dot]
                left -= n + 2
            elif k % 6 == 5 and left >= 4:
                n = min(5, left - 2)
                sent = [next(w) for _ in range(n)]
                parts.append(" ".join(voc[i] for i in sent)
                             + f" : https://example.com/{voc[next(w)]} ")
                ids += sent + [self.colon, self.lnk]
                left -= n + 2
            else:
                n = min(11, left - 1) if left > 1 else 0
                sent = [next(w) for _ in range(n)]
                parts.append(" ".join(voc[i] for i in sent) + " . ")
                ids += sent + [self.dot]
                left -= n + 1
            k += 1
        return {"title": " ".join(voc[i] for i in title_ids),
                "body": "".join(parts).strip(),
                "ids": np.asarray(ids, np.int32)}


def make_document_calls(mix: dict, vocab: Sequence[str], seed: int,
                        n_calls: int, stream: int = 0) -> List[List[dict]]:
    """``n_calls`` lists of ``docs_per_call`` documents. ``stream``
    separates the warm-up's documents from the window's."""
    rng = np.random.default_rng([int(seed), 0xD0C5, int(stream)])
    plan = DocumentPlan(vocab)
    grid = length_grid(mix["length"], int(mix["docs_per_call"]))
    n_words = len(vocab) - plan.first_word
    p = _zipf_p(n_words, mix.get("words", {}))
    cdf = np.cumsum(p)
    calls = []
    for _ in range(n_calls):
        lengths = rng.permutation(grid)
        # +8 a document: the fenced payload and inline extras draw words
        # that yield no token
        draw = rng.random(int(lengths.sum()) + 8 * len(lengths))
        ids = np.minimum(np.searchsorted(cdf, draw), n_words - 1) \
            + plan.first_word
        ids = _no_adjacent_repeats(ids, plan.first_word, len(vocab))
        docs, off = [], 0
        for length in lengths.tolist():
            docs.append(plan.build(length, ids[off:off + length + 8]))
            off += length + 8
        calls.append(docs)
    return calls


def make_token_stream(mix: dict, vocab_size: int, n_special: int,
                      bos_id: int, seed: int, rows: int, bptt: int,
                      windows: int, stream: int = 0) -> Dict[str, np.ndarray]:
    """``{"x", "y"}`` of shape ``(windows, rows, bptt)``: a Zipf corpus
    of seeded documents (each opening with ``bos``), laid out as ``rows``
    contiguous streams the way an LM loader cuts it; ``y`` is ``x``
    shifted by one token."""
    rng = np.random.default_rng([int(seed), 0x70CE, int(stream)])
    words = mix.get("words", {})
    n_words = vocab_size - n_special
    cdf = np.cumsum(_zipf_p(n_words, words))
    per_row = windows * bptt + 1
    total = rows * per_row
    ids = np.minimum(np.searchsorted(cdf, rng.random(total)),
                     n_words - 1).astype(np.int32) + n_special
    lo, hi = mix.get("doc_tokens", [40, 400])
    pos = 0
    while pos < total:
        ids[pos] = bos_id
        pos += int(rng.integers(lo, hi))
    stream_rows = ids.reshape(rows, per_row)
    x = stream_rows[:, :-1].reshape(rows, windows, bptt).transpose(1, 0, 2)
    y = stream_rows[:, 1:].reshape(rows, windows, bptt).transpose(1, 0, 2)
    return {"x": np.ascontiguousarray(x), "y": np.ascontiguousarray(y)}


def length_quartiles(lengths: Sequence[int]) -> dict:
    q1, q2, q3 = statistics.quantiles(list(lengths), n=4)
    return {"n": len(lengths), "min": int(min(lengths)), "q1": q1,
            "median": q2, "q3": q3, "max": int(max(lengths)),
            "mean": float(np.mean(lengths))}
