"""WordPiece-style tokenizer with corpus-built vocab.

The reference ships pretrained BERT vocabularies through its resource-plugin
downloader (reference: core/src/main/java/com/alibaba/alink/common/dl/
BertResources.java:28,76-85). This build runs in a zero-egress environment, so
the tokenizer can (a) load a local vocab file with the standard BERT format,
or (b) build a frequency vocab from the training corpus — greedy
longest-match-first WordPiece with ``##`` continuation, same algorithm family
as the reference's BERT tokenization.

The ids are the published algorithm's (BERT's ``BasicTokenizer`` then
``WordpieceTokenizer``; ``tests/test_tokenizer_corpus.py`` holds the
per-character form as its plain reference and compares with
``transformers.BertTokenizer``). What differs is where the work runs:

- *the character table*: the per-character rules (drop, separate, isolate,
  keep) are applied by one ``str.translate`` through ``_CharTable``, a dict
  that classifies a code point with this file's predicates the first time a
  text holds it and keeps the answer, so the pass over a text's characters
  runs in C and the rules are written once;
- *the word memo*: ``encode_batch`` looks each distinct word of one call up
  in the vocabulary once (``word -> ids``, a dict that is dropped when the
  call returns: no text and no id outlives a call), and counts
  ``tokenizer.words`` and ``tokenizer.word_memo_hits`` once per call.
  ``encode_slices`` is the same call handed out a run of rows at a time, so
  that a caller can start on the first rows while the rest are encoded;
  the memo and the counts stay the whole call's.
"""

from __future__ import annotations

import collections
import re
import sys
import unicodedata
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..common.metrics import metrics

PAD, UNK, CLS, SEP, MASK = "[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"
_SPECIALS = [PAD, UNK, CLS, SEP, MASK]


def _is_punctuation(ch: str) -> bool:
    cp = ord(ch)
    # ASCII non-alnum blocks count as punctuation (BERT convention, so that
    # e.g. "$" and "`" split even though unicodedata calls them symbols)
    if (33 <= cp <= 47 or 58 <= cp <= 64 or 91 <= cp <= 96 or
            123 <= cp <= 126):
        return True
    return unicodedata.category(ch).startswith("P")


def _is_cjk(cp: int) -> bool:
    return (0x4E00 <= cp <= 0x9FFF or 0x3400 <= cp <= 0x4DBF or
            0x20000 <= cp <= 0x2A6DF or 0x2A700 <= cp <= 0x2B73F or
            0x2B740 <= cp <= 0x2B81F or 0x2B820 <= cp <= 0x2CEAF or
            0xF900 <= cp <= 0xFAFF or 0x2F800 <= cp <= 0x2FA1F)


class _CharTable(dict):
    """``str.translate`` table of BERT's basic tokenization, filled the first
    time a code point is met: ``None`` drops it (control characters, U+0000,
    U+FFFD, and combining marks where accents are stripped), a space
    separates words, a character between spaces stands alone (CJK,
    punctuation), and any other maps to itself."""

    def __init__(self, strip_marks: bool):
        super().__init__()
        self.strip_marks = strip_marks

    def __missing__(self, cp: int):
        ch = chr(cp)
        cat = unicodedata.category(ch)
        # whitespace first: \t \n \r are category Cc but BERT treats them
        # as word separators, not strippable control chars
        if ch.isspace():
            to = " "
        elif (cp == 0 or cp == 0xFFFD or cat.startswith("C")
              or (self.strip_marks and cat == "Mn")):
            to = None
        elif _is_cjk(cp) or _is_punctuation(ch):
            to = f" {ch} "
        else:
            to = cp
        self[cp] = to
        return to


# what a code point maps to depends on nothing but the Unicode database, so
# the two tables (accents kept, accents stripped) are the process's; a thread
# that fills an entry another is filling writes the same value
_CHAR_TABLES = (_CharTable(strip_marks=False), _CharTable(strip_marks=True))


def _basic_tokens(text: str, do_lower_case: bool = True) -> List[str]:
    """BERT basic tokenization: clean control chars, isolate CJK chars,
    optionally lowercase + strip accents, split on punctuation."""
    if do_lower_case:
        text = unicodedata.normalize("NFD", text.lower())
    return text.translate(_CHAR_TABLES[bool(do_lower_case)]).split()


_LEGACY_TOKEN_RE = re.compile(r"\w+|[^\w\s]", re.UNICODE)

# one call's word -> ids. The ids are a tuple because the memo holds them for
# the length of the call: a tuple of ints leaves the garbage collector's lists
# at its first young collection, where a list for each of a batch's tens of
# thousands of distinct words would be promoted to the oldest generation and
# bring on a full collection of the process's heap every few batches
_WordMemo = Dict[str, Tuple[int, ...]]


class Tokenizer:
    def __init__(self, vocab: Dict[str, int], max_input_chars_per_word: int = 64,
                 do_lower_case: bool = True, legacy: bool = False):
        self.vocab = vocab
        self.inv = {i: t for t, i in vocab.items()}
        self.max_chars = max_input_chars_per_word
        self.do_lower_case = do_lower_case
        # pre-round-4 models built their vocab with a \w+ regex (no accent
        # stripping, "_" kept inside words); serving them must keep that
        # behavior or their vocab entries stop matching
        self.legacy = legacy

    # -- construction ------------------------------------------------------
    @staticmethod
    def from_vocab_file(path: str, do_lower_case: bool = True) -> "Tokenizer":
        vocab = {}
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f):
                vocab[line.rstrip("\n")] = i
        return Tokenizer(vocab, do_lower_case=do_lower_case)

    @staticmethod
    def build(texts: Sequence[str], vocab_size: int = 8000) -> "Tokenizer":
        """Frequency vocab: whole words + single chars as fallback pieces."""
        counter: collections.Counter = collections.Counter()
        chars: collections.Counter = collections.Counter()
        for t in texts:
            for w in _basic_tokens(t):
                counter[w] += 1
                chars.update(w)
        vocab = {s: i for i, s in enumerate(_SPECIALS)}
        for ch, _ in chars.most_common():
            if len(vocab) >= vocab_size:
                break
            if ch not in vocab:
                vocab[ch] = len(vocab)
            cont = "##" + ch
            if len(vocab) < vocab_size and cont not in vocab:
                vocab[cont] = len(vocab)
        for w, _ in counter.most_common():
            if len(vocab) >= vocab_size:
                break
            if w not in vocab:
                vocab[w] = len(vocab)
        return Tokenizer(vocab)

    # -- encoding ----------------------------------------------------------
    def _wordpiece(self, word: str) -> List[str]:
        n, vocab = len(word), self.vocab
        if n > self.max_chars:
            return [UNK]
        pieces, start = [], 0
        while start < n:
            end = n
            while start < end:
                sub = word[start:end]
                if start > 0:
                    sub = "##" + sub
                if sub in vocab:
                    break
                end -= 1
            else:
                return [UNK]
            pieces.append(sub)
            start = end
        return pieces

    def _words(self, text: str) -> List[str]:
        return (_LEGACY_TOKEN_RE.findall(text.lower()) if self.legacy
                else _basic_tokens(text, self.do_lower_case))

    def tokenize(self, text: str) -> List[str]:
        out = []
        for w in self._words(text):
            out.extend(self._wordpiece(w))
        return out

    def _piece_ids(self, text: str, memo: _WordMemo,
                   budget: int = sys.maxsize) -> Tuple[List[int], int]:
        """Ids of ``text``'s word pieces, each distinct word looked up once
        per ``memo``, and the number of words looked at. The row stops at
        the word that reaches ``budget``: what follows would be cut off
        anyway."""
        vocab, unk = self.vocab, self.vocab[UNK]
        out: List[int] = []
        n = 0
        for n, w in enumerate(self._words(text), 1):
            ids = memo.get(w)
            if ids is None:
                ids = memo[w] = tuple(
                    [vocab.get(p, unk) for p in self._wordpiece(w)])
            out += ids
            if len(out) >= budget:
                break
        return out, n

    def _encode_row(self, text: str, pair: Optional[str], max_len: int,
                    memo: _WordMemo
                    ) -> Tuple[List[int], int, int]:
        """One row's ids ``[CLS] a... [SEP] b... [SEP]`` (unpadded), the
        length of its first segment with [CLS] and [SEP], and the number of
        words looked up."""
        vocab, unk = self.vocab, self.vocab[UNK]
        cls, sep = vocab.get(CLS, unk), vocab.get(SEP, unk)
        if pair is None:
            a, n = self._piece_ids(text, memo, max_len - 2)
            b: List[int] = []
        else:
            a, n = self._piece_ids(text, memo)
            b, nb = self._piece_ids(pair, memo)
            n += nb
        budget = max_len - 2 - (1 if b else 0)
        if b:
            # longest-first truncation keeps both segments represented
            while len(a) + len(b) > budget:
                (a if len(a) >= len(b) else b).pop()
        else:
            a = a[:budget]
        return [cls] + a + [sep] + (b + [sep] if b else []), len(a) + 2, n

    def encode(
        self,
        text: str,
        pair: Optional[str] = None,
        max_len: int = 128,
    ):
        """Returns (input_ids, attention_mask, token_type_ids), BERT layout:
        [CLS] a... [SEP] b... [SEP], padded to max_len."""
        ids, n_a, _ = self._encode_row(text, pair, max_len, {})
        n = len(ids)
        pad = max_len - n
        return (ids + [self.vocab[PAD]] * pad, [1] * n + [0] * pad,
                [0] * n_a + [1] * (n - n_a) + [0] * pad)

    def encode_batch(
        self, texts: Sequence[str], pairs: Optional[Sequence[str]] = None,
        max_len: int = 128,
    ):
        """Batch encode -> dict of (n, max_len) int32 arrays, each row what
        ``encode`` gives. Every distinct word of the call meets the
        vocabulary once (the memo lives as long as the call)."""
        enc, = self.encode_slices(texts, pairs, max_len,
                                  rows=max(len(texts), 1))
        return enc

    def encode_slices(
        self, texts: Sequence[str], pairs: Optional[Sequence[str]] = None,
        max_len: int = 128, *, rows: int,
    ) -> Iterator[Dict[str, np.ndarray]]:
        """``encode_batch`` a run of ``rows`` rows at a time: yields what
        ``encode_batch`` gives for ``texts[0:rows]``, ``texts[rows:2*rows]``
        and so on (the last run may be shorter; no text, one empty run), each
        encoded when it is asked for. The memo is the whole call's, so a word
        met in one run is not looked up again in a later one, and the two
        counters are added once, before the last run is handed out."""
        n = len(texts)
        pad = self.vocab[PAD]
        memo: _WordMemo = {}
        words = 0
        for s in range(0, max(n, 1), rows):
            m = min(rows, n - s)
            ids = np.full((m, max_len), pad, np.int32)
            mask = np.zeros((m, max_len), np.int32)
            types = np.zeros((m, max_len), np.int32)
            for i in range(m):
                p = pairs[s + i] if pairs is not None else None
                row, n_a, n_words = self._encode_row(
                    str(texts[s + i]), p if p is None else str(p), max_len,
                    memo)
                ids[i, :len(row)] = row
                mask[i, :len(row)] = 1
                types[i, n_a:len(row)] = 1
                words += n_words
            if s + rows >= n:
                metrics.incr("tokenizer.words", words)
                metrics.incr("tokenizer.word_memo_hits", words - len(memo))
            yield {"input_ids": ids, "attention_mask": mask,
                   "token_type_ids": types}

    # -- persistence -------------------------------------------------------
    def to_list(self) -> List[str]:
        return [self.inv[i] for i in range(len(self.inv))]

    @staticmethod
    def from_list(tokens: Sequence[str], do_lower_case: bool = True,
                  legacy: bool = False) -> "Tokenizer":
        return Tokenizer({t: i for i, t in enumerate(tokens)},
                         do_lower_case=do_lower_case, legacy=legacy)

    @property
    def vocab_size(self) -> int:
        return len(self.vocab)
