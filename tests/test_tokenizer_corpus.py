"""Tokenizer coverage on the SHIPPED real-text corpora (their first tier-1
consumers): vocab round-trips (list + file), deterministic vocab builds,
and deterministic batch shapes on data/reviews_unlabeled.txt and
data/sst2_mini.csv; and the parity of the tokenizer's character table and
word memo with the per-character code they replaced and with the published
tokenizer."""

import random
import re
import unicodedata

import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.dl.data import load_reviews, load_sst2, sst2_split
from alink_tpu.dl.tokenizer import (CLS, PAD, SEP, UNK, Tokenizer,
                                    _basic_tokens, _is_cjk, _is_punctuation)

pytestmark = pytest.mark.training


# ---------------------------------------------------------------------------
# corpus loaders
# ---------------------------------------------------------------------------

def test_load_reviews_shape_and_content():
    texts = load_reviews()
    assert len(texts) == 4400
    assert all(isinstance(t, str) and t for t in texts)
    assert load_reviews(limit=16) == texts[:16]


def test_load_sst2_rows_and_labels():
    texts, y = load_sst2()
    assert len(texts) == len(y) > 400
    assert set(np.unique(y)) == {0, 1}
    # quoted commas must survive csv parsing as one text field
    assert all("\n" not in t for t in texts)
    # roughly balanced — the holdout accuracy metric is meaningful
    assert 0.3 < float(y.mean()) < 0.7


def test_sst2_split_deterministic_and_disjoint():
    tr1, try1, ho1, hoy1 = sst2_split(seed=0)
    tr2, try2, ho2, hoy2 = sst2_split(seed=0)
    assert tr1 == tr2 and ho1 == ho2
    assert np.array_equal(try1, try2) and np.array_equal(hoy1, hoy2)
    texts, _ = load_sst2()
    assert len(tr1) + len(ho1) == len(texts)
    assert len(ho1) == max(1, int(len(texts) * 0.2))


# ---------------------------------------------------------------------------
# vocab round-trips
# ---------------------------------------------------------------------------

def test_vocab_roundtrip_list_and_file(tmp_path):
    texts = load_reviews(limit=200)
    tok = Tokenizer.build(texts, vocab_size=500)
    sample = texts[:20]

    # list round-trip (the checkpoint path: save_bert_checkpoint stores
    # to_list(), fine-tune rebuilds via from_list)
    tok2 = Tokenizer.from_list(tok.to_list())
    assert tok2.vocab == tok.vocab
    for t in sample:
        assert tok2.tokenize(t) == tok.tokenize(t)

    # vocab.txt round-trip (the HF-layout file the BERT ops read)
    p = tmp_path / "vocab.txt"
    p.write_text("\n".join(tok.to_list()) + "\n", encoding="utf-8")
    tok3 = Tokenizer.from_vocab_file(str(p))
    assert tok3.vocab == tok.vocab
    for t in sample:
        assert tok3.encode(t, max_len=24) == tok.encode(t, max_len=24)


def test_vocab_build_deterministic():
    texts = load_reviews(limit=300)
    a = Tokenizer.build(texts, vocab_size=400)
    b = Tokenizer.build(texts, vocab_size=400)
    assert a.to_list() == b.to_list()


# ---------------------------------------------------------------------------
# deterministic batch shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("max_len", [16, 32])
def test_encode_batch_shapes_on_corpora(max_len):
    sst_texts, _ = load_sst2()
    texts = sst_texts[:64] + load_reviews(limit=64)
    tok = Tokenizer.build(texts, vocab_size=600)
    enc = tok.encode_batch(texts, max_len=max_len)
    assert sorted(enc) == ["attention_mask", "input_ids", "token_type_ids"]
    for k, arr in enc.items():
        assert arr.shape == (len(texts), max_len), k
        assert arr.dtype == np.int32, k
    ids, mask = enc["input_ids"], enc["attention_mask"]
    assert set(np.unique(mask)) <= {0, 1}
    # layout: [CLS] first, ids outside the mask are all [PAD], real tokens
    # never exceed the vocab
    assert (ids[:, 0] == tok.vocab[CLS]).all()
    assert (ids[mask == 0] == tok.vocab[PAD]).all()
    assert ids.max() < tok.vocab_size
    # every row ends its masked span with [SEP] (truncation keeps it)
    last = mask.sum(axis=1) - 1
    assert (ids[np.arange(len(texts)), last] == tok.vocab[SEP]).all()
    # determinism: the same corpus encodes to the same blocks
    enc2 = tok.encode_batch(texts, max_len=max_len)
    for k in enc:
        assert np.array_equal(enc[k], enc2[k]), k


# ---------------------------------------------------------------------------
# parity of the character table and the word memo with the per-character
# tokenizer they replaced, kept here as the plain reference
# ---------------------------------------------------------------------------

def _ref_basic_tokens(text, do_lower_case=True):
    """BERT's basic tokenization one character at a time, as
    ``dl/tokenizer.py`` had it before its character table."""
    if do_lower_case:
        text = text.lower()
        text = "".join(ch for ch in unicodedata.normalize("NFD", text)
                       if unicodedata.category(ch) != "Mn")
    out, word = [], []

    def flush():
        if word:
            out.append("".join(word))
            word.clear()

    for ch in text:
        if ch.isspace():
            flush()
            continue
        cp = ord(ch)
        if cp == 0 or cp == 0xFFFD or unicodedata.category(ch).startswith("C"):
            continue
        if _is_cjk(cp) or _is_punctuation(ch):
            flush()
            out.append(ch)
        else:
            word.append(ch)
    flush()
    return out


def _ref_wordpiece(tok, word):
    if len(word) > tok.max_chars:
        return [UNK]
    pieces, start = [], 0
    while start < len(word):
        end, cur = len(word), None
        while start < end:
            sub = ("##" if start else "") + word[start:end]
            if sub in tok.vocab:
                cur = sub
                break
            end -= 1
        if cur is None:
            return [UNK]
        pieces.append(cur)
        start = end
    return pieces


def _ref_tokenize(tok, text):
    words = (re.findall(r"\w+|[^\w\s]", text.lower()) if tok.legacy
             else _ref_basic_tokens(text, tok.do_lower_case))
    return [p for w in words for p in _ref_wordpiece(tok, w)]


def _ref_encode(tok, text, pair=None, max_len=128):
    a = _ref_tokenize(tok, text)
    b = _ref_tokenize(tok, pair) if pair is not None else []
    budget = max_len - 2 - (1 if b else 0)
    if b:
        while len(a) + len(b) > budget:
            (a if len(a) >= len(b) else b).pop()
    else:
        a = a[:budget]
    toks = [CLS] + a + [SEP] + (b + [SEP] if b else [])
    types = [0] * (len(a) + 2) + [1] * (len(b) + 1 if b else 0)
    ids = [tok.vocab.get(t, tok.vocab[UNK]) for t in toks]
    pad = max_len - len(ids)
    return (ids + [tok.vocab[PAD]] * pad, [1] * len(ids) + [0] * pad,
            types + [0] * pad)


_KEYS = ("input_ids", "attention_mask", "token_type_ids")


def _ref_encode_batch(tok, texts, pairs=None, max_len=128):
    rows = [_ref_encode(tok, t, p, max_len)
            for t, p in zip(texts, pairs or [None] * len(texts))]
    return {k: np.asarray([r[j] for r in rows], np.int32).reshape(
                len(texts), max_len)
            for j, k in enumerate(_KEYS)}


def _same_arrays(got, want):
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype == np.int32, k
        assert got[k].shape == want[k].shape, k
        assert np.array_equal(got[k], want[k]), k


_LATIN = [chr(c) for c in range(0x250)]        # ASCII with every control
#                                                character, Latin-1, Extended
_MARKS = [chr(c) for c in range(0x300, 0x370)]
_ODD = (list("你好世界㐀豈𠀀") + ["�", " ", " ", "　", " ",
                                  "​", "‌", "‍", "﻿",
                                  "­", "\ud800", "", "İ", "ẞ"])
_WORDS = ["the", "quick", "brown", "fox", "jumped", "Unbelievable", "juggs",
          "20", "Café", "dog", "naïve", "a" * 70, "b" * 101]
# U+000B, U+000C, U+001C-1F and U+0085 are white space to Python and control
# characters to the published tokenizer, which drops them where this one (like
# the code it replaced) starts a new word
_SEPARATOR_CONTROLS = "\x0b\x0c\x1c\x1d\x1e\x1f\x85"
_SMALL_VOCAB = (
    [PAD, UNK, CLS, SEP, "[MASK]", "the", "quick", "brown", "fox", "jump",
     "##ed", "##s", "over", "lazy", "dog", "un", "##believ", "##able", ",",
     ".", "!", "?", "'", "s", "##gg", "ju", "2", "##0", "你", "好", "-",
     "é", "##é", "ß", "##ß", "A", "B", "##A", "The", "Fox", "cafe", "naive"]
    + list("abcdefghijklmnopqrstuvwxyz")
    + ["##" + c for c in "abcdefghijklmnopqrstuvwxyz"])


def _fuzz_strings(seed, n, alphabet, max_chars=48):
    rng = random.Random(seed)
    pool = alphabet + [" "] * (len(alphabet) // 8) + _WORDS
    return ["".join(rng.choice(pool) for _ in range(rng.randint(0, max_chars)))
            for _ in range(n)]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("lower", [True, False])
def test_character_table_equals_the_per_character_pass(lower, seed):
    for s in _fuzz_strings(seed, 1000, _LATIN + _MARKS + _ODD):
        assert _basic_tokens(s, lower) == _ref_basic_tokens(s, lower), repr(s)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("lower", [True, False])
def test_tokenize_matches_published_tokenizer_on_fuzz(tmp_path, lower, seed):
    transformers = pytest.importorskip("transformers")
    p = tmp_path / "vocab.txt"
    p.write_text("\n".join(_SMALL_VOCAB) + "\n", encoding="utf-8")
    theirs = transformers.BertTokenizer(str(p), do_lower_case=lower)
    # the published default for a word's longest length; this file's is 64
    ours = Tokenizer({t: i for i, t in enumerate(_SMALL_VOCAB)},
                     max_input_chars_per_word=100, do_lower_case=lower)
    # cased, the published tokenizer composes a letter and its mark (NFC)
    # and this one keeps them apart, so marks are fuzzed uncased only
    alphabet = [c for c in _LATIN + _ODD + (_MARKS if lower else [])
                if c not in _SEPARATOR_CONTROLS and c != "\ud800"]
    for s in _fuzz_strings(seed, 1500, alphabet):
        got = ours.tokenize(s)
        assert got == theirs.tokenize(s), repr(s)
        assert got == _ref_tokenize(ours, s), repr(s)


def _budget_texts(tok, max_len):
    """Single texts shorter than, equal to and longer than ``max_len``'s
    budget of pieces, and the odd ones."""
    word = "fox "                                   # one piece a word
    budget = max_len - 2
    texts = [word * (budget - 1), word * budget, word * (budget + 1),
             word * (3 * budget), "", "   ", "a" * 65, "x" * 64 + " fox",
             "Unbelievable, the fox jumps... over?", "juggs 20 你好 Café-dog",
             "\x00�​", "fox" + "!" * (budget + 5)]
    assert len(tok.tokenize(texts[1])) == budget
    return texts + _fuzz_strings(max_len, 40, _LATIN + _MARKS + _ODD,
                                 max_chars=4 * max_len)


@pytest.mark.parametrize("max_len", [16, 128, 512])
@pytest.mark.parametrize("kind", ["single", "pair", "legacy"])
def test_encode_batch_equals_encode_row_by_row(kind, max_len):
    tok = Tokenizer.from_list(_SMALL_VOCAB, legacy=kind == "legacy")
    texts = _budget_texts(tok, max_len)
    # a pair's segments: both short, one long, both long, one empty
    pairs = texts[::-1] if kind == "pair" else None
    enc = tok.encode_batch(texts, pairs, max_len=max_len)
    _same_arrays(enc, _ref_encode_batch(tok, texts, pairs, max_len))
    for i, (t, p) in enumerate(zip(texts, pairs or [None] * len(texts))):
        row = tok.encode(t, p, max_len)
        assert row == _ref_encode(tok, t, p, max_len), repr(t)
        assert all(isinstance(v, int) for part in row for v in part)
        for j, k in enumerate(_KEYS):
            assert enc[k][i].tolist() == row[j], (k, repr(t))
    assert tok.encode_batch([], max_len=max_len)["input_ids"].shape == (
        0, max_len)


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_encode_batch_on_the_benchmark_documents(seed):
    from benchmark import gen

    vocab = gen.make_vocab(2000)
    docs, _ = gen.make_documents(seed, 24, 128, vocab)
    tok = Tokenizer.from_list(vocab)
    assert min(len(tok.tokenize(d)) for d in docs) > 126     # every row is cut
    _same_arrays(tok.encode_batch(docs, max_len=128),
                 _ref_encode_batch(tok, docs, max_len=128))


def _word_counters():
    return (metrics.counter("tokenizer.words"),
            metrics.counter("tokenizer.word_memo_hits"))


@pytest.mark.parametrize("texts,pairs,max_len,words,hits", [
    (["the fox, the dog", "the fox"], None, 16, 7, 3),
    # a row stops at the word that fills its budget: the tail is not looked up
    (["fox " * 40], None, 16, 14, 13),
    # a pair is tokenised whole, then cut longest first
    (["fox " * 20], ["dog " * 20], 16, 40, 38),
    ([], None, 16, 0, 0),
])
def test_word_counters_count_once_a_call(texts, pairs, max_len, words, hits):
    tok = Tokenizer.from_list(_SMALL_VOCAB)
    w0, h0 = _word_counters()
    tok.encode_batch(texts, pairs, max_len=max_len)
    w1, h1 = _word_counters()
    assert (w1 - w0, h1 - h0) == (words, hits)


def test_word_memo_does_not_outlive_a_call():
    tok = Tokenizer.from_list(_SMALL_VOCAB)
    first = tok.encode_batch(["the fox"], max_len=8)
    w0, h0 = _word_counters()
    # nothing is remembered from the call before: no hit, and a vocabulary
    # that changed between two calls is the one the second call reads
    del tok.vocab["fox"]
    second = tok.encode_batch(["the fox"], max_len=8)
    w1, h1 = _word_counters()
    assert (w1 - w0, h1 - h0) == (2, 0)
    assert not np.array_equal(first["input_ids"], second["input_ids"])
    _same_arrays(second, _ref_encode_batch(tok, ["the fox"], max_len=8))
    assert not [v for v in vars(tok).values()
                if isinstance(v, dict) and "the" in v and v is not tok.vocab]


# ---------------------------------------------------------------------------
# encode_slices: encode_batch handed out a run of rows at a time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1, 7, 64, 1000])
@pytest.mark.parametrize("paired", [False, True])
def test_encode_slices_concatenated_equal_encode_batch(paired, rows):
    texts = load_reviews(limit=150)
    pairs = load_sst2()[0][:150] if paired else None
    tok = Tokenizer.build(texts, vocab_size=600)
    w0, h0 = _word_counters()
    whole = tok.encode_batch(texts, pairs, max_len=48)
    w1, h1 = _word_counters()
    assert w1 - w0 > h1 - h0 > 0          # words repeat inside the call
    slices = tok.encode_slices(texts, pairs, max_len=48, rows=rows)
    first = next(slices)                  # nothing is encoded before it is asked
    assert _word_counters() == ((w1, h1) if rows < 150 else
                                (2 * w1 - w0, 2 * h1 - h0))
    got = [first] + list(slices)
    assert [len(g["input_ids"]) for g in got] \
        == [min(rows, 150 - s) for s in range(0, 150, rows)]
    _same_arrays({k: np.concatenate([g[k] for g in got]) for k in whole}, whole)
    # one memo for the whole call: the counters grow as one whole call's do
    w2, h2 = _word_counters()
    assert (w2 - w1, h2 - h1) == (w1 - w0, h1 - h0)


def test_encode_slices_of_no_text_is_one_empty_run():
    tok = Tokenizer.from_list(_SMALL_VOCAB)
    (only,) = tok.encode_slices([], max_len=8, rows=4)
    assert only["input_ids"].shape == (0, 8)
