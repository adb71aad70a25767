"""Text-layer tests: pre-rules, document contract, tokenizer, vocab.

Modeled on the reference's pure-function table tests
(`py/code_intelligence/util_test.py:6-29`) and the doc-builder golden test
(`py/code_intelligence/github_util_test.py:47-55`).
"""

import numpy as np
import pytest

from code_intelligence_tpu.text import (
    SPECIALS,
    TK_BOS,
    TK_MAJ,
    TK_UNK,
    TK_UP,
    Tokenizer,
    Vocab,
    build_issue_text,
    pre_process,
    tokenize_texts,
)
from code_intelligence_tpu.text import rules as R


class TestPreRules:
    def test_fenced_code_block_replaced(self):
        out = pre_process("before\n```python\nx = 1\n```\nafter")
        assert R.TK_CODE_BLOCK in out
        assert "x = 1" not in out

    def test_inline_code_replaced(self):
        out = pre_process("run `pip install foo` first")
        assert R.TK_CODE_INLINE in out
        assert "pip install" not in out

    def test_link_keeps_anchor_text(self):
        out = pre_process("see [the docs](https://example.com/x) here")
        assert R.TK_LINK in out
        assert "the docs" in out
        assert "example.com" not in out

    def test_bare_url_replaced(self):
        out = pre_process("at https://example.com/path?q=1 end")
        assert R.TK_LINK in out
        assert "example.com" not in out

    def test_image_marker(self):
        assert R.TK_IMAGE in pre_process("![screenshot](http://x.png)")

    def test_char_repetition(self):
        out = pre_process("loooooong")
        assert R.TK_REP in out

    def test_word_repetition(self):
        out = pre_process("why why why why")
        assert R.TK_WREP in out and "4" in out

    def test_html_entities_fixed(self):
        assert "&amp;" not in pre_process("a &amp; b")

    def test_spec_add_spaces(self):
        toks = Tokenizer(add_bos=False).tokenize("kind/bug #123 @user")
        assert "kind" in toks and "/" in toks and "bug" in toks

    def test_non_string_input(self):
        assert pre_process(None) == ""


class TestDocumentContract:
    def test_field_markers_byte_identical(self):
        # The reference's exact contract: inference.py:118.
        out = build_issue_text("My Title", "My body.")
        assert out.startswith("xxxfldtitle ")
        assert " xxxfldbody " in out

    def test_golden(self):
        out = build_issue_text("Add GPU support", "Please add it")
        assert (
            out == "xxxfldtitle Add GPU support xxxfldbody Please add it"
        ), out


class TestTokenizer:
    def test_bos_prepended(self):
        assert Tokenizer().tokenize("hello world")[0] == TK_BOS

    def test_caps_factoring(self):
        toks = Tokenizer(add_bos=False).tokenize("Hello WORLD")
        assert toks == [TK_MAJ, "hello", TK_UP, "world"]

    def test_deterministic(self):
        t = Tokenizer()
        s = "The quick brown fox jumped over `the lazy dog` #42."
        assert t.tokenize(s) == t.tokenize(s)

    def test_contraction_split(self):
        toks = Tokenizer(add_bos=False).tokenize("don't panic")
        assert toks[:2] == ["don", "'t"]

    def test_parallel_matches_serial(self):
        texts = [f"Issue number {i} has a **bold** claim" for i in range(40)]
        serial = tokenize_texts(texts, n_workers=0)
        par = tokenize_texts(texts, n_workers=2, chunksize=8)
        assert serial == par


class TestVocab:
    def _docs(self):
        return [["a", "b", "a"], ["a", "c"], ["b", "a"]]

    def test_specials_first(self):
        v = Vocab.build(self._docs(), min_freq=1)
        assert v.itos[: len(SPECIALS)] == SPECIALS

    def test_frequency_order(self):
        v = Vocab.build(self._docs(), min_freq=1)
        tail = v.itos[len(SPECIALS) :]
        assert tail == ["a", "b", "c"]

    def test_min_freq(self):
        v = Vocab.build(self._docs(), min_freq=2)
        assert "c" not in v.stoi

    def test_numericalize_roundtrip(self):
        v = Vocab.build(self._docs(), min_freq=1)
        ids = v.numericalize(["a", "zzz", "b"])
        assert ids.dtype == np.int32
        assert v.textify(ids) == ["a", TK_UNK, "b"]

    def test_save_load(self, tmp_path):
        v = Vocab.build(self._docs(), min_freq=1)
        v.save(tmp_path / "v.json")
        v2 = Vocab.load(tmp_path / "v.json")
        assert v2.itos == v.itos and v2.unk_id == v.unk_id


class TestReviewRegressions:
    """Regressions from the round-1 code review."""

    def test_issue_ref_not_a_heading(self):
        out = pre_process("#1234 crashes on start")
        assert R.TK_HEADING not in out and "1234" in out

    def test_real_heading_still_marked(self):
        assert R.TK_HEADING in pre_process("# Overview\ntext")

    def test_snake_case_survives_emphasis(self):
        assert pre_process("use convert_to_json here") == "use convert_to_json here"

    def test_emphasis_still_stripped(self):
        out = pre_process("a **bold** claim")
        assert "bold" in out and "*" not in out

    def test_br_becomes_break_not_marker(self):
        out = pre_process("line1<br />line2")
        assert "line1" in out and "line2" in out and R.TK_HTML_BLOCK not in out

    def test_unicode_words_whole(self):
        assert Tokenizer(add_bos=False).tokenize("héllo wörld") == ["héllo", "wörld"]

    def test_unclosed_fence_swallowed(self):
        out = pre_process("```python\nsecret_code = 1")
        assert "secret_code" not in out and R.TK_CODE_BLOCK in out


class TestMaxVocab:
    def test_cap_respected(self):
        docs = [[f"tok{i}"] * 3 for i in range(100)]
        v = Vocab.build(docs, max_vocab=len(SPECIALS) + 10, min_freq=1)
        assert len(v) == len(SPECIALS) + 10


# ---------------------------------------------------------------------------
# Exactness of the guarded pre-rules (PR 29): a rule scans a text only where
# its pattern can match, and gives what the unguarded rule gave, always.
# ---------------------------------------------------------------------------
#
# The plain rules below are the rules as they were before the guards, written
# out here with their patterns so that they do not follow an edit to
# ``rules.py``.

import html as _html  # noqa: E402
import random  # noqa: E402
import re  # noqa: E402

_P_FENCED_CODE = re.compile(r"```.*?(?:```|\Z)|~~~.*?(?:~~~|\Z)", re.DOTALL)
_P_INDENT_CODE = re.compile(r"(?:^|\n)(?:(?:    |\t)[^\n]*\n?)+")
_P_INLINE_CODE = re.compile(r"`[^`\n]+`")
_P_IMAGE = re.compile(r"!\[([^\]]*)\]\(([^)]*)\)")
_P_LINK = re.compile(r"\[([^\]]*)\]\(([^)]*)\)")
_P_AUTOLINK = re.compile(r"https?://\S+|www\.\S+")
_P_BR = re.compile(r"<br\s*/?>", re.IGNORECASE)
_P_HTML_TAG = re.compile(r"<[^>\n]+>")
_P_HEADING = re.compile(r"^(#{1,6})(?:[ \t]+|$)", re.MULTILINE)
_P_QUOTE = re.compile(r"^\s{0,3}>\s?", re.MULTILINE)
_P_LIST = re.compile(r"^\s{0,3}(?:[-*+]|\d+[.)])\s+", re.MULTILINE)
_P_HRULE = re.compile(r"^\s{0,3}(?:-{3,}|\*{3,}|_{3,})\s*$", re.MULTILINE)
_P_EMPHASIS = re.compile(r"(?<!\w)(\*{1,3}|_{1,3})(?=\S)(.+?)(?<=\S)\1(?!\w)")
_P_REP = re.compile(r"(\S)(\1{3,})")
_P_WREP = re.compile(r"(?:^|\s)(\S+)((?:\s+\1){3,})\b")
_P_SPACE = re.compile(r" {2,}")


def _plain_md_code_blocks(t):
    t = _P_FENCED_CODE.sub(" xxcdb ", t)
    return _P_INDENT_CODE.sub("\n xxcdb \n", t)


def _plain_md_inline_code(t):
    return _P_INLINE_CODE.sub(" xxcdi ", t)


def _plain_md_images(t):
    return _P_IMAGE.sub(r" xximg \1 ", t)


def _plain_md_links(t):
    t = _P_LINK.sub(r" xxlnk \1 ", t)
    return _P_AUTOLINK.sub(" xxlnk ", t)


def _plain_md_html(t):
    t = _P_BR.sub("\n", t)
    return _P_HTML_TAG.sub(" xxhtm ", t)


def _plain_md_structure(t):
    t = _P_HRULE.sub(" xxhrl ", t)
    t = _P_HEADING.sub(" xxhdr ", t)
    t = _P_QUOTE.sub(" xxqot ", t)
    t = _P_LIST.sub(" xxlst ", t)
    return _P_EMPHASIS.sub(r"\2", t)


def _plain_fix_html(t):
    t = t.replace("&nbsp;", " ")
    t = _html.unescape(t)
    return t.replace(" ", " ").replace("\r", "\n")


def _plain_replace_rep(t):
    def _sub(m):
        c, rep = m.groups()
        return f" xxrep {len(rep) + 1} {c} "

    return _P_REP.sub(_sub, t)


def _plain_replace_wrep(t):
    def _sub(m):
        w, rest = m.groups()
        return f" xxwrep {len(rest.split()) + 1} {w} "

    return _P_WREP.sub(_sub, t)


def _plain_spec_add_spaces(t):
    return re.sub(r"([/#@])", r" \1 ", t)


def _plain_rm_useless_spaces(t):
    return _P_SPACE.sub(" ", t)


# in the order of ``default_pre_rules()``
PLAIN_RULES = [
    _plain_md_code_blocks, _plain_md_inline_code, _plain_md_images,
    _plain_md_links, _plain_md_html, _plain_md_structure, _plain_fix_html,
    _plain_replace_rep, _plain_replace_wrep, _plain_spec_add_spaces,
    _plain_rm_useless_spaces,
]
RULE_NAMES = [f.__name__[len("_plain_"):] for f in PLAIN_RULES]
# the 17 patterns the chain hands to ``rules._scan``, by their names there
SCANNED = ["_RE_FENCED_CODE", "_RE_INDENT_CODE", "_RE_INLINE_CODE",
           "_RE_IMAGE", "_RE_LINK", "_RE_AUTOLINK", "_RE_BR", "_RE_HTML_TAG",
           "_RE_HRULE", "_RE_HEADING", "_RE_QUOTE", "_RE_LIST",
           "_RE_EMPHASIS", "_RE_REP", "_RE_WREP", "_RE_SPEC", "_RE_SPACE"]


def _plain_chain(t):
    for rule in PLAIN_RULES:
        t = rule(t)
    return t


def _plain_issue_text(title, body):
    return (f"xxxfldtitle {_plain_chain(title).strip()} "
            f"xxxfldbody {_plain_chain(body).strip()}")


# every construct at the start, in the middle and at the end of a text
_CONSTRUCTS = [
    "```\ncode here\n```", "```python\nx = 1", "~~~\ntilde fence\n~~~",
    "~~~ unclosed", "    indented code", "\tindented by a tab",
    "`inline`", "``", "`a` and `b`", "![alt](http://x/y.png)", "![]()",
    "[text](https://example.com/a_b)", "[](x)", "[unclosed](",
    "https://example.com/p?q=1", "http://x", "www.example.com", "httpx",
    "<br>", "<BR />", "<br\t/>", "<div class='a'>", "<", ">", "a < b > c",
    "# Heading", "###### six", "####### seven", "#1234", "#", "# ",
    "> quoted", ">> nested quote", "   > indented quote",
    "- item", "* item", "+ item", "1. first", "1) first", "12) twelfth",
    "-no space", "1.5 is a number", "٣. arabic-indic", "- nbsp item",
    "---", "***", "___", "   ----  ", "----x", "_ _ _",
    "*em*", "**bold**", "***both***", "_em_", "__bold__", "___both___",
    "**nested *emphasis* inside**", "*unbalanced", "**unbalanced*",
    "snake_case_name", "a*b*c", "* * *", "_", "__",
    "&amp;", "&nbsp;", "&lt;tag&gt;", "&#39;", "&#x27;", "&", "&bogus;",
    "a b", "x &nbsp; y",
    "loooong", "!!!!", "aaaa", "aaa", "....", "    ", "ééééé", "\x00\x00\x00",
    "\x00\x00\x00\x00", "no no no no", "no no no", "that that",
    "why  why\twhy\nwhy", "no no no no-one", "no no no note", "é é é é",
    "a a a a a a", "go go go go!", "x\x1cx\x1cx\x1cx",
    "kind/bug", "@user", "a/b#c@d", "path/to/file.py", "/ # @",
    "double  space", "triple   space", " leading", "trailing ",
    "line one\r\nline two\r\n", "tab\tseparated\twords", "\n\n\n",
    "naïve café 日本語のテキスト", "Ünïcödé wörds HERE", "",
]
_FILLER = ["plain words only", "Fix the crash in Parser.parse",
           "see the docs. Version 2.1 (beta)", "line one\nline two",
           "  spaced out  ", "ÀÉÎ õü", "a", ""]


def _handwritten_corpus():
    texts = list(_CONSTRUCTS) + list(_FILLER)
    for i, c in enumerate(_CONSTRUCTS):
        f, g = _FILLER[i % len(_FILLER)], _FILLER[(i + 3) % len(_FILLER)]
        texts += [f"{c} {f}", f"{f} {c}", f"{f} {c} {g}", f"{f}\n{c}\n{g}",
                  f"{c}\n{g}", f"{f}\n{c}", f"{f}\r\n{c}\r\n{g}",
                  f"{c}{_CONSTRUCTS[(i * 7 + 1) % len(_CONSTRUCTS)]}",
                  f"{c} {_CONSTRUCTS[(i * 5 + 2) % len(_CONSTRUCTS)]}\n"
                  f"{_CONSTRUCTS[(i * 3 + 4) % len(_CONSTRUCTS)]}"]
    return texts


def _random_corpus(n=2000, seed=29):
    rng = random.Random(seed)
    # heavy in the characters the guards look for
    alphabet = (list("`~![]()<>#-*+_&;/@.)\\ \n\t\r0123456789")
                + [" "] * 6 + list("aab") + ["é", " ", "  ", "```", "\n    ",
                "http", "www.", "://", "<br", "&nbsp;", "&amp;", "no no ",
                "aaaa", "\n- ", "\n> ", "\n# ", "1. ", "---", "**", "__", "](",
                "!["])
    return ["".join(rng.choice(alphabet)
                    for _ in range(rng.randint(0, 60))) for _ in range(n)]


def _generator_documents():
    """One call of the benchmark generator's documents (the flagship mix)."""
    import json
    from pathlib import Path

    from benchmark.harness import traffic

    root = Path(__file__).resolve().parents[1]
    mix = json.loads((root / "benchmark" / "mixes"
                      / "issues_heavy_tail_bulk.json").read_text())
    words = traffic.vocab_words(SPECIALS, 60000)
    return traffic.make_document_calls(mix, words, 7, 1, stream=1)[0]


_CORPORA = {}


def _corpus(name):
    """``(texts, pairs)``: single fields, and (title, body) documents."""
    if name not in _CORPORA:
        if name == "generator":
            docs = _generator_documents()
            pairs = [(d["title"], d["body"]) for d in docs]
            texts = [t for pair in pairs for t in pair]
        else:
            texts = (_handwritten_corpus() if name == "handwritten"
                     else _random_corpus())
            pairs = list(zip(texts, texts[1:] + texts[:1]))
        _CORPORA[name] = (texts, pairs)
    return _CORPORA[name]


CORPUS_NAMES = ["generator", "handwritten", "random"]


def _native_or_skip(backend):
    if backend == "native":
        from code_intelligence_tpu.text import native

        if not native.native_available():
            pytest.skip("native tokenizer not built")


class TestGuardedRulesExact:
    def test_the_plain_copies_follow_the_chain_order(self):
        assert RULE_NAMES == [r.__name__ for r in R.default_pre_rules()]
        assert R.default_pre_rules() == R.MARKDOWN_PRE_RULES + R.TEXT_PRE_RULES
        assert sorted(SCANNED) == sorted(
            n for n in vars(R) if n.startswith("_RE_"))

    @pytest.mark.parametrize("corpus", CORPUS_NAMES)
    @pytest.mark.parametrize("index", range(len(PLAIN_RULES)), ids=RULE_NAMES)
    def test_rule_equals_its_plain_copy(self, index, corpus):
        rule, plain = R.default_pre_rules()[index], PLAIN_RULES[index]
        texts, _ = _corpus(corpus)
        for t in texts:
            assert rule(t) == plain(t), repr(t)
            # and on the text this rule meets inside the chain, on both
            # applications
            for _ in range(2):
                for before in PLAIN_RULES[:index]:
                    t = before(t)
                assert rule(t) == plain(t), repr(t)
                for after in PLAIN_RULES[index:]:
                    t = after(t)

    @pytest.mark.parametrize("backend", ["python", "native"])
    @pytest.mark.parametrize("corpus", CORPUS_NAMES)
    def test_chain_gives_the_same_token_ids(self, corpus, backend):
        _native_or_skip(backend)
        _, pairs = _corpus(corpus)
        tok = Tokenizer(backend=backend)
        assert tok._use_native == (backend == "native")
        plain = [tok.tokenize_pre_processed(
            _plain_chain(_plain_issue_text(title, body)).strip())
            for title, body in pairs]
        vocab = Vocab.build(plain, min_freq=1)
        for (title, body), want in zip(pairs, plain):
            text = build_issue_text(title, body)
            assert text == _plain_issue_text(title, body), repr((title, body))
            got = tok.tokenize(text)
            assert got == want, repr((title, body))
            np.testing.assert_array_equal(vocab.numericalize(got),
                                          vocab.numericalize(want))

    @pytest.mark.parametrize("corpus", CORPUS_NAMES)
    def test_each_guard_is_a_necessary_condition(self, corpus, monkeypatch):
        """Wherever a pattern's ``search`` succeeds on the text a rule
        hands it, the rule's guard was true."""
        names = {getattr(R, n): n for n in SCANNED}
        real_scan = R._scan
        seen = {n: {"matched": 0, "guard_false": 0} for n in SCANNED}
        wrong = []

        def checked_scan(pattern, repl, t, may_match):
            found = pattern.search(t) is not None
            if found and not may_match:
                wrong.append((names[pattern], t))
            seen[names[pattern]]["matched"] += found
            seen[names[pattern]]["guard_false"] += not may_match
            return real_scan(pattern, repl, t, may_match)

        monkeypatch.setattr(R, "_scan", checked_scan)
        texts, pairs = _corpus(corpus)
        for t in texts:
            for rule in R.default_pre_rules():
                rule(t)
        tok = Tokenizer()
        for title, body in pairs:
            tok.tokenize(build_issue_text(title, body))
        assert not wrong, wrong[:5]
        if corpus != "generator":
            # not vacuous: every pattern matched somewhere, and every
            # guard said "cannot match" somewhere
            idle = {n: s for n, s in seen.items()
                    if not (s["matched"] and s["guard_false"])}
            assert not idle, idle

    def test_word_and_digit_classes_are_the_regex_engines(self):
        """``_says_a_word_twice`` splits where ``\\s`` matches, and
        ``_has_digit`` scans wherever a non-ASCII ``\\d`` could be."""
        every = "".join(map(chr, range(0x110000)))
        assert set(re.findall(r"\s", every)) == {
            c for c in every if f"a{c}b".split() != [f"a{c}b"]}
        assert all(not c.isascii() or c in "0123456789"
                   for c in re.findall(r"\d", every))

    @pytest.mark.parametrize("text,made", [
        ("plain words only", 0),
        ("Fix the crash in Parser.parse", 0),
        ("see `code` and **bold** at https://x.y/z", 5),
    ], ids=["plain", "plain-with-a-dot", "markdown"])
    def test_counting_passes(self, text, made):
        assert R._tally.counts is None
        with R.counting_passes() as counts:
            pre_process(text)
            assert counts == [len(SCANNED), made]
            pre_process(text)
        assert counts[0] == 2 * len(SCANNED) and R._tally.counts is None
        pre_process(text)  # none open: nothing counted
        assert counts[0] == 2 * len(SCANNED)
