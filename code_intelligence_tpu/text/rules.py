"""Text pre/post-processing rules for GitHub-issue text.

Functional equivalent of the reference's two-stage pipeline
(`py/code_intelligence/inference.py:46-53`):
``compose(mdparse.transform_pre_rules + fastai.defaults.text_pre_rules)``
followed by fastai's post-tokenization case rules. We own the rule set (the
vocab is retrained from scratch), so the special-token *names* are ours, but
the behavior class is the same:

* markdown structure (code blocks, inline code, links, images, block quotes)
  is replaced by special marker tokens so the LM sees document structure
  rather than noisy payloads;
* HTML entities are unescaped; repeated characters/words are collapsed to
  ``xxrep``/``xxwrep`` markers; case information is factored into ``xxmaj`` /
  ``xxup`` markers so the vocab stays lowercase.

The title/body document contract of the reference
(``'xxxfldtitle ' + parse(title) + ' xxxfldbody ' + parse(body)``,
`inference.py:95-126`) is preserved verbatim via :func:`build_issue_text`.
"""

from __future__ import annotations

import contextlib
import html
import operator
import re
import threading
from typing import Callable, Iterable, Iterator, List, Sequence

# ---------------------------------------------------------------------------
# Special tokens
# ---------------------------------------------------------------------------

TK_UNK = "xxunk"
TK_PAD = "xxpad"
TK_BOS = "xxbos"
TK_EOS = "xxeos"
TK_MAJ = "xxmaj"  # next token was Capitalized
TK_UP = "xxup"  # next token was ALL-CAPS
TK_REP = "xxrep"  # char repetition: 'cccc' -> 'xxrep 4 c'
TK_WREP = "xxwrep"  # word repetition: 'no no no' -> 'xxwrep 3 no'

# Markdown structure markers (mdparse-equivalents).
TK_CODE_BLOCK = "xxcdb"  # fenced ``` block
TK_CODE_INLINE = "xxcdi"  # `inline code`
TK_LINK = "xxlnk"
TK_IMAGE = "xximg"
TK_HTML_BLOCK = "xxhtm"
TK_QUOTE = "xxqot"
TK_LIST_ITEM = "xxlst"
TK_HEADING = "xxhdr"
TK_HRULE = "xxhrl"

# Document-field markers — the reference's exact wire/vocab contract
# (`inference.py:118`). Note the triple-x: these are the literal strings the
# reference puts in training documents, so we keep them byte-identical.
TK_FLD_TITLE = "xxxfldtitle"
TK_FLD_BODY = "xxxfldbody"

SPECIALS: List[str] = [
    TK_UNK,
    TK_PAD,
    TK_BOS,
    TK_EOS,
    TK_MAJ,
    TK_UP,
    TK_REP,
    TK_WREP,
    TK_CODE_BLOCK,
    TK_CODE_INLINE,
    TK_LINK,
    TK_IMAGE,
    TK_HTML_BLOCK,
    TK_QUOTE,
    TK_LIST_ITEM,
    TK_HEADING,
    TK_HRULE,
    TK_FLD_TITLE,
    TK_FLD_BODY,
]

Rule = Callable[[str], str]

# ---------------------------------------------------------------------------
# A pre-rule pays for a regex scan only where the text can match
# ---------------------------------------------------------------------------
#
# Most issue text holds nothing most patterns need (no backtick, no ``<``,
# no doubled space), and the chain runs twice over every document (once
# a field in :func:`build_issue_text`, once more in ``Tokenizer.tokenize``).
# So every rule hands each of its patterns to :func:`_scan` with a
# NECESSARY condition of a match that runs at C speed (substring or
# character membership): false means no match is possible and the text
# comes back untouched, true means no more than "scan it". The result is
# the unguarded ``pattern.sub`` for every input.


class _PassTally(threading.local):
    counts = None  # [scans the rules could make, scans made] while open


_tally = _PassTally()


@contextlib.contextmanager
def counting_passes() -> Iterator[List[int]]:
    """Count the pre-rules' regex scans on this thread while open:
    yields ``[could, made]``, the scans the rules applied could make and
    the scans their guards let through. For a traced caller
    (``engine.embed_issues``); with none open the rules count nothing.
    Not re-entrant."""
    counts = _tally.counts = [0, 0]
    try:
        yield counts
    finally:
        _tally.counts = None


def _scan(pattern: "re.Pattern[str]", repl, t: str, may_match: bool) -> str:
    """``pattern.sub(repl, t)``, scanned only when ``may_match``: the
    caller's necessary condition of a match in ``t``."""
    counts = _tally.counts
    if counts is not None:
        counts[0] += 1
        counts[1] += may_match
    return pattern.sub(repl, t) if may_match else t


def _has_digit(t: str) -> bool:
    # ``\d`` also matches the other scripts' digits: outside ASCII, scan
    return not t.isascii() or any(map(t.__contains__, "0123456789"))


def _says_a_char_four_times(t: str) -> bool:
    """Four equal characters in a row somewhere in an ASCII text, by
    big-integer arithmetic: byte ``k`` of ``x ^ (x >> 8)`` is
    ``t[k] ^ t[k - 1]``, so such a run is three zero bytes in a row
    (a tenth of what ``_RE_REP``'s back-reference costs to find none).
    Other text is scanned."""
    if not t.isascii():
        return True
    b = t.encode("ascii")
    x = int.from_bytes(b, "big")
    return b"\0\0\0" in (x ^ (x >> 8)).to_bytes(len(b), "big")


def _says_a_word_twice(t: str) -> bool:
    """Some whitespace-separated word equals the next one, at an even
    index: any three equal words in a row hold such a pair, and
    ``_RE_WREP`` needs four (the last may be the head of a longer one)."""
    words = t.split()
    return any(map(operator.eq, words[::2], words[1::2]))

# ---------------------------------------------------------------------------
# Markdown pre-rules (mdparse-equivalent, string -> string)
# ---------------------------------------------------------------------------

# Closed fences first; an *unclosed* fence swallows to end-of-text (GitHub
# issues very often have unterminated ``` blocks — leaking raw code into the
# token stream pollutes the vocab).
_RE_FENCED_CODE = re.compile(r"```.*?(?:```|\Z)|~~~.*?(?:~~~|\Z)", re.DOTALL)
_RE_INDENT_CODE = re.compile(r"(?:^|\n)(?:(?:    |\t)[^\n]*\n?)+")
_RE_INLINE_CODE = re.compile(r"`[^`\n]+`")
_RE_IMAGE = re.compile(r"!\[([^\]]*)\]\(([^)]*)\)")
_RE_LINK = re.compile(r"\[([^\]]*)\]\(([^)]*)\)")
_RE_AUTOLINK = re.compile(r"https?://\S+|www\.\S+")
_RE_HTML_TAG = re.compile(r"<[^>\n]+>")
# GFM: '#' only opens a heading when followed by whitespace/EOL — a bare
# '#1234' at line start is an issue reference, not a heading.
_RE_HEADING = re.compile(r"^(#{1,6})(?:[ \t]+|$)", re.MULTILINE)
_RE_QUOTE = re.compile(r"^\s{0,3}>\s?", re.MULTILINE)
_RE_LIST = re.compile(r"^\s{0,3}(?:[-*+]|\d+[.)])\s+", re.MULTILINE)
_RE_HRULE = re.compile(r"^\s{0,3}(?:-{3,}|\*{3,}|_{3,})\s*$", re.MULTILINE)
# Word-boundary guards so intra-word '_'/'*' (snake_case, a*b) survive —
# GFM does not treat intra-word underscores as emphasis.
_RE_EMPHASIS = re.compile(r"(?<!\w)(\*{1,3}|_{1,3})(?=\S)(.+?)(?<=\S)\1(?!\w)")


def md_code_blocks(t: str) -> str:
    """Replace fenced/indented code blocks with a single ``xxcdb`` marker."""
    t = _scan(_RE_FENCED_CODE, f" {TK_CODE_BLOCK} ", t,
              "```" in t or "~~~" in t)
    return _scan(_RE_INDENT_CODE, f"\n {TK_CODE_BLOCK} \n", t,
                 t.startswith(("    ", "\t")) or "\n    " in t or "\n\t" in t)


def md_inline_code(t: str) -> str:
    return _scan(_RE_INLINE_CODE, f" {TK_CODE_INLINE} ", t, "`" in t)


def md_images(t: str) -> str:
    return _scan(_RE_IMAGE, rf" {TK_IMAGE} \1 ", t, "![" in t)


def md_links(t: str) -> str:
    """``[text](url)`` -> ``xxlnk text``; bare URLs -> ``xxlnk``."""
    t = _scan(_RE_LINK, rf" {TK_LINK} \1 ", t, "](" in t)
    return _scan(_RE_AUTOLINK, f" {TK_LINK} ", t, "http" in t or "www." in t)


_RE_BR = re.compile(r"<br\s*/?>", re.IGNORECASE)


def md_html(t: str) -> str:
    # <br> carries line-break semantics — convert before the generic tag
    # replacement eats it.
    t = _scan(_RE_BR, "\n", t, "<" in t)
    return _scan(_RE_HTML_TAG, f" {TK_HTML_BLOCK} ", t, "<" in t)


def md_structure(t: str) -> str:
    """Headings, quotes, lists, horizontal rules, emphasis."""
    t = _scan(_RE_HRULE, f" {TK_HRULE} ", t,
              "---" in t or "***" in t or "___" in t)
    # a multiline ``^`` is the start of the text or follows a "\n"
    t = _scan(_RE_HEADING, f" {TK_HEADING} ", t,
              t.startswith("#") or "\n#" in t)
    t = _scan(_RE_QUOTE, f" {TK_QUOTE} ", t, ">" in t)
    t = _scan(_RE_LIST, f" {TK_LIST_ITEM} ", t,
              "-" in t or "*" in t or "+" in t
              or (("." in t or ")" in t) and _has_digit(t)))
    # an opening and a closing run of the same character
    return _scan(_RE_EMPHASIS, r"\2", t,
                 t.count("*") > 1 or t.count("_") > 1)


MARKDOWN_PRE_RULES: List[Rule] = [
    md_code_blocks,
    md_inline_code,
    md_images,
    md_links,
    md_html,
    md_structure,
]

# ---------------------------------------------------------------------------
# Plain-text pre-rules (fastai ``defaults.text_pre_rules`` equivalents)
# ---------------------------------------------------------------------------

_RE_REP = re.compile(r"(\S)(\1{3,})")
_RE_WREP = re.compile(r"(?:^|\s)(\S+)((?:\s+\1){3,})\b")
_RE_SPEC = re.compile(r"([/#@])")
_RE_SPACE = re.compile(r" {2,}")


def fix_html(t: str) -> str:
    """Un-escape HTML entities and normalize whitespace artifacts.

    (``<br>`` tags are handled earlier by :func:`md_html`, which runs before
    the generic tag replacement in the default rule ordering.)
    """
    if "&" in t:  # every entity opens with one; no regex of ours here
        t = html.unescape(t.replace("&nbsp;", " "))
    return t.replace(" ", " ").replace("\r", "\n")


def _rep_marker(m: re.Match) -> str:
    c, rep = m.groups()
    return f" {TK_REP} {len(rep) + 1} {c} "


def replace_rep(t: str) -> str:
    """``cccc`` -> ``xxrep 4 c`` (runs of 4+ of the same char)."""
    return _scan(_RE_REP, _rep_marker, t, _says_a_char_four_times(t))


def _wrep_marker(m: re.Match) -> str:
    w, rest = m.groups()
    n = len(rest.split()) + 1
    return f" {TK_WREP} {n} {w} "


def replace_wrep(t: str) -> str:
    """``no no no no`` -> ``xxwrep 4 no`` (runs of 4+ of the same word)."""
    return _scan(_RE_WREP, _wrep_marker, t, _says_a_word_twice(t))


def spec_add_spaces(t: str) -> str:
    """Add spaces around ``/``, ``#``, ``@`` so paths/labels/mentions split."""
    return _scan(_RE_SPEC, r" \1 ", t, "/" in t or "#" in t or "@" in t)


def rm_useless_spaces(t: str) -> str:
    return _scan(_RE_SPACE, " ", t, "  " in t)


TEXT_PRE_RULES: List[Rule] = [
    fix_html,
    replace_rep,
    replace_wrep,
    spec_add_spaces,
    rm_useless_spaces,
]


def default_pre_rules() -> List[Rule]:
    """Markdown rules then plain-text rules, matching the reference's
    ``transform_pre_rules + defaults.text_pre_rules`` ordering
    (`inference.py:52-53`)."""
    return MARKDOWN_PRE_RULES + TEXT_PRE_RULES


def compose(rules: Iterable[Rule]) -> Rule:
    def _composed(t: str) -> str:
        for r in rules:
            t = r(t)
        return t

    return _composed


def pre_process(text: str, rules: Sequence[Rule] | None = None) -> str:
    """Apply the full pre-rule chain to one field (title OR body)."""
    if not isinstance(text, str):
        text = "" if text is None else str(text)
    return compose(rules if rules is not None else default_pre_rules())(text).strip()


def build_issue_text(title: str, body: str) -> str:
    """The reference's document contract, byte-identical:
    ``'xxxfldtitle ' + parse(title) + ' xxxfldbody ' + parse(body)``
    (`py/code_intelligence/inference.py:118`)."""
    return f"{TK_FLD_TITLE} {pre_process(title)} {TK_FLD_BODY} {pre_process(body)}"


# ---------------------------------------------------------------------------
# Post-tokenization rules (token-list -> token-list): case factoring
# ---------------------------------------------------------------------------


def replace_all_caps(tokens: Sequence[str]) -> List[str]:
    """``WARNING`` -> ``xxup warning`` (fastai ``replace_all_caps`` semantics)."""
    out: List[str] = []
    for tok in tokens:
        if len(tok) > 1 and tok.isupper() and tok.isalpha():
            out.append(TK_UP)
            out.append(tok.lower())
        else:
            out.append(tok)
    return out


def deal_caps(tokens: Sequence[str]) -> List[str]:
    """``Hello`` -> ``xxmaj hello`` (fastai ``deal_caps`` semantics)."""
    out: List[str] = []
    for tok in tokens:
        if len(tok) > 1 and tok[0].isupper() and tok[1:].islower() and tok.isalpha():
            out.append(TK_MAJ)
            out.append(tok.lower())
        else:
            out.append(tok.lower() if tok.isalpha() else tok)
    return out


def default_post_rules() -> List[Callable[[Sequence[str]], List[str]]]:
    return [replace_all_caps, deal_caps]
