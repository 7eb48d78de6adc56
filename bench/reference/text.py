"""TPC-H comment text as token ids, from the specification's grammar.

TPC-H fills every comment column with a "text string [min, max]" (clause
4.2.2.10): a substring of one pseudo-text pool, at a random offset, of a
length in characters drawn uniformly from ``[min, max]``.  The pool is
sentences of the grammar of clause 4.2.2.14 over the word lists of clause
4.2.2.13 (``tpch_grammar.json``, with dbgen's weights).

Here the pool is made from the seed in bulk with numpy and tokenized by
whitespace: a term is one word (case kept, punctuation dropped), and a
comment holds the words that lie whole inside its substring, in order; the
broken words at its two ends are dropped.  A relation's token matrix is
``[rows, width]`` with ``width = (max + 1) // (shortest word + 1)``, the
most whole words ``max`` characters can hold, so no comment is ever cut;
the rest of a row is PAD (id 0).
"""
from __future__ import annotations

import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

PAD_ID = 0
GRAMMAR = Path(__file__).resolve().parent / "tpch_grammar.json"
_SENTENCES_PER_STEP = 1 << 19


class Grammar:
    """The grammar expanded into sentence templates of leaf word classes,
    and the word table: each entry's characters, tokens and class."""

    def __init__(self, spec: dict) -> None:
        self.spec = spec
        words = spec["words"]
        extra = [spec["supplier_comments"]["first"],
                 *spec["supplier_comments"]["second"]]
        terms = sorted({t for cls, ents in words.items() if cls != "T"
                        for e, _ in ents for t in e.split()} | set(extra))
        self.terms = ["<pad>"] + terms
        self.term_id = {t: i for i, t in enumerate(self.terms)}
        self.vocab = len(self.terms)
        # leaf classes: every word list, and "adjective," (comma attached)
        self.classes = list(words) + ["adjective,"]
        ent_chars, ent_space, tok_ids, tok_off, ent_first, ent_ntok = \
            [], [], [], [], [], []
        self.class_range: Dict[str, Tuple[int, int]] = {}
        self.class_weights: Dict[str, List[int]] = {}
        for cls in self.classes:
            base = words["adjective" if cls == "adjective," else cls]
            lo = len(ent_chars)
            for text, _ in base:
                toks = [] if cls == "T" else text.split()
                ent_chars.append(len(text) + (cls == "adjective,"))
                ent_space.append(cls != "T")    # a terminator is attached
                ent_first.append(len(tok_ids))
                ent_ntok.append(len(toks))
                pos = 0
                for t in toks:
                    tok_ids.append(self.term_id[t])
                    tok_off.append(pos)
                    pos += len(t) + 1
            self.class_range[cls] = (lo, len(ent_chars))
            self.class_weights[cls] = [int(wt) for _, wt in base]
        table, off, total = [], [], []
        for cls in self.classes:
            lo, hi = self.class_range[cls]
            w = self.class_weights[cls]
            off.append(len(table))
            total.append(sum(w))
            table.extend(e for e, n in zip(range(lo, hi), w) for _ in range(n))
        self.ent_table = np.asarray(table, np.int32)
        self.cls_table_off = np.asarray(off, np.int32)
        self.cls_total = np.asarray(total, np.int32)
        self.ent_chars = np.asarray(ent_chars, np.int32)
        self.ent_space = np.asarray(ent_space, np.int32)
        most = max(ent_ntok)
        self.ent_tok = np.full((len(ent_chars), most), -1, np.int32)
        self.ent_tok_off = np.zeros((len(ent_chars), most), np.int32)
        for e, (f, n) in enumerate(zip(ent_first, ent_ntok)):
            self.ent_tok[e, :n] = np.arange(f, f + n)
            self.ent_tok_off[e, :n] = tok_off[f:f + n]
        self.tok_ids = np.asarray(tok_ids, np.int32)
        self.tok_chars = np.asarray([len(self.terms[i]) for i in tok_ids],
                                    np.int32)
        self.shortest = int(self.tok_chars.min())
        tpl, prob = self._templates()
        self.tpl_len = np.asarray([len(t) for t in tpl], np.int32)
        self.tpl_off = np.concatenate([[0], np.cumsum(self.tpl_len)[:-1]])
        self.tpl_syms = np.asarray([self.classes.index(s) for t in tpl
                                    for s in t], np.int32)
        self.tpl_cdf = np.cumsum(prob) / np.sum(prob)
        ent_mean = np.zeros(len(self.classes))
        for c, cls in enumerate(self.classes):
            lo, hi = self.class_range[cls]
            w = np.asarray(self.class_weights[cls], np.float64)
            ent_mean[c] = w @ (self.ent_chars[lo:hi] + self.ent_space[lo:hi])
            ent_mean[c] /= w.sum()
        per_tpl = np.add.reduceat(ent_mean[self.tpl_syms], self.tpl_off)
        self.mean_sentence_chars = float(per_tpl @ (prob / np.sum(prob)))

    def _expand(self, sym: str) -> List[Tuple[List[str], float]]:
        if sym in self.spec["words"] or sym == "adjective,":
            return [([sym], 1.0)]
        rules = self.spec[sym]
        total = sum(w for _, w in rules)
        out = []
        for rhs, w in rules:
            seqs = [([], w / total)]
            for part in rhs:
                seqs = [(s + t, p * q) for s, p in seqs
                        for t, q in self._expand(part)]
            out.extend(seqs)
        return out

    def _templates(self):
        exp = self._expand("sentence")
        return [s for s, _ in exp], np.asarray([p for _, p in exp])

    def width(self, max_chars: int) -> int:
        """The most whole words a substring of ``max_chars`` holds."""
        return (max_chars + 1) // (self.shortest + 1)


def _step(g: Grammar, rng: np.random.Generator):
    """``_SENTENCES_PER_STEP`` sentences: token ids, their character spans
    from the step's start, and the characters the step takes."""
    t = np.searchsorted(g.tpl_cdf, rng.random(_SENTENCES_PER_STEP),
                        side="right")
    t = np.minimum(t, len(g.tpl_cdf) - 1)
    n = g.tpl_len[t]
    first = np.repeat(g.tpl_off[t] - np.cumsum(n) + n, n)
    syms = g.tpl_syms[first + np.arange(first.size)]
    # integer weights: draw r < the class's total, look the entry up
    r = (rng.random(syms.size) * g.cls_total[syms]).astype(np.int32)
    ent = g.ent_table[g.cls_table_off[syms] + r]
    end = np.cumsum(g.ent_chars[ent] + g.ent_space[ent], dtype=np.int32)
    start = end - g.ent_chars[ent]
    tok = g.ent_tok[ent]                  # [slots, most tokens], -1 = none
    live = tok >= 0
    s = (start[:, None] + g.ent_tok_off[ent])[live]
    tok = tok[live]
    return g.tok_ids[tok], s, s + g.tok_chars[tok], int(end[-1])


def load_grammar() -> Grammar:
    return Grammar(json.loads(GRAMMAR.read_text()))


class Pool:
    """The pseudo-text pool as tokens with their character spans."""

    def __init__(self, g: Grammar, rng: np.random.Generator,
                 chars: int, threads: int = 4) -> None:
        # steps of a fixed number of sentences, each from its own stream,
        # made on a few threads; enough steps to fill ``chars`` with margin
        per_step = _SENTENCES_PER_STEP * g.mean_sentence_chars
        n = int(np.ceil(chars / per_step * 1.02)) + 1
        seeds = rng.integers(0, 2**63 - 1, size=n)
        with ThreadPoolExecutor(threads) as ex:
            steps = list(ex.map(lambda s: _step(g, np.random.default_rng(s)),
                                seeds))
        ids, starts, ends, base = [], [], [], 0
        for i, s, e, used in steps:
            ids.append(i)
            starts.append(s + base)
            ends.append(e + base)
            base += used
        if base < chars:
            raise AssertionError(f"pool of {base} chars < {chars}")
        self.ids = np.concatenate(ids)
        self.starts = np.concatenate(starts)
        self.ends = np.concatenate(ends)
        keep = int(np.searchsorted(self.ends, chars, side="right"))
        self.ids, self.starts, self.ends = (
            self.ids[:keep], self.starts[:keep], self.ends[:keep])
        self.chars = chars

    def comments(self, rng: np.random.Generator, rows: int, lo: int, hi: int,
                 width: int) -> np.ndarray:
        """int32 [rows, width]: each row the whole words of a substring at a
        random offset, of ``lo..hi`` characters."""
        length = rng.integers(lo, hi + 1, size=rows).astype(np.int32)
        off = (rng.random(rows) * (self.chars - length + 1)).astype(np.int32)
        order = np.argsort(off, kind="stable")   # sorted keys search fast
        off, length = off[order], length[order]
        first = np.searchsorted(self.starts, off, side="left")
        stop = np.searchsorted(self.ends, off + length, side="right")
        k = np.maximum(stop - first, 0)
        if k.max(initial=0) > width:
            raise AssertionError(f"a comment of {k.max()} words > {width}")
        first = first.astype(np.int32)
        out = np.zeros((rows, width), np.int32)
        for c in range(int(k.max(initial=0))):
            live = np.nonzero(k > c)[0]
            out[order[live], c] = self.ids[first[live] + c]
        return out
