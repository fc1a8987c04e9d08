"""The knowledge-base hierarchy of WikiKV's deployment: root → dimensions →
topics → pages, every segment name built from a seeded word vocabulary so
that keyword routing (Q4 contains) has real tokens to match.

A page's text is ``page_text_bytes`` long: a line that names its two words
and its topic, then a body of vocabulary words, one of ``BODIES`` seeded
bodies chosen by the CRC-32 of the page's path.

Config keys: ``pages``, ``topics_per_dimension``, ``pages_per_topic``,
``vocabulary``, ``page_text_bytes``.  Populations: ``page`` (leaf paths)
and ``topic``.  ``tiny`` gives the configuration's CPU rehearsal size.
"""
from __future__ import annotations

import zlib

import numpy as np

import refkv


def vocabulary(rng: np.random.Generator, n: int) -> list[str]:
    """``n`` distinct lowercase words of 4 to 8 letters, sorted."""
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
    words: set[str] = set()
    while len(words) < n:
        k = int(rng.integers(4, 9))
        words.add(bytes(letters[rng.integers(0, 26, size=k)]).decode())
    return sorted(words)


#: distinct page bodies
BODIES = 4096


def bodies(rng: np.random.Generator, vocab: list[str], width: int) -> list[str]:
    """``BODIES`` texts of at least ``width`` characters of vocabulary words."""
    n_words = width // 3 + 1          # words are 4 to 8 letters and a space
    return [" ".join(vocab[w] for w in row)
            for row in rng.integers(0, len(vocab), size=(BODIES, n_words)).tolist()]


class Hierarchy:
    def __init__(self, config: dict, rng: np.random.Generator):
        n_topics = config["topics_per_dimension"]
        n_pages = config["pages_per_topic"]
        n_dims = -(-config["pages"] // (n_topics * n_pages))
        self.vocab = vocabulary(rng, config["vocabulary"])
        vocab = self.vocab
        self.text_bytes = int(config["page_text_bytes"])
        self.bodies = bodies(rng, vocab, self.text_bytes)
        dim_w = rng.integers(0, len(vocab), size=n_dims)
        topic_w = rng.integers(0, len(vocab), size=(n_dims, n_topics))
        page_w = rng.integers(0, len(vocab), size=(n_dims, n_topics, n_pages, 2))
        dim_names = [f"{vocab[w]}_d{d}" for d, w in enumerate(dim_w.tolist())]
        items = [("/", refkv.dir_record("root", sub_dirs=dim_names,
                                        summary="seeded wiki"))]
        topics: list[str] = []
        pages: list[str] = []
        for d, dname in enumerate(dim_names):
            dpath = "/" + dname
            tnames = [f"{vocab[w]}_t{t}" for t, w in enumerate(topic_w[d].tolist())]
            items.append((dpath, refkv.dir_record(
                dname, sub_dirs=tnames, summary=f"dimension {dname}")))
            for t, tname in enumerate(tnames):
                tpath = f"{dpath}/{tname}"
                words = page_w[d, t].tolist()
                pnames = [f"{vocab[a]}_{vocab[b]}_p{j}" for j, (a, b) in enumerate(words)]
                items.append((tpath, refkv.dir_record(
                    tname, files=pnames, summary=f"topic {tname}")))
                topics.append(tpath)
                for pname, (a, b) in zip(pnames, words):
                    ppath = f"{tpath}/{pname}"
                    items.append((ppath, refkv.file_record(
                        pname, self.text(ppath, f"{vocab[a]} {vocab[b]} is filed under {tname}."))))
                    pages.append(ppath)
        self.items = items
        self.populations = {"page": pages, "topic": topics}

    def text(self, path: str, head: str) -> str:
        """A page's text: ``head``, then its body, ``page_text_bytes`` in all."""
        body = self.bodies[zlib.crc32(path.encode()) % BODIES]
        return head + " " + body[: self.text_bytes - len(head) - 1]

    def query(self, path: str) -> str:
        """A navigating user's question about a page: its two name words and
        its topic's word."""
        a, b = refkv.basename(path).split("_")[:2]
        topic_word = refkv.basename(refkv.parent(path)).split("_")[0]
        return f"{a} {b} {topic_word}"

    def new_item(self, parent: str, serial: int, rng: np.random.Generator
                 ) -> tuple[str, tuple]:
        """A new page under the topic ``parent``."""
        a, b = (self.vocab[i] for i in rng.integers(0, len(self.vocab), size=2))
        name = f"{a}_{b}_n{serial}"
        tname = refkv.basename(parent)
        path = refkv.child(parent, name)
        return path, refkv.file_record(name, self.text(path, f"{a} {b} is filed under {tname}."))

    def update_spec(self, path: str, serial: int, rng: np.random.Generator
                    ) -> tuple:
        """A page rewrite: the whole text, its first line stamped with the
        update's serial."""
        a, b = refkv.basename(path).split("_")[:2]
        tname = refkv.basename(refkv.parent(path))
        head = f"{a} {b} is filed under {tname}, revision {serial}."
        return ("field", None, self.text(path, head), 0)


def build(config: dict, rng: np.random.Generator) -> Hierarchy:
    return Hierarchy(config, rng)


def tiny(config: dict) -> dict:
    """The configuration at its CPU rehearsal size: two dimensions."""
    return dict(config, pages=2 * config["topics_per_dimension"] * config["pages_per_topic"])
