"""YCSB's core table (Cooper et al., SoCC 2010) laid out as paths:
``/<table>/g<k % groups>/user<FNV(k)>``, each record ``fieldcount`` fields of
``fieldlength`` random characters held as one text.

Keys follow CoreWorkload's hashed insert order: record k is named ``user``
followed by the FNV-1a 64-bit hash of k's eight little-endian bytes, read
as a signed 64-bit number and made non-negative (``Utils.fnvhash64``).
Config keys: ``table``, ``recordcount``, ``fieldcount``, ``fieldlength``,
``groups``.  Population: ``record`` (the loaded keys).  ``tiny`` gives
the configuration's CPU rehearsal size.
"""
from __future__ import annotations

import numpy as np

import refkv

_ALPHABET = b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789"
#: random bytes → printable field characters
_TO_ALPHABET = bytes(_ALPHABET[i % len(_ALPHABET)] for i in range(256))
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)
#: records whose text is drawn in one numpy call
_CHUNK = 1 << 16


def fnv_keys(first: int, n: int) -> list[int]:
    """YCSB key numbers for records first .. first + n - 1."""
    v = np.arange(first, first + n, dtype=np.uint64)
    h = np.full(n, _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= _FNV_PRIME
    return np.abs(h.view(np.int64)).tolist()


def random_texts(rng: np.random.Generator, n: int, width: int) -> list[str]:
    out: list[str] = []
    for lo in range(0, n, _CHUNK):
        m = min(_CHUNK, n - lo)
        raw = rng.bytes(m * width).translate(_TO_ALPHABET)
        out.extend(raw[i * width:(i + 1) * width].decode("ascii") for i in range(m))
    return out


class YcsbTable:
    def __init__(self, config: dict, rng: np.random.Generator):
        self.table = "/" + config["table"]
        self.groups = config["groups"]
        self.fieldcount = config["fieldcount"]
        self.fieldlength = config["fieldlength"]
        n = config["recordcount"]
        self.recordcount = n
        names = [f"user{k}" for k in fnv_keys(0, n)]
        texts = random_texts(rng, n, self.fieldcount * self.fieldlength)
        gnames = [f"g{g}" for g in range(self.groups)]
        members: list[list[str]] = [[] for _ in range(self.groups)]
        for i, name in enumerate(names):
            members[i % self.groups].append(name)
        items = [("/", refkv.dir_record("root", sub_dirs=[config["table"]],
                                        summary="ycsb")),
                 (self.table, refkv.dir_record(config["table"], sub_dirs=gnames,
                                               summary="usertable"))]
        items += [(f"{self.table}/{g}", refkv.dir_record(g, files=members[i]))
                  for i, g in enumerate(gnames)]
        records = [f"{self.table}/g{i % self.groups}/{name}"
                   for i, name in enumerate(names)]
        items += [(p, refkv.file_record(name, text))
                  for p, name, text in zip(records, names, texts)]
        self.items = items
        self.populations = {"record": records}

    def new_item(self, parent: str | None, serial: int, rng: np.random.Generator
                 ) -> tuple[str, tuple]:
        """The next record of the insert sequence (``serial`` counts inserts
        from 0); ``parent`` is unused, the key decides the group."""
        k = self.recordcount + serial
        name = f"user{fnv_keys(k, 1)[0]}"
        text = random_texts(rng, 1, self.fieldcount * self.fieldlength)[0]
        return (f"{self.table}/g{k % self.groups}/{name}",
                refkv.file_record(name, text))

    def update_spec(self, path: str, serial: int, rng: np.random.Generator
                    ) -> tuple:
        """YCSB's default update: one field rewritten (writeallfields=false)."""
        j = int(rng.integers(0, self.fieldcount))
        value = random_texts(rng, 1, self.fieldlength)[0]
        return ("field", j, value, self.fieldlength)

    def query(self, path: str) -> str:
        return refkv.basename(path)


def build(config: dict, rng: np.random.Generator) -> YcsbTable:
    return YcsbTable(config, rng)


def tiny(config: dict) -> dict:
    """The configuration at its CPU rehearsal size: 4,096 records in 64 groups."""
    return dict(config, recordcount=4096, groups=64)
