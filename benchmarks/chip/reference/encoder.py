"""Plain reference encoder: parsed N-Triples terms to int32 planes.

Each distinct term (keyed by the UTF-8 bytes of ``Term.key()``) gets an
id in first-appearance order and its metadata once: kind and property
flags, lexical length, datatype id, and a 32-bit hash of its key bytes.
A triple's row gathers those per position, in the column layout below.
This is the semantics of the assessed program's legacy parser and encoder,
written out plainly and kept with the benchmark.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

from . import vocab
from .parser import _TRIPLE_RE, Term, parse_lines, parse_term

# plane (column) layout of a row
COL_S, COL_P, COL_O = 0, 1, 2
COL_S_FLAGS, COL_P_FLAGS, COL_O_FLAGS = 3, 4, 5
COL_S_LEN, COL_P_LEN, COL_O_LEN = 6, 7, 8
COL_O_DT = 9
COL_S_HASH, COL_P_HASH, COL_O_HASH = 10, 11, 12
N_PLANES = 13

_H_BYTE = np.uint32(0x9E3779B1)
_H_POS = np.uint32(0x85EBCA77)


def mix32(x: np.ndarray) -> np.ndarray:
    """murmur3 fmix32 over uint32 lanes."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x = x * np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x = x * np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def content_hash(keys: Sequence[bytes]) -> np.ndarray:
    """uint32 hash of each key: every (byte, position) pair is mixed, the
    values of one key are XORed together, and its length is folded in."""
    if not keys:
        return np.zeros(0, np.uint32)
    blob = np.frombuffer(b"".join(keys), np.uint8)
    lens = np.fromiter((len(k) for k in keys), np.int64, len(keys))
    starts = np.zeros(len(keys), np.int64)
    np.cumsum(lens[:-1], out=starts[1:])
    pos = (np.arange(blob.size, dtype=np.int64)
           - np.repeat(starts, lens)).astype(np.uint32)
    v = mix32((blob.astype(np.uint32) + np.uint32(1)) * _H_BYTE
              ^ pos * _H_POS)
    acc = np.bitwise_xor.reduceat(v, starts)
    return mix32(acc ^ lens.astype(np.uint32) * _H_POS)


def term_metadata(t: Term, base_namespaces: Sequence[str]
                  ) -> tuple[int, int, int]:
    """(flags, length, datatype id) of one term."""
    f = vocab.VALID
    dt_id = vocab.DT_NONE
    if t.kind == "iri":
        f |= vocab.KIND_IRI
        if vocab.iri_valid(t.value):
            f |= vocab.IRI_VALID
        if any(t.value.startswith(ns) for ns in base_namespaces):
            f |= vocab.INTERNAL
        if t.value in vocab.LICENSE_PREDICATES:
            f |= vocab.IS_LICENSE_PRED
        if t.value in vocab.LICENSE_INDICATION_PREDICATES:
            f |= vocab.IS_LICENSE_INDICATION
        if t.value in vocab.LABEL_PREDICATES:
            f |= vocab.IS_LABEL_PRED
        if t.value == vocab.SAMEAS:
            f |= vocab.IS_SAMEAS
        if t.value == vocab.RDFTYPE:
            f |= vocab.IS_RDFTYPE
    elif t.kind == "blank":
        f |= vocab.KIND_BLANK
    else:
        f |= vocab.KIND_LITERAL
        if t.lang:
            f |= vocab.HAS_LANG
            dt_id = vocab.DT_LANGSTRING
        if t.datatype:
            f |= vocab.HAS_DATATYPE
            dt_id = vocab.datatype_id(t.datatype)
        if vocab.lexical_ok(t.value,
                            dt_id if t.datatype else vocab.DT_STRING):
            f |= vocab.LEXICAL_OK
        if vocab.is_license_statement(t.value):
            f |= vocab.IS_LICENSE_STATEMENT
    return f, len(t.value), dt_id


class Encoder:
    """Interns terms across calls, so rows encoded at different times
    (a dataset, then the lines a changeset adds) share one id space."""

    def __init__(self, base_namespaces: Sequence[str] = ()):
        self.base_namespaces = tuple(base_namespaces)
        self._ids: dict[bytes, int] = {}
        self._token_ids: dict[str, int] = {}   # token text -> id
        self._keys: list[bytes] = []
        self._meta: list[tuple[int, int, int]] = []
        self._table = np.zeros((0, 4), np.int32)  # flags, len, dt, hash

    @property
    def n_terms(self) -> int:
        """Distinct terms interned so far: the size of the id space."""
        return len(self._keys)

    def _intern(self, t: Term) -> int:
        kb = t.key().encode("utf-8")
        tid = self._ids.get(kb)
        if tid is None:
            tid = len(self._keys)
            self._ids[kb] = tid
            self._keys.append(kb)
            self._meta.append(term_metadata(t, self.base_namespaces))
        return tid

    def _sync_table(self) -> np.ndarray:
        """Per-id (flags, length, datatype, hash), extended for new ids."""
        have = self._table.shape[0]
        if have < len(self._keys):
            meta = np.asarray(self._meta[have:], np.int64).reshape(-1, 3)
            hashes = content_hash(self._keys[have:]).view(np.int32)
            new = np.concatenate([meta.astype(np.int32),
                                  hashes[:, None]], axis=1)
            self._table = np.concatenate([self._table, new])
        return self._table

    def _token(self, tok: str) -> int:
        """Id of a term token; a token's text decides its term, so each
        distinct text is parsed once."""
        tid = self._token_ids.get(tok)
        if tid is None:
            tid = self._token_ids[tok] = self._intern(parse_term(tok))
        return tid

    def _line_ids(self, lines: Iterable[str]):
        for line in lines:
            m = _TRIPLE_RE.match(line.strip())
            if m:
                yield (self._token(m.group(1)), self._token(m.group(2)),
                       self._token(m.group(3)))
            else:   # comments, blank and malformed lines, as the parser
                for s, p, o in parse_lines([line]):
                    yield self._intern(s), self._intern(p), self._intern(o)

    def encode_lines(self, lines: Iterable[str]) -> np.ndarray:
        """(rows, N_PLANES) int32 planes of the N-Triples ``lines``."""
        ids = list(self._line_ids(lines))
        spo = np.asarray(ids, np.int64).reshape(-1, 3)
        table = self._sync_table()
        s, p, o = spo[:, 0], spo[:, 1], spo[:, 2]
        planes = np.empty((spo.shape[0], N_PLANES), np.int32)
        planes[:, COL_S], planes[:, COL_P], planes[:, COL_O] = s, p, o
        for col, ix in ((COL_S_FLAGS, s), (COL_P_FLAGS, p), (COL_O_FLAGS, o)):
            planes[:, col] = table[ix, 0]
        for col, ix in ((COL_S_LEN, s), (COL_P_LEN, p), (COL_O_LEN, o)):
            planes[:, col] = table[ix, 1]
        planes[:, COL_O_DT] = table[o, 2]
        for col, ix in ((COL_S_HASH, s), (COL_P_HASH, p), (COL_O_HASH, o)):
            planes[:, col] = table[ix, 3]
        return planes
