"""Plain reference assessment over int32 planes: the 16 metrics of the
``all`` set, their counters, and the two HyperLogLog register banks.

Every counter is a numpy mask over the planes, written from the metric
definitions (paper Table 2 and the extended set); a row counts only where
its subject carries the VALID bit.  The registers follow the HyperLogLog
fold with ``p`` bucket bits over a murmur-style hash of the content-hash
columns.  Nothing here is taken from the assessed program.
"""
from __future__ import annotations

import numpy as np

from . import vocab
from .encoder import (COL_O, COL_O_FLAGS, COL_O_HASH, COL_O_LEN, COL_P_FLAGS,
                      COL_P_HASH, COL_P_LEN, COL_S, COL_S_FLAGS, COL_S_HASH,
                      COL_S_LEN)

URI_TOO_LONG = 80                     # RC1 threshold, characters
SKETCHES = {"spo": (COL_S_HASH, COL_P_HASH, COL_O_HASH),
            "p": (COL_P_HASH,)}
_FLAGS = {"s": COL_S_FLAGS, "p": COL_P_FLAGS, "o": COL_O_FLAGS}
_LEN = {"s": COL_S_LEN, "p": COL_P_LEN, "o": COL_O_LEN}


def counters(planes: np.ndarray, dtype=np.int64
             ) -> dict[str, dict[str, int]]:
    """metric -> counter -> number of valid rows that satisfy it, as a
    counter of ``dtype`` would hold it (a narrower one wraps)."""
    planes = np.asarray(planes)

    def flag(pos, bit):
        return (planes[:, _FLAGS[pos]] & bit) == bit

    def uri(pos):
        return flag(pos, vocab.KIND_IRI)

    def lit(pos):
        return flag(pos, vocab.KIND_LITERAL)

    def blank(pos):
        return flag(pos, vocab.KIND_BLANK)

    def internal(pos):
        return flag(pos, vocab.INTERNAL)

    def external(pos):
        return uri(pos) & ~internal(pos)

    def too_long(pos):
        return uri(pos) & (planes[:, _LEN[pos]] > URI_TOO_LONG)

    valid = flag("s", vocab.VALID)
    label = flag("p", vocab.IS_LABEL_PRED)
    typed = lit("o") & flag("o", vocab.HAS_DATATYPE)
    masks = {
        "L1": {"lic": flag("p", vocab.IS_LICENSE_PRED)},
        "L2": {"hlic": uri("s") & flag("p", vocab.IS_LICENSE_INDICATION)
               & lit("o") & flag("o", vocab.IS_LICENSE_STATEMENT)},
        "I2": {"r3": (uri("s") & internal("s") & uri("o") & external("o"))
               | (external("s") & uri("o") & internal("o")),
               "total": valid},
        "U1": {"lab_s": uri("s") & internal("s") & label,
               "lab_p": internal("p") & label,
               "lab_o": uri("o") & internal("o") & label,
               "total": valid},
        "RC1": {"too_long": too_long("s") | too_long("p") | too_long("o"),
                "total": valid},
        "SV3": {"malformed": typed
                & ((planes[:, COL_O_FLAGS] & vocab.LEXICAL_OK) == 0)},
        "CN2": {"uri_uri": uri("s") & uri("o"), "total": valid},
        "I1": {"sameas": flag("p", vocab.IS_SAMEAS), "total": valid},
        "SV1": {"typed": typed, "lits": lit("o")},
        "SV2": {"ok_s": uri("s") & flag("s", vocab.IRI_VALID),
                "ok_p": uri("p") & flag("p", vocab.IRI_VALID),
                "ok_o": uri("o") & flag("o", vocab.IRI_VALID),
                "uri_s": uri("s"), "uri_p": uri("p"), "uri_o": uri("o")},
        "V1": {"lang": lit("o") & flag("o", vocab.HAS_LANG),
               "lits": lit("o")},
        "IO1": {"blank": blank("s") | blank("o"), "total": valid},
        "CS1": {"self": (planes[:, COL_S] == planes[:, COL_O]) & uri("o"),
                "total": valid},
        "CM1": {"typed": flag("p", vocab.IS_RDFTYPE), "total": valid},
        "CN2_EXACT": {"total": valid},
        "SCH1": {"total": valid},
    }
    def count(mask):
        return int(np.array(np.count_nonzero(mask & valid)).astype(dtype))

    return {m: {c: count(mask) for c, mask in cs.items()}
            for m, cs in masks.items()}


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
    x ^= x >> np.uint32(13)
    x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
    x ^= x >> np.uint32(16)
    return x


def _row_hash(planes: np.ndarray, cols, salt=0x9E3779B9) -> np.ndarray:
    h = np.full((planes.shape[0],), salt, np.uint32)
    for c in cols:
        h = _fmix32(h ^ planes[:, c].astype(np.uint32))
        h = (h * np.uint32(5) + np.uint32(0xE6546B64)).astype(np.uint32)
    return _fmix32(h)


def _clz32(x: np.ndarray) -> np.ndarray:
    out = np.full(x.shape, 32, np.int32)
    nz = x != 0
    out[nz] = 31 - np.floor(np.log2(x[nz].astype(np.float64))).astype(
        np.int32)
    return out


def hll_fold(planes: np.ndarray, cols, p: int) -> np.ndarray:
    """HyperLogLog registers (2^p of them) over the valid rows."""
    planes = np.asarray(planes)
    h = _row_hash(planes, cols)
    bucket = (h >> np.uint32(32 - p)).astype(np.int64)
    w = (h << np.uint32(p)).astype(np.uint32)
    max_rank = 32 - p + 1
    rank = np.minimum(np.where(w == 0, max_rank, _clz32(w) + 1), max_rank)
    rank = np.where(planes[:, COL_S_FLAGS] != 0, rank, 0).astype(np.int32)
    regs = np.zeros(1 << p, np.int32)
    np.maximum.at(regs, bucket, rank)
    return regs


def registers(planes: np.ndarray, p: int) -> dict[str, np.ndarray]:
    return {name: hll_fold(planes, cols, p)
            for name, cols in SKETCHES.items()}


def hll_estimate(regs: np.ndarray) -> float:
    """HyperLogLog estimate, with linear counting at small range."""
    m = regs.shape[0]
    alpha = (0.7213 / (1.0 + 1.079 / m) if m >= 128
             else {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7213))
    raw = alpha * m * m / np.sum(np.exp2(-regs.astype(np.float64)))
    zeros = int((regs == 0).sum())
    if raw <= 2.5 * m and zeros > 0:
        return float(m * np.log(m / zeros))
    return float(raw)


def _ratio(num: float, den: float) -> float:
    return float(num) / float(den) if den else 0.0


def values(c: dict, regs: dict) -> dict[str, float]:
    """metric -> value, from the counters and the register banks."""
    est = {k: hll_estimate(v) for k, v in regs.items()}
    return {
        "L1": 1.0 if c["L1"]["lic"] > 0 else 0.0,
        "L2": 1.0 if c["L2"]["hlic"] > 0 else 0.0,
        "I2": _ratio(c["I2"]["r3"], c["I2"]["total"]),
        "U1": _ratio(c["U1"]["lab_s"] + c["U1"]["lab_p"] + c["U1"]["lab_o"],
                     c["U1"]["total"]),
        "RC1": _ratio(c["RC1"]["too_long"], c["RC1"]["total"]),
        "SV3": float(c["SV3"]["malformed"]),
        "CN2": _ratio(c["CN2"]["total"] - c["CN2"]["uri_uri"],
                      c["CN2"]["total"]),
        "I1": _ratio(c["I1"]["sameas"], c["I1"]["total"]),
        "SV1": _ratio(c["SV1"]["typed"], c["SV1"]["lits"]),
        "SV2": _ratio(c["SV2"]["ok_s"] + c["SV2"]["ok_p"] + c["SV2"]["ok_o"],
                      c["SV2"]["uri_s"] + c["SV2"]["uri_p"]
                      + c["SV2"]["uri_o"]),
        "V1": _ratio(c["V1"]["lang"], c["V1"]["lits"]),
        "IO1": _ratio(c["IO1"]["blank"], c["IO1"]["total"]),
        "CS1": _ratio(c["CS1"]["self"], c["CS1"]["total"]),
        "CM1": _ratio(c["CM1"]["typed"], c["CM1"]["total"]),
        "CN2_EXACT": _ratio(est.get("spo", c["CN2_EXACT"]["total"]),
                            c["CN2_EXACT"]["total"]),
        "SCH1": float(est.get("p", 0.0)),
    }


class Assessment:
    """The reference's answer for one dataset: counters, registers,
    values and the number of triples."""

    def __init__(self, planes: np.ndarray, p: int, counter_dtype=np.int64):
        self.n_triples = int(np.count_nonzero(
            planes[:, COL_S_FLAGS] & vocab.VALID))
        self.counts = counters(planes, counter_dtype)
        self.registers = registers(planes, p)
        self.values = values(self.counts, self.registers)


def control(planes: np.ndarray, p: int) -> Assessment:
    """The reference one precision lower: 16-bit counters, ``p - 1``."""
    return Assessment(planes, p - 1, counter_dtype=np.int16)
