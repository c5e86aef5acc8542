"""Differential equivalence suite for the vectorized ingest path
(``repro.rdf.ingest``) against the legacy parser+encoder reference.

The contract under test: for ANY input — clean, dirty, or adversarial — the
vectorized tokenizer + batch dictionary encoder produces a TripleTensor that
is *byte-identical* to ``encode(parse_ntriples(text))`` (planes, ``n_terms``,
and dictionary term keys/metadata), and streaming chunked ingest composes to
the same result with bounded resident memory.
"""
import math
import os

import numpy as np
import pytest

from repro import qa, spans
from repro.rdf import (DirtProfile, TermDictionary, bsbm_ntriples, encode,
                       parse_encode, parse_ntriples, stream_chunks,
                       stream_chunks_text, vocab)
from repro.rdf import ingest

BSBM_NS = ("http://bsbm.example.org/",)
DIRTY = os.path.join(os.path.dirname(__file__), "data", "dirty.nt")


def assert_identical(text, ns=()):
    """Both paths must agree bit-for-bit, dictionary included."""
    d_ref = TermDictionary(ns)
    ref = encode(parse_ntriples(text), dictionary=d_ref)
    d_vec = TermDictionary(ns)
    vec = parse_encode(text, dictionary=d_vec)
    assert ref.planes.shape == vec.planes.shape
    assert np.array_equal(ref.planes, vec.planes)
    assert ref.n_valid == vec.n_valid and ref.n_terms == vec.n_terms
    assert d_ref.terms == d_vec.terms
    assert np.array_equal(d_ref.flags, d_vec.flags)
    assert np.array_equal(d_ref.lengths, d_vec.lengths)
    assert np.array_equal(d_ref.datatypes, d_vec.datatypes)
    return ref, vec


# --- generator corpora --------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 3, 11, 1234])
def test_differential_bsbm(seed):
    text = bsbm_ntriples(150, seed=seed)
    ref, _ = assert_identical(text, BSBM_NS)
    assert len(ref) > 300


def test_differential_bsbm_heavy_dirt():
    dirt = DirtProfile(malformed_literal=0.5, long_uri=0.4,
                       license_stmt_literal=0.1)
    assert_identical(bsbm_ntriples(100, seed=5, dirt=dirt), BSBM_NS)


def test_differential_with_comments_blanks_malformed():
    text = (
        "# header comment\n"
        "\n"
        "   \t  \n"
        '<http://a> <http://b> "x"@en .\n'
        "garbage that is not a triple\n"
        '<http://a> <http://b> <http://c> .\r\n'          # CRLF
        '_:n0 <http://b> "3.14"^^<http://www.w3.org/2001/XMLSchema#decimal> .\n'
        '<http://a>\t<http://b>\t<http://c>\t.\n'          # tab-separated
        '   <http://a> <http://b> "trailing ws" .   \n'
        '<http://a> <http://b> "no trailing newline" .')
    ref, _ = assert_identical(text, ("http://a",))
    assert len(ref) == 7  # 6 valid + 1 sentinel


def test_differential_term_shapes():
    text = (
        '<http://a> <http://b> "" .\n'
        '<http://a> <http://b> ""@en .\n'
        '<http://a> <http://b> ""^^<> .\n'                 # falsy datatype
        '<http://a> <http://b> "unicode é中文" .\n'
        '<http://ünï.example/ö> <http://b> <http://c> .\n'
        '<x:/> <a://b:c> <ab:cd://x> .\n'                  # iri_valid edges
        '<http://x> <notvalid> <x:y> .\n'
        '<http://a> <http://b> "value with spaces" .\n'
        '<http://a> <http://b> _:blank.o .\n'
        '<http://a> <http://b> "tab\tin value" .\n'
        '<http://a> <http://purl.org/dc/terms/license> <http://c> .\n'
        '<http://a> <http://www.w3.org/2000/01/rdf-schema#label> "L"@en-GB .\n'
        '<http://a> <http://www.w3.org/1999/02/22-rdf-syntax-ns#type> <http://T> .\n'
        '<http://a> <http://www.w3.org/2002/07/owl#sameAs> <http://b> .\n'
        '<http://a> <http://b> "licensed under CC-BY" .\n')
    assert_identical(text, ("http://a",))


def test_differential_escaped_literals():
    text = (
        '<http://a> <http://b> "esc \\" quote" .\n'
        '<http://a> <http://b> "nl \\n and tab \\t" .\n'
        '<http://a> <http://b> "back \\\\ slash" .\n'
        '<http://a> <http://b> "uni \\u0041\\U00000042" .\n'
        '<http://a> <http://b> "bad \\q escape" .\n'
        # escaped and raw-tab spellings of the SAME literal must intern once
        '<http://a> <http://b> "same\ttab" .\n'
        '<http://a> <http://b> "same\\ttab" .\n')
    d = TermDictionary()
    tt = parse_encode(text, dictionary=d)
    assert len(tt) == 7
    ref, vec = assert_identical(text)
    # rows 5 and 6 share one object id
    assert vec.planes[5, 2] == vec.planes[6, 2]


# The license-statement detector's semantics, pinned as literal answers: a
# rewrite of ``vocab.LICENSE_STATEMENT_RE`` or of the dedup pass that applies
# it has to give the same flag on every one of these texts.
LICENSE_TEXTS = [
    ("LICENCE: see below", True),
    ("Copyright ACME Corp", True),
    ("ALL RIGHTS RESERVED", True),
    ("(c) 2019 ACME", True),
    ("(C)2019", True),
    ("Creative Commons Attribution", True),
    ("creativecommons", True),
    ("In the Public Domain", True),
    ("CC BY 4.0", True),
    ("cc-by-sa", True),
    ("licensee", True),
    ("LİCENSE", True),                  # dotted capital I
    ("licenſe", True),                  # long s
    ("Licence ouverte", True),
    ("x" * 300 + " copyright", True),        # a wide dedup tier
    ("z" * 9000 + " license " + "z" * 100, True),   # over 8 KiB
    ("licensing terms", False),
    ("copy right", False),
    ("public-domain", False),
    ("ccby", False),
    ("(c) ACME", False),
    ("rights reserved", False),
    ("CC_BY", False),
    ("lic ense", False),
    ("2019", False),
    ("y" * 300, False),
]


@pytest.mark.parametrize("text,expected", LICENSE_TEXTS,
                         ids=[f"{i}-{e}" for i, (_, e) in
                              enumerate(LICENSE_TEXTS)])
def test_license_statement_flag(text, expected):
    assert vocab.is_license_statement(text) == expected
    for suffix in ("", "@fr", f"^^<{vocab.XSD_NS}string>"):
        line = f'<http://a> <http://b> "{text}"{suffix} .\n'
        assert_identical(line)
        d = TermDictionary()
        tt = parse_encode(line, dictionary=d)
        flags = int(d.flags[tt.planes[0, 2]])
        assert bool(flags & vocab.IS_LICENSE_STATEMENT) == expected, suffix


# --- malformed-input fuzz corpus (checked in) ---------------------------------

def test_dirty_corpus_differential():
    with open(DIRTY, "rb") as f:
        data = f.read()
    text = data.decode("utf-8")
    d_ref = TermDictionary()
    ref = encode(parse_ntriples(text), dictionary=d_ref)
    d_vec = TermDictionary()
    vec = parse_encode(data, dictionary=d_vec)
    assert np.array_equal(ref.planes, vec.planes)
    assert d_ref.terms == d_vec.terms

    # identical parse-error sentinel counts in both parsers
    def sentinels(d, tt):
        sid = {t: i for i, t in enumerate(d.terms)}.get(
            "<urn:repro:parse-error>")
        if sid is None:
            return 0
        return int((tt.planes[:, 0] == sid).sum())
    n_ref, n_vec = sentinels(d_ref, ref), sentinels(d_vec, vec)
    assert n_ref == n_vec and n_ref >= 10

    # a finite SV3 (malformed-datatype count) must come out of assessment
    res = qa.assess(vec, metrics="paper")
    assert math.isfinite(res.values["SV3"])
    assert res.values["SV3"] >= 1.0  # the "bad"^^xsd:integer line


def test_dirty_corpus_streams_identically(tmp_path):
    whole = parse_encode(open(DIRTY, "rb").read())
    chunks = list(stream_chunks(DIRTY, 7, block_bytes=512))
    assert np.array_equal(np.concatenate([c.planes for c in chunks]),
                          whole.planes)


# --- streaming ----------------------------------------------------------------

def test_stream_chunks_exact_sizes_and_shared_ids(tmp_path):
    text = bsbm_ntriples(120, seed=2)
    path = tmp_path / "d.nt"
    path.write_text(text)
    whole = parse_encode(text, base_namespaces=BSBM_NS)
    chunks = list(stream_chunks(path, 64, base_namespaces=BSBM_NS,
                                block_bytes=1024))
    assert all(c.n_rows == 64 for c in chunks[:-1])
    assert 0 < chunks[-1].n_rows <= 64
    cat = np.concatenate([c.planes for c in chunks])
    assert np.array_equal(cat, whole.planes)       # global term ids
    n_terms = [c.n_terms for c in chunks]
    assert n_terms == sorted(n_terms)              # dictionary only grows
    assert n_terms[-1] == whole.n_terms


def test_stream_chunks_tiny_blocks_carry_remainders():
    text = bsbm_ntriples(40, seed=9)
    whole = parse_encode(text, base_namespaces=BSBM_NS)
    # block smaller than most lines: every read carries a partial line
    chunks = list(stream_chunks_text(text, 13, base_namespaces=BSBM_NS,
                                     block_bytes=32))
    cat = np.concatenate([c.planes for c in chunks])
    assert np.array_equal(cat, whole.planes)


def test_stream_chunks_edge_inputs(tmp_path):
    empty = tmp_path / "empty.nt"
    empty.write_text("")
    assert list(stream_chunks(empty, 10)) == []
    comments = tmp_path / "c.nt"
    comments.write_text("# only\n# comments\n\n")
    assert list(stream_chunks(comments, 10)) == []
    no_nl = tmp_path / "n.nt"
    no_nl.write_text("<http://a> <http://b> <http://c> .")  # no newline
    [only] = list(stream_chunks(no_nl, 10))
    assert len(only) == 1
    with pytest.raises(ValueError, match="chunk_triples"):
        list(stream_chunks(no_nl, 0))


def test_stream_shared_dictionary_across_files(tmp_path):
    a, b = tmp_path / "a.nt", tmp_path / "b.nt"
    a.write_text('<http://x> <http://p> <http://y> .\n')
    b.write_text('<http://x> <http://p> <http://z> .\n')
    d = TermDictionary()
    ca = list(stream_chunks(a, 10, dictionary=d))
    cb = list(stream_chunks(b, 10, dictionary=d))
    # shared subject/predicate resolve to the same global ids
    assert ca[0].planes[0, 0] == cb[0].planes[0, 0]
    assert ca[0].planes[0, 1] == cb[0].planes[0, 1]
    assert len(d) == 4


# --- assessment equivalence matrix -------------------------------------------

def test_assess_matrix_legacy_vectorized_single_streamed(tmp_path):
    """qa.assess values identical across {legacy, vectorized} ingest ×
    {single-shot, streamed-chunks} execution — sketches included, because
    streamed chunks share one dictionary (global term ids)."""
    text = bsbm_ntriples(80, seed=4)
    path = tmp_path / "m.nt"
    path.write_text(text)

    legacy_tt = encode(parse_ntriples(text), base_namespaces=BSBM_NS)
    pipe = qa.pipeline().metrics("all").base(*BSBM_NS)

    ref = pipe.run(legacy_tt)                                # legacy single
    legacy_chunked = pipe.chunked(5).run(legacy_tt)          # legacy chunked
    vec_single = pipe.run(str(path))                         # vector single
    vec_streamed = pipe.streamed(64).run(str(path))          # vector streamed
    vec_streamed_gen = pipe.run(
        stream_chunks(path, 64, base_namespaces=BSBM_NS))    # explicit stream

    for other in (legacy_chunked, vec_single, vec_streamed, vec_streamed_gen):
        assert set(other.values) == set(ref.values)
        for k, v in ref.values.items():
            assert other.values[k] == pytest.approx(v, abs=0), k
        assert other.n_triples == ref.n_triples
    assert vec_streamed.exec_stats is not None
    assert vec_streamed.exec_stats.chunks_total >= 2


def test_pipeline_streamed_text_and_describe():
    text = bsbm_ntriples(30, seed=6)
    pipe = qa.pipeline().metrics("paper").base(*BSBM_NS)
    ref = pipe.run(text)
    streamed = pipe.streamed(32).run(text)
    for k, v in ref.values.items():
        assert streamed.values[k] == pytest.approx(v, abs=0), k
    assert "streamed@32" in pipe.streamed(32).describe()
    assert pipe.streamed(32).single_shot().exec.stream_triples == 0
    with pytest.raises(ValueError, match="stream_triples"):
        qa.ExecutionConfig(stream_triples=-1)
    with pytest.raises(FileNotFoundError):
        qa.pipeline().streamed(8).run("no_such_file.nt")


# --- fast-path internals ------------------------------------------------------

def test_dedup_matches_reference_interning():
    """The batch np.unique dedup must assign first-appearance ids exactly
    like sequential interning, mixing fast and fallback lines."""
    text = ('<http://a> <http://b> <http://a> .\n'     # term reuse s==o
            'malformed line\n'
            '<http://a> <http://b> "esc\\"" .\n'       # fallback literal
            '<http://c> <http://b> <http://a> .\n')
    d = TermDictionary()
    tt = parse_encode(text, dictionary=d)
    assert tt.planes[0, 0] == tt.planes[0, 2]          # s == o id
    assert d.terms[0] == "<http://a>"                  # first-appearance order
    assert d.terms[1] == "<http://b>"
    assert len(tt) == 4


def test_vectorized_iri_validity_matches_regex():
    cases = ["http://ok.example/x", "x:/", "a://b:c", "ab:cd://x", "ftp://y",
             "notvalid", "x:y", "1http://bad", "http//missing", "urn:x",
             "http://sp ace", "http://brace{x}", 'http://quote"x',
             "a+b.c-9://tail", "://nohead", "http://"]
    text = "".join(f'<http://s> <http://p> <{c}> .\n' for c in cases)
    _, vec = assert_identical(text)
    got = [(f & vocab.IRI_VALID) != 0 for f in vec.planes[:, 5]]
    want = [vocab.iri_valid(c) for c in cases]
    assert got == want


# --- tokens over 128 B: the wide dedup tiers -----------------------------------

XSD = vocab.XSD_NS
CAP = ingest.MAX_FAST_TOKEN
NARROW = 128             # the widest of the two narrow dedup tiers
LONG_LENGTHS = (128, 129, 255, 256, 257, 2200, CAP, CAP + 1)


def _fill(unit, nbytes):
    """``unit`` repeated, then ASCII padding, to exactly ``nbytes`` bytes."""
    s = unit * (nbytes // len(unit.encode()))
    return s + "a" * (nbytes - len(s.encode()))


def _iri(n):
    return "<http://example.org/" + _fill("x", n - 21) + ">"


def _lit(n, unit="y", suffix=""):
    return '"' + _fill(unit, n - 2 - len(suffix)) + '"' + suffix


# each shape puts one token of exactly n bytes into a line
LONG_SHAPES = {
    "plain": lambda n: f"<http://s> <http://p> {_lit(n)} .",
    "lang": lambda n: f"<http://s> <http://p> {_lit(n, 'w ', '@en')} .",
    "xsd_string": lambda n: (
        f"<http://s> <http://p> {_lit(n, 'v ', f'^^<{XSD}string>')} ."),
    "xsd_integer": lambda n: (
        f"<http://s> <http://p> {_lit(n, '7', f'^^<{XSD}integer>')} ."),
    "iri_subject": lambda n: f"{_iri(n)} <http://p> <http://o> .",
    "iri_object": lambda n: f"<http://s> <http://p> {_iri(n)} .",
    "blank": lambda n: f"_:{_fill('b', n - 2)} <http://p> <http://o> .",
    "raw_tab": lambda n: "<http://s> <http://p> " + _lit(n, "t\t") + " .",
    "non_ascii": lambda n: f"<http://s> <http://p> {_lit(n, 'é中')} .",
    "license": lambda n: (
        f'<http://s> <http://p> "Creative Commons {_fill("z", n - 19)}" .'),
}


def _long_case(name):
    """(text, token occurrences over 128 B and within the cap, lines
    holding a token over the cap) of one case."""
    if name in LONG_SHAPES:
        text = "".join(LONG_SHAPES[name](n) + "\n" for n in LONG_LENGTHS)
        wide = sum(NARROW < n <= CAP for n in LONG_LENGTHS)
        return text + "<http://s> <http://p> <http://o> .\n", wide, 1
    if name == "repeated":
        # the same long tokens within a block and, streamed, across blocks
        lit, iri = _lit(2200, "r "), _iri(300)
        lines = [f"{iri} <http://p> {lit} .",
                 "<http://s> <http://p> <http://o> .",
                 f"<http://s> <http://p> {lit} .",
                 f"<http://s> <http://p> {iri} .",
                 f"{iri} <http://p> {lit} ."]
        return "".join(ln + "\n" for ln in lines), 6, 0
    # iri_and_literal: a 321 B IRI subject and a 502 B literal object
    long_iri = "http://example.org/" + "x" * 300
    return (f'<{long_iri}> <http://p> "{"y" * 500}" .\n'
            '<http://s> <http://p> <http://o> .\n'), 2, 0


@pytest.mark.parametrize("name", sorted(LONG_SHAPES) + ["repeated",
                                                         "iri_and_literal"])
def test_long_tokens_match_reference(name):
    """Tokens up to the cap take the vectorized path in tiers wider than
    128 B, one over it the reference parser; either way the planes and the
    dictionary are the reference's, single-shot and streamed in blocks
    smaller than a line."""
    text, n_wide, n_fallback = _long_case(name)
    with spans.run() as rec:
        ref, _ = assert_identical(text)
    assert rec.counts["ingest.wide_tokens"] == n_wide
    assert rec.counts["ingest.fallback_lines"] == n_fallback
    d_ref = TermDictionary()
    encode(parse_ntriples(text), dictionary=d_ref)
    d = TermDictionary()
    chunks = list(stream_chunks_text(text, 3, dictionary=d, block_bytes=1024))
    assert np.array_equal(np.concatenate([c.planes for c in chunks]),
                          ref.planes)
    assert d.terms == d_ref.terms
    assert np.array_equal(d.flags, d_ref.flags)


def test_wide_tiers_exist_only_for_long_tokens(monkeypatch):
    """A block with no token over 128 B dedups in the 64 and 128 B tiers
    alone; longer tokens add doubling tiers, each under 2x its bytes."""
    seen = []
    real = ingest._tier_dedup

    def recording(pad, ts, lens, W):
        seen.append((W, lens.size * W, int(lens.sum())))
        return real(pad, ts, lens, W)

    monkeypatch.setattr(ingest, "_tier_dedup", recording)
    narrow = ('<http://s> <http://p> "x" .\n'
              f'<http://s> <http://p> {_lit(128)} .\n')
    assert_identical(narrow)
    assert [w for w, _, _ in seen] == [64, 128]
    seen.clear()
    wide = "".join(f"{_iri(n)} <http://p> {_lit(n)} .\n"
                   for n in (129, 300, 2200, 2200))
    assert_identical(wide)
    assert [w for w, _, _ in seen] == [64, 256, 512, 4096]
    for W, mat_bytes, token_bytes in seen[1:]:
        assert mat_bytes < 2 * token_bytes


def test_parse_encode_accepts_bytes_and_str():
    text = '<http://a> <http://b> "x" .\n'
    a = parse_encode(text)
    b = parse_encode(text.encode("utf-8"))
    assert np.array_equal(a.planes, b.planes)


def test_surrogate_escapes_stay_escaped_and_intern():
    """Regression: \\uD800-\\uDFFF decode to lone surrogates, which cannot
    be UTF-8 encoded — they must stay escaped so interning never crashes."""
    text = '<http://s> <http://p> "a\\uD800b und \\uFFFF ok" .\n'
    ref, vec = assert_identical(text)
    assert len(ref) == 1
    t = parse_ntriples(text)[0][2]
    assert "\\uD800" in t.value and "￿" in t.value


def test_unicode_digit_typed_literals_match_reference():
    """Regression: the reference lexical regex \\d is unicode-aware; typed
    literals with non-ASCII values must not diverge from it."""
    text = ('<http://s> <http://p> "١٢٣"^^'
            '<http://www.w3.org/2001/XMLSchema#integer> .\n'
            '<http://s> <http://p> "12é4"^^'
            '<http://www.w3.org/2001/XMLSchema#integer> .\n')
    _, vec = assert_identical(text)
    assert (vec.planes[0, 5] & vocab.LEXICAL_OK)       # arabic-indic digits
    assert not (vec.planes[1, 5] & vocab.LEXICAL_OK)


def test_comment_lines_with_embedded_line_breaks():
    """Regression: legacy splitlines splits '#...' lines at \\r/\\f/NEL —
    content after the break is NOT part of the comment."""
    text = ('#c\r<http://a> <http://b> <http://c> .\n'
            '#c\x0cgarbage after formfeed\n'
            '#c\x85<http://a> <http://b> <http://d> .\n'
            '# a normal comment\n'
            '<http://a> <http://b> <http://e> .\n')
    ref, vec = assert_identical(text)
    assert len(ref) == 4  # 3 post-break lines (2 triples + 1 sentinel) + 1


def test_invalid_utf8_fails_loudly():
    """Invalid bytes fail at ingest (like a text-mode read would), never by
    poisoning the dictionary or crashing deep in a fallback decode."""
    with pytest.raises(UnicodeDecodeError):
        parse_encode(b'\xff not a triple\n')
    with pytest.raises(UnicodeDecodeError):
        parse_encode(b'<http://s\xff> <http://p> <http://o> .\n')


def test_streamed_checkpointing(tmp_path):
    """--stream + checkpoint_dir must actually checkpoint and resume."""
    text = bsbm_ntriples(60, seed=13)
    path = tmp_path / "s.nt"
    path.write_text(text)
    ck = tmp_path / "ckpt"
    pipe = qa.pipeline().metrics("paper").base(*BSBM_NS)
    res = pipe.streamed(64, checkpoint_dir=str(ck), checkpoint_every=1).run(
        str(path))
    assert res.exec_stats.checkpoints_written >= 1
    res2 = pipe.streamed(64, checkpoint_dir=str(ck), checkpoint_every=1).run(
        str(path))
    assert res2.exec_stats.resumed_from is not None
    assert res2.exec_stats.attempts == 0
    assert res2.values == res.values


# --- transparent gzip ---------------------------------------------------------

def test_parse_encode_gzip_bytes_differential():
    """A gzipped payload decodes to the identical TripleTensor — gzip is
    sniffed from magic bytes, never from a filename suffix."""
    import gzip

    text = bsbm_ntriples(60, seed=21, dirt=DirtProfile(0.1, 0.1, 0.05))
    raw = parse_encode(text.encode("utf-8"), base_namespaces=BSBM_NS)
    gz = parse_encode(gzip.compress(text.encode("utf-8")),
                      base_namespaces=BSBM_NS)
    assert np.array_equal(raw.planes, gz.planes)
    assert raw.n_terms == gz.n_terms and raw.n_valid == gz.n_valid


def test_qa_assess_accepts_bytes_and_gzip_bytes():
    """The front door takes raw or gzipped bytes directly — same values
    and registers as the equivalent text, single-shot and streamed."""
    import gzip

    text = bsbm_ntriples(50, seed=24)
    want = qa.assess(text, metrics="paper", base=BSBM_NS)
    for payload in (text.encode("utf-8"),
                    gzip.compress(text.encode("utf-8"))):
        got = qa.assess(payload, metrics="paper", base=BSBM_NS)
        assert got.values == want.values
        for k in want.registers:
            np.testing.assert_array_equal(got.registers[k],
                                          want.registers[k])
    streamed = qa.pipeline().metrics("paper").base(*BSBM_NS).streamed(
        16).run(gzip.compress(text.encode("utf-8")))
    assert streamed.values == want.values


def test_stream_chunks_over_gzip_file(tmp_path):
    """Chunked streaming over a ``.nt.gz`` file composes to the plain
    whole-file result (segmentation runs on the decompressed stream)."""
    import gzip

    text = bsbm_ntriples(80, seed=22)
    gz_path = tmp_path / "d.nt.gz"
    gz_path.write_bytes(gzip.compress(text.encode("utf-8")))
    whole = parse_encode(text, base_namespaces=BSBM_NS)
    chunks = list(stream_chunks(gz_path, 64, base_namespaces=BSBM_NS,
                                block_bytes=1024))
    cat = np.concatenate([c.planes for c in chunks])
    assert np.array_equal(cat, whole.planes)
    assert chunks[-1].n_terms == whole.n_terms


def test_gzip_twin_reuses_frozen_segments(tmp_path):
    """Incremental assessment of a dataset's ``.nt.gz`` twin reuses the
    segments frozen by its plain-text run: CDC segmentation happens on
    decompressed bytes, so nothing is rescanned."""
    import gzip

    text = bsbm_ntriples(70, seed=23)
    plain, gzed = tmp_path / "d.nt", tmp_path / "twin.nt.gz"
    plain.write_text(text)
    gzed.write_bytes(gzip.compress(text.encode("utf-8")))
    store = tmp_path / "store"
    pipe = qa.pipeline().metrics("paper").base(*BSBM_NS)
    first = pipe.incremental(str(store)).run(str(plain))
    second = pipe.incremental(str(store)).run(str(gzed))
    assert second.values == first.values
    assert second.exec_stats.bytes_rescanned == 0
