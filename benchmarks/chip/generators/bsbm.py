"""The Berlin SPARQL Benchmark (BSBM) e-commerce dataset as N-Triples lines,
and the update transactions of its "Explore and Update" use case.

The rules are those of the BSBM V3.1 specification (Bizer and Schultz),
section "Benchmark Dataset", and of its data generator:

* the dataset scales with its number of products, and every product has
  20 offers and 10 reviews;
* products per producer are drawn from N(50, 16.6), offers per vendor
  from N(2000, 667), reviews per reviewer from N(20, 6.6);
* product types form a hierarchy and product features a pool, sized as
  the specification's table has them (151 types and 4,745 features for
  2,785 products = 1M triples; 731 and 23,833 for 70,812 products = 25M),
  between and beyond those as a power of the product count;
* each class carries the properties the generator writes: see
  ``_products``, ``_offers``, ``_reviews`` and the others below, one line
  per statement, in the generator's order (types, features, producers with
  their products, vendors with their offers, rating sites with their
  reviewers and reviews).

What the specification leaves to its generator's code (the word list, the
words per text, the share of optional properties, the features per
product, the languages of review texts) this module fixes, and the
configurations list it under ``assumed``.  The optional shares and the
features per product are set so that 2,785 products give the table's
1,000,313 statements to within 1%.

Every draw comes from the seed, and a dump of ``n_triples`` has exactly
that many lines for every seed (the generator's output for enough
products, cut after the ``n_triples``-th statement, which falls among
the last rating site's reviews), so seeds change the data and never its
size.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

NS = "http://www4.wiwiss.fu-berlin.de/bizer/bsbm/v01/"
VOC = NS + "vocabulary/"
INST = NS + "instances/"
XSD = "http://www.w3.org/2001/XMLSchema#"
TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
LABEL = "<http://www.w3.org/2000/01/rdf-schema#label>"
COMMENT = "<http://www.w3.org/2000/01/rdf-schema#comment>"
SUBCLASS = "<http://www.w3.org/2000/01/rdf-schema#subClassOf>"
DC = "http://purl.org/dc/elements/1.1/"
FOAF = "http://xmlns.com/foaf/0.1/"
REV = "http://purl.org/stuff/rev#"
COUNTRY = "http://downlode.org/rdf/iso-3166/countries#"
COUNTRIES = ("US", "GB", "DE", "FR", "ES", "AT", "JP", "CN", "RU", "KR")
LANGUAGES = ("en", "de", "fr", "es", "ja", "zh", "ru", "ko")

# the specification's table: (products, product types, product features)
SCALE_1M, SCALE_25M = (2785, 151, 4745), (70812, 731, 23833)
OFFERS_PER_PRODUCT, REVIEWS_PER_PRODUCT = 20, 10
PRODUCTS_PER_PRODUCER = (50.0, 16.6)
OFFERS_PER_VENDOR = (2000.0, 667.0)
REVIEWS_PER_REVIEWER = (20.0, 6.6)
TYPE_BRANCHING = 5
STATEMENTS_PER_OFFER = 10


@dataclasses.dataclass(frozen=True)
class Words:
    """Words per text, as (fewest, most), and the shares of optional
    properties: the generator's own choices, listed under ``assumed``."""
    label: tuple = (1, 3)
    comment: tuple = (50, 150)
    textual: tuple = (3, 15)
    title: tuple = (4, 15)
    review: tuple = (50, 200)
    features: tuple = (17, 31)
    optional_property: float = 0.5
    rating: float = 0.7
    dictionary: int = 10_000
    corpus: int = 1 << 20


RULES = Words()


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a stream number."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def scaled(n_products: int, at_1m: int, at_25m: int) -> int:
    """A count of the specification's table at ``n_products`` products: the
    power of the product count through its 1M and 25M columns."""
    a = math.log(at_25m / at_1m) / math.log(SCALE_25M[0] / SCALE_1M[0])
    return max(4, round(at_1m * (n_products / SCALE_1M[0]) ** a))


def _dictionary(n: int) -> list[str]:
    """``n`` made-up lowercase words of 3 to 10 letters, the same for every
    seed (the specification's generator draws from a word list)."""
    rng = rng_for(0x6B53, 7)
    lens = rng.integers(3, 11, n)
    letters = rng.integers(0, 26, int(lens.sum())).astype(np.uint8) + 97
    text = letters.tobytes().decode()
    ends = np.cumsum(lens)
    return [text[e - k:e] for e, k in zip(ends.tolist(), lens.tolist())]


class _Text:
    """Texts of random words: a window of a corpus drawn from the seed, so
    that a text costs one slice."""

    def __init__(self, rng: np.random.Generator, rules: Words):
        words = _dictionary(rules.dictionary)
        pick = rng.integers(0, len(words), rules.corpus)
        self.corpus = " ".join(words[i] for i in pick.tolist()) + " "
        lens = np.fromiter((len(words[i]) + 1 for i in pick.tolist()),
                           np.int64, rules.corpus)
        self.starts = np.concatenate([[0], np.cumsum(lens)])
        self.rng = rng

    def many(self, n: int, span: tuple) -> list[str]:
        lo, hi = span
        k = self.rng.integers(lo, hi + 1, n)
        first = self.rng.integers(0, len(self.starts) - hi - 1, n)
        a = self.starts[first].tolist()
        b = (self.starts[first + k] - 1).tolist()
        c = self.corpus
        return [c[i:j] for i, j in zip(a, b)]


def _date(rng, n: int, first: str, days: int) -> list[str]:
    base = np.datetime64(first)
    return [str(d) for d in base + rng.integers(0, days, n)]


def _datetime(rng, n: int, first: str, days: int) -> list[str]:
    base = np.datetime64(first + "T00:00:00")
    return [str(d) for d in base + rng.integers(0, days * 86400, n)]


def _groups(rng, total: int, mean_sd: tuple) -> np.ndarray:
    """Group sizes drawn from a rounded normal (at least 1) until they
    cover ``total`` items; the last group is cut to fit."""
    mean, sd = mean_sd
    sizes = []
    left = total
    while left > 0:
        s = np.maximum(1, np.round(rng.normal(mean, sd, max(
            8, int(left / mean) + 8)))).astype(np.int64)
        for x in s.tolist():
            sizes.append(min(x, left))
            left -= sizes[-1]
            if left == 0:
                break
    return np.asarray(sizes, np.int64)


def lit(value: str, datatype: str) -> str:
    return f'"{value}"^^<{XSD}{datatype}>'


@dataclasses.dataclass
class Dump:
    """A dump as lines (without newlines), the first line of each offer
    in it, and what a transaction draws from."""
    lines: list[str]
    offers: list[int]
    text: _Text
    leaves: np.ndarray          # product types a product can have
    n_features: int
    n_producers: int
    n_vendors: int
    reviewer_site: np.ndarray   # rating site of each reviewer
    next_product: int
    next_offer: int
    next_review: int

    def data(self) -> bytes:
        return ("\n".join(self.lines) + "\n").encode()


def producer_iri(j: int) -> str:
    return f"<{INST}dataFromProducer{j}/Producer{j}>"


def vendor_iri(v: int) -> str:
    return f"<{INST}dataFromVendor{v}/Vendor{v}>"


def site_iri(r: int) -> str:
    return f"<{INST}dataFromRatingSite{r}/RatingSite{r}>"


def _publisher_lines(s: str, cls: str, label: str, comment: str,
                     homepage: str, country: str, date: str) -> list[str]:
    """A producer's or vendor's statements; it publishes them itself."""
    return [f"{s} {TYPE} <{VOC}{cls}> .",
            f"{s} {LABEL} \"{label}\" .",
            f"{s} {COMMENT} \"{comment}\" .",
            f"{s} <{FOAF}homepage> <{homepage}> .",
            f"{s} <{VOC}country> <{COUNTRY}{country}> .",
            f"{s} <{DC}publisher> {s} .",
            f"{s} <{DC}date> {lit(date, 'date')} ."]


def _types(rng, text: _Text, n: int, rules: Words):
    """The product type hierarchy, breadth first from its root (type 1):
    its lines and its leaves."""
    parent = [0] + [1 + (i - 1) // TYPE_BRANCHING for i in range(1, n)]
    has_child = np.zeros(n + 1, bool)
    has_child[parent[1:]] = True
    labels, comments = text.many(n, rules.label), text.many(n, rules.comment)
    dates = _date(rng, n, "2000-01-01", 2900)
    pub = f"<{INST}StandardizationInstitution1>"
    lines = []
    for i in range(1, n + 1):
        s = f"<{INST}ProductType{i}>"
        lines += [f"{s} {TYPE} <{VOC}ProductType> .",
                  f"{s} {LABEL} \"{labels[i - 1]}\" .",
                  f"{s} {COMMENT} \"{comments[i - 1]}\" ."]
        if parent[i - 1]:
            lines.append(f"{s} {SUBCLASS} <{INST}ProductType"
                         f"{parent[i - 1]}> .")
        lines += [f"{s} <{DC}publisher> {pub} .",
                  f"{s} <{DC}date> {lit(dates[i - 1], 'date')} ."]
    leaves = np.flatnonzero(~has_child[1:]) + 1
    return lines, leaves


def _features(rng, text: _Text, n: int, rules: Words) -> list[str]:
    labels, comments = text.many(n, rules.label), text.many(n, rules.comment)
    dates = _date(rng, n, "2000-01-01", 2900)
    pub = f"<{INST}StandardizationInstitution1>"
    lines = []
    for i in range(1, n + 1):
        s = f"<{INST}ProductFeature{i}>"
        lines += [f"{s} {TYPE} <{VOC}ProductFeature> .",
                  f"{s} {LABEL} \"{labels[i - 1]}\" .",
                  f"{s} {COMMENT} \"{comments[i - 1]}\" .",
                  f"{s} <{DC}publisher> {pub} .",
                  f"{s} <{DC}date> {lit(dates[i - 1], 'date')} ."]
    return lines


def _products(rng, text: _Text, ids, producers, leaves, n_features: int,
              rules: Words) -> list[str]:
    n = len(ids)
    typ = leaves[rng.integers(0, len(leaves), n)].tolist()
    labels, comments = text.many(n, rules.label), text.many(n, rules.comment)
    textual = [text.many(n, rules.textual) for _ in range(5)]
    numeric = rng.integers(1, 2001, (5, n)).tolist()
    optional = (rng.random((4, n)) < rules.optional_property).tolist()
    n_feat = rng.integers(rules.features[0], rules.features[1] + 1,
                          n).tolist()
    dates = _date(rng, n, "2000-01-01", 2900)
    lines = []
    for k in range(n):
        i, j = int(ids[k]), int(producers[k])
        s = f"<{INST}dataFromProducer{j}/Product{i}>"
        prod = producer_iri(j)
        lines += [f"{s} {TYPE} <{VOC}Product> .",
                  f"{s} {TYPE} <{INST}ProductType{typ[k]}> .",
                  f"{s} {LABEL} \"{labels[k]}\" .",
                  f"{s} {COMMENT} \"{comments[k]}\" .",
                  f"{s} <{VOC}producer> {prod} ."]
        feats = np.sort(rng.choice(n_features, n_feat[k], replace=False))
        lines += [f"{s} <{VOC}productFeature> <{INST}ProductFeature"
                  f"{f + 1}> ." for f in feats.tolist()]
        for t in range(5):
            if t < 3 or optional[t - 3][k]:
                lines.append(f"{s} <{VOC}productPropertyTextual{t + 1}> "
                             f"\"{textual[t][k]}\" .")
        for t in range(5):
            if t < 3 or optional[t - 1][k]:
                lines.append(f"{s} <{VOC}productPropertyNumeric{t + 1}> "
                             f"{lit(numeric[t][k], 'integer')} .")
        lines += [f"{s} <{DC}publisher> {prod} .",
                  f"{s} <{DC}date> {lit(dates[k], 'date')} ."]
    return lines


def _offers(rng, ids, vendors, products, product_producer) -> list[str]:
    """Ten statements per offer, in order."""
    n = len(ids)
    price = rng.integers(500, 1_000_001, n).tolist()
    start = np.datetime64("2008-01-01") + rng.integers(0, 180, n)
    valid_to = start + rng.integers(30, 181, n)
    days = rng.integers(1, 22, n).tolist()
    dates = _date(rng, n, "2008-01-01", 180)
    start, valid_to = start.tolist(), valid_to.tolist()
    lines = []
    for k in range(n):
        o, v, p = int(ids[k]), int(vendors[k]), int(products[k])
        j = int(product_producer[k])
        s = f"<{INST}dataFromVendor{v}/Offer{o}>"
        lines += [f"{s} {TYPE} <{VOC}Offer> .",
                  f"{s} <{VOC}product> <{INST}dataFromProducer{j}/"
                  f"Product{p}> .",
                  f"{s} <{VOC}vendor> {vendor_iri(v)} .",
                  f"{s} <{VOC}price> \"{price[k] // 100}."
                  f"{price[k] % 100:02d}\"^^<{VOC}USD> .",
                  f"{s} <{VOC}validFrom> "
                  f"{lit(str(start[k]) + 'T00:00:00', 'dateTime')} .",
                  f"{s} <{VOC}validTo> "
                  f"{lit(str(valid_to[k]) + 'T00:00:00', 'dateTime')} .",
                  f"{s} <{VOC}deliveryDays> {lit(days[k], 'integer')} .",
                  f"{s} <{VOC}offerWebpage> <http://www.vendor{v}.com/"
                  f"Offer{o}/> .",
                  f"{s} <{DC}publisher> {vendor_iri(v)} .",
                  f"{s} <{DC}date> {lit(dates[k], 'date')} ."]
    return lines


def _reviews(rng, text: _Text, ids, reviewers, sites, products,
             product_producer, rules: Words) -> list[str]:
    n = len(ids)
    titles, texts = text.many(n, rules.title), text.many(n, rules.review)
    lang = rng.integers(0, len(LANGUAGES), n).tolist()
    when = _datetime(rng, n, "2007-06-01", 390)
    ratings = rng.integers(1, 11, (4, n)).tolist()
    rated = (rng.random((4, n)) < rules.rating).tolist()
    dates = _date(rng, n, "2008-01-01", 180)
    lines = []
    for k in range(n):
        i, r, site = int(ids[k]), int(reviewers[k]), int(sites[k])
        j, p = int(product_producer[k]), int(products[k])
        s = f"<{INST}dataFromRatingSite{site}/Review{i}>"
        lines += [f"{s} {TYPE} <{VOC}Review> .",
                  f"{s} <{VOC}reviewFor> <{INST}dataFromProducer{j}/"
                  f"Product{p}> .",
                  f"{s} <{REV}reviewer> <{INST}dataFromRatingSite{site}/"
                  f"Reviewer{r}> .",
                  f"{s} <{VOC}reviewDate> {lit(when[k], 'dateTime')} .",
                  f"{s} <{DC}title> \"{titles[k]}\" .",
                  f"{s} <{REV}text> \"{texts[k]}\"@{LANGUAGES[lang[k]]} ."]
        for t in range(4):
            if rated[t][k]:
                lines.append(f"{s} <{VOC}rating{t + 1}> "
                             f"{lit(ratings[t][k], 'integer')} .")
        lines += [f"{s} <{DC}publisher> {site_iri(site)} .",
                  f"{s} <{DC}date> {lit(dates[k], 'date')} ."]
    return lines


def _person(rng, text: _Text, r: int, site: int) -> list[str]:
    s = f"<{INST}dataFromRatingSite{site}/Reviewer{r}>"
    name = text.many(1, (1, 1))[0].capitalize()
    mbox = rng.bytes(20).hex()
    return [f"{s} {TYPE} <{FOAF}Person> .",
            f"{s} <{FOAF}name> \"{name}\" .",
            f"{s} <{FOAF}mbox_sha1sum> \"{mbox}\" .",
            f"{s} <{VOC}country> <{COUNTRY}"
            f"{COUNTRIES[int(rng.integers(len(COUNTRIES)))]}> .",
            f"{s} <{DC}publisher> {site_iri(site)} .",
            f"{s} <{DC}date> "
            f"{lit(_date(rng, 1, '2008-01-01', 180)[0], 'date')} ."]


def generate(n_products: int, seed: int, rules: Words = RULES) -> Dump:
    """The whole dataset for ``n_products`` products."""
    rng = rng_for(seed)
    text = _Text(rng, rules)
    n_types = scaled(n_products, SCALE_1M[1], SCALE_25M[1])
    n_features = scaled(n_products, SCALE_1M[2], SCALE_25M[2])
    lines, leaves = _types(rng, text, n_types, rules)
    lines += _features(rng, text, n_features, rules)

    per_producer = _groups(rng, n_products, PRODUCTS_PER_PRODUCER)
    product_producer = np.repeat(np.arange(1, len(per_producer) + 1),
                                 per_producer)
    first = 0
    for j, m in enumerate(per_producer.tolist(), 1):
        lines += _publisher_lines(
            producer_iri(j), "Producer", text.many(1, rules.label)[0],
            text.many(1, rules.comment)[0], f"http://www.Producer{j}.com/",
            COUNTRIES[int(rng.integers(len(COUNTRIES)))],
            _date(rng, 1, "2000-01-01", 2900)[0])
        lines += _products(rng, text, np.arange(first + 1, first + m + 1),
                           product_producer[first:first + m], leaves,
                           n_features, rules)
        first += m

    # every product has exactly its offers and reviews, in random order
    n_offers = OFFERS_PER_PRODUCT * n_products
    offer_product = rng.permutation(np.repeat(
        np.arange(1, n_products + 1), OFFERS_PER_PRODUCT))
    per_vendor = _groups(rng, n_offers, OFFERS_PER_VENDOR)
    offer_vendor = np.repeat(np.arange(1, len(per_vendor) + 1), per_vendor)
    offers = []
    first = 0
    for v, m in enumerate(per_vendor.tolist(), 1):
        lines += _publisher_lines(
            vendor_iri(v), "Vendor", text.many(1, rules.label)[0],
            text.many(1, rules.comment)[0], f"http://www.vendor{v}.com/",
            COUNTRIES[int(rng.integers(len(COUNTRIES)))],
            _date(rng, 1, "2000-01-01", 2900)[0])
        offers += range(len(lines), len(lines) + STATEMENTS_PER_OFFER * m,
                        STATEMENTS_PER_OFFER)
        prods = offer_product[first:first + m]
        lines += _offers(rng, np.arange(first + 1, first + m + 1),
                         offer_vendor[first:first + m], prods,
                         product_producer[prods - 1])
        first += m

    n_reviews = REVIEWS_PER_PRODUCT * n_products
    review_product = rng.permutation(np.repeat(
        np.arange(1, n_products + 1), REVIEWS_PER_PRODUCT))
    per_reviewer = _groups(rng, n_reviews, REVIEWS_PER_REVIEWER)
    # one rating site for every 100 reviewers
    reviewer_site = np.arange(len(per_reviewer)) // 100 + 1
    first = 0
    for r, m in enumerate(per_reviewer.tolist(), 1):
        site = int(reviewer_site[r - 1])
        if (r - 1) % 100 == 0:
            lines += [f"{site_iri(site)} {TYPE} <{VOC}RatingSite> .",
                      f"{site_iri(site)} {LABEL} "
                      f"\"{text.many(1, rules.label)[0]}\" .",
                      f"{site_iri(site)} <{FOAF}homepage> "
                      f"<http://www.ratingsite{site}.com/> ."]
        lines += _person(rng, text, r, site)
        prods = review_product[first:first + m]
        lines += _reviews(rng, text, np.arange(first + 1, first + m + 1),
                          np.full(m, r), np.full(m, site), prods,
                          product_producer[prods - 1], rules)
        first += m
    return Dump(lines, offers, text, leaves, n_features, len(per_producer),
                len(per_vendor), reviewer_site, n_products + 1, n_offers + 1,
                n_reviews + 1)


def products_for(n_triples: int) -> int:
    """Products whose dataset holds at least ``n_triples`` statements:
    the table's 1M column (359 statements a product), with room."""
    return max(2, math.ceil(n_triples / 359.0 * 1.04) + 2)


def dump(n_triples: int, seed: int, rules: Words = RULES) -> Dump:
    """A dump of exactly ``n_triples`` statements."""
    d = generate(products_for(n_triples), seed, rules)
    if len(d.lines) < n_triples:
        raise ValueError(f"{len(d.lines)} statements for "
                         f"{products_for(n_triples)} products, under "
                         f"{n_triples}")
    del d.lines[n_triples:]
    d.offers = [o for o in d.offers
                if o + STATEMENTS_PER_OFFER <= n_triples]
    return d


def transactions(d: Dump, seed: int, rules: Words = RULES):
    """Endless update transactions against ``d``, drawn from ``seed``; each
    one is applied to ``d`` as it is drawn.  A transaction deletes one
    uniformly chosen offer (its ten statements) and inserts one new
    product with its offers and reviews at the end of the file.  Yields
    ``(delete_at, deleted, inserted)``: the line index of the deleted
    offer before the change, its lines, and the inserted lines."""
    rng = rng_for(seed, stream=1)
    text = d.text
    text.rng = rng          # the dump's corpus, this seed's draws
    while True:
        k = int(rng.integers(0, len(d.offers)))
        at = d.offers[k]
        deleted = d.lines[at:at + STATEMENTS_PER_OFFER]
        del d.lines[at:at + STATEMENTS_PER_OFFER]
        d.offers = [o if o < at else o - STATEMENTS_PER_OFFER
                    for j, o in enumerate(d.offers) if j != k]

        p = d.next_product
        d.next_product += 1
        producer = int(rng.integers(1, d.n_producers + 1))
        new = _products(rng, text, [p], [producer], d.leaves, d.n_features,
                        rules)
        offer_ids = np.arange(d.next_offer,
                              d.next_offer + OFFERS_PER_PRODUCT)
        d.next_offer += OFFERS_PER_PRODUCT
        start = len(d.lines) + len(new)
        new += _offers(rng, offer_ids,
                       rng.integers(1, d.n_vendors + 1, OFFERS_PER_PRODUCT),
                       np.full(OFFERS_PER_PRODUCT, p),
                       np.full(OFFERS_PER_PRODUCT, producer))
        review_ids = np.arange(d.next_review,
                               d.next_review + REVIEWS_PER_PRODUCT)
        d.next_review += REVIEWS_PER_PRODUCT
        reviewers = rng.integers(1, len(d.reviewer_site) + 1,
                                 REVIEWS_PER_PRODUCT)
        new += _reviews(rng, text, review_ids, reviewers,
                        d.reviewer_site[reviewers - 1],
                        np.full(REVIEWS_PER_PRODUCT, p),
                        np.full(REVIEWS_PER_PRODUCT, producer), rules)
        d.offers += range(start, start + STATEMENTS_PER_OFFER
                          * OFFERS_PER_PRODUCT, STATEMENTS_PER_OFFER)
        d.lines += new
        yield at, deleted, new
