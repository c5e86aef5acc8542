"""Each cell's traffic, driven end to end at a tiny size on the CPU, and
the generators it draws from."""
import collections
import json
import os

import numpy as np
import pytest

import run as harness
from cells import run_tiny, tiny
from generators import bsbm
from reference.encoder import Encoder

CELLS = ("bsbm_dump.ntriples", "bsbm_update.changesets", "bsbm_dump.encoded")


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_with_its_end_to_end_metrics(name):
    out = run_tiny(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    want = {m["name"] for m in harness.cell_metrics(bench, name, False)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_traced_cell_reports_its_host_and_counter_metrics(name):
    out = run_tiny(name, trace=True)
    assert out["correct"], out["checks"]
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    # the CPU has no TPU planes: only the device-trace metrics are silent
    want = {m["name"] for m in harness.cell_metrics(bench, name, True)
            if m["source"] != "device_trace"}
    assert want <= set(out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3, 2**40 + 1])
def test_dump_has_exactly_its_size_for_every_seed(seed):
    d = bsbm.dump(5000, seed)
    assert len(d.lines) == 5000
    assert d.data().count(b"\n") == 5000
    assert d.offers
    for o in d.offers:
        assert_offer(d.lines[o:o + bsbm.STATEMENTS_PER_OFFER])


def assert_offer(lines):
    assert len(lines) == bsbm.STATEMENTS_PER_OFFER
    assert lines[0].endswith(f"<{bsbm.VOC}Offer> .")
    subject = lines[0].split(" ", 1)[0]
    assert all(x.split(" ", 1)[0] == subject for x in lines)


def test_dump_is_the_seeds_own():
    a, b = bsbm.dump(3000, 11), bsbm.dump(3000, 11)
    assert a.data() == b.data()
    assert a.data() != bsbm.dump(3000, 12).data()


def test_generator_follows_the_specifications_table():
    d = bsbm.generate(2785, 2**33 + 1)
    assert abs(len(d.lines) - 1_000_313) < 10_003      # within 1%
    subjects = collections.Counter(
        x.split(" ", 2)[2] for x in d.lines
        if x.split(" ", 2)[1] == bsbm.TYPE)
    assert subjects[f"<{bsbm.VOC}Offer> ."] == 20 * 2785
    assert subjects[f"<{bsbm.VOC}Review> ."] == 10 * 2785
    assert subjects[f"<{bsbm.VOC}Product> ."] == 2785
    assert subjects[f"<{bsbm.VOC}ProductType> ."] == 151
    assert subjects[f"<{bsbm.VOC}ProductFeature> ."] == 4745
    for cls in ("Producer", "Vendor"):
        assert subjects[f"<{bsbm.VOC}{cls}> ."] > 0
    assert subjects[f"<{bsbm.FOAF}Person> ."] == len(d.reviewer_site)


def test_transactions_delete_an_offer_and_append_a_product():
    d = bsbm.dump(6000, 5)
    before = list(d.lines)
    txns = bsbm.transactions(d, 5)
    at, deleted, inserted = next(txns)
    assert deleted == before[at:at + bsbm.STATEMENTS_PER_OFFER]
    assert_offer(deleted)
    assert d.lines == (before[:at] + before[at + bsbm.STATEMENTS_PER_OFFER:]
                       + inserted)
    assert inserted[0].endswith(f"<{bsbm.VOC}Product> .")
    kinds = collections.Counter(x.rsplit(" ", 2)[1] for x in inserted
                                if f" {bsbm.TYPE} " in x)
    assert kinds[f"<{bsbm.VOC}Offer>"] == bsbm.OFFERS_PER_PRODUCT
    assert kinds[f"<{bsbm.VOC}Review>"] == bsbm.REVIEWS_PER_PRODUCT
    again = bsbm.dump(6000, 5)
    assert next(bsbm.transactions(again, 5)) == (at, deleted, inserted)
    for _ in range(50):      # offer positions stay those of offers
        next(txns)
    for o in d.offers:
        assert_offer(d.lines[o:o + bsbm.STATEMENTS_PER_OFFER])


def test_encoded_planes_are_the_reference_encoding_of_the_dump(tmp_path):
    _, _, config, traffic = tiny("bsbm_dump.encoded")
    runner = harness.load_module("runners", "dump").Runner(
        config, traffic, 3, str(tmp_path))
    try:
        runner.setup()
        want = Encoder(config["base_namespaces"]).encode_lines(
            bsbm.dump(traffic["triples"], 3).lines)
        assert runner.planes.shape == (traffic["triples"], 13)
        assert np.array_equal(runner.planes, want)
        assert runner.target.n_terms == int(want[:, :3].max()) + 1
    finally:
        runner.close()


def test_tiny_sizes_keep_the_cells_shapes():
    for name in CELLS:
        _, cell, config, traffic = tiny(name)
        assert traffic["runner"] in ("dump", "update")
        assert os.path.exists(os.path.join(
            harness.HERE, "runners", traffic["runner"] + ".py"))
        assert config["metrics"] == "all"
    assert json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
