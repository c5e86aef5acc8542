"""Pallas kernel validation: shape/dtype sweeps + hypothesis property tests
against the pure oracles (interpret=True on CPU; TPU is the target)."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # property tests skip; deterministic sweeps still run
    HAVE_HYPOTHESIS = False

import jax.numpy as jnp

from repro.core import expr as E
from repro.core import sketches as hll
from repro.core.planner import plan
from repro.core.metrics import get_metrics, ALL_METRICS
from repro.kernels.fused_scan import ops as fops, ref as fref
from repro.kernels.hll import ops as hops, ref as href
from repro.kernels.qap_count import ops as qops, ref as qref
from repro.rdf import synth_encoded
from repro.rdf.triple_tensor import N_PLANES, COL_S, COL_P, COL_O, COL_S_FLAGS

FULL_PLAN = plan(get_metrics(ALL_METRICS))


@pytest.mark.parametrize("n", [1, 7, 8, 100, 8192, 8193, 20000])
@pytest.mark.parametrize("block_n", [8, 256, 8192])
def test_qap_count_shape_sweep(n, block_n):
    tt = synth_encoded(n, seed=n)
    got = np.asarray(qops.fused_count(jnp.asarray(tt.planes),
                                      FULL_PLAN.program,
                                      FULL_PLAN.n_counters,
                                      block_n=block_n))
    want = qref.counts_ref_np(tt.planes, FULL_PLAN.program,
                              FULL_PLAN.n_counters)
    np.testing.assert_array_equal(got, want.astype(np.int32))


def test_qap_count_jnp_oracle_agrees_with_np():
    tt = synth_encoded(4096, seed=1)
    a = np.asarray(qref.counts_ref_jnp(jnp.asarray(tt.planes),
                                       FULL_PLAN.program,
                                       FULL_PLAN.n_counters))
    b = qref.counts_ref_np(tt.planes, FULL_PLAN.program, FULL_PLAN.n_counters)
    np.testing.assert_array_equal(a, b.astype(np.int32))


# --- hypothesis: random expression trees --------------------------------------

if HAVE_HYPOTHESIS:
    _plane = st.integers(0, N_PLANES - 1)
    _bit = st.sampled_from([1 << i for i in range(15)])

    def _exprs(depth=3):
        leaf = st.one_of(
            st.builds(E.HasBits, _plane, _bit),
            st.builds(E.AnyBits, _plane, _bit),
            st.builds(E.Cmp, _plane, st.sampled_from(
                ["lt", "le", "gt", "ge", "eq", "ne"]), st.integers(-4, 120)),
            st.builds(E.EqPlanes, _plane, _plane),
        )
        return st.recursive(
            leaf,
            lambda kids: st.one_of(st.builds(E.And, kids, kids),
                                   st.builds(E.Or, kids, kids),
                                   st.builds(E.Not, kids)),
            max_leaves=8)

    @settings(max_examples=30, deadline=None)
    @given(exprs=st.lists(_exprs(), min_size=1, max_size=5),
           n=st.integers(1, 3000), seed=st.integers(0, 99))
    def test_qap_kernel_random_programs(exprs, n, seed):
        program = E.compile_program(exprs)
        assert E.program_stack_depth(program) >= 1
        tt = synth_encoded(n, seed=seed)
        planes = jnp.asarray(tt.planes)
        got = np.asarray(qops.fused_count(planes, program, len(exprs)))
        want = qref.counts_ref_np(tt.planes, program, len(exprs))
        np.testing.assert_array_equal(got, want.astype(np.int32))
        # triangulate with the direct AST path
        direct = np.asarray(jnp.stack(
            [jnp.sum(e.to_mask(planes), dtype=jnp.int32) for e in exprs]))
        np.testing.assert_array_equal(got, direct)


# --- HLL kernel ----------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 8, 1000, 4096, 9000])
@pytest.mark.parametrize("p", [8, 12])
@pytest.mark.parametrize("cols", [(COL_S,), (COL_S, COL_P, COL_O)])
def test_hll_kernel_sweep(n, p, cols):
    tt = synth_encoded(n, seed=n + p)
    got = np.asarray(hops.hll_fold(jnp.asarray(tt.planes), cols, p))
    valid = tt.planes[:, COL_S_FLAGS] != 0
    want = href.hll_fold_ref(tt.planes, cols, p, valid=valid)
    np.testing.assert_array_equal(got, want)


if not HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("true_card", [100, 1000, 50_000])
    def test_hll_estimate_accuracy_fixed(true_card):
        _check_hll_accuracy(true_card)
else:
    @settings(max_examples=10, deadline=None)
    @given(true_card=st.integers(100, 50_000))
    def test_hll_estimate_accuracy(true_card):
        _check_hll_accuracy(true_card)


def _check_hll_accuracy(true_card):
    """Estimate within ~5 standard errors (1.04/sqrt(m) per HLL paper)."""
    p = 12
    rng = np.random.default_rng(true_card)
    ids = rng.choice(10_000_000, size=true_card, replace=False)
    planes = np.zeros((true_card, N_PLANES), np.int32)
    planes[:, COL_S] = ids
    planes[:, COL_S_FLAGS] = 1
    regs = href.hll_fold_ref(planes, (COL_S,), p,
                             valid=np.ones(true_card, bool))
    est = href.hll_estimate_ref(regs)
    rel = abs(est - true_card) / true_card
    assert rel < 5 * 1.04 / np.sqrt(1 << p), (est, true_card, rel)


def test_hll_block_n_bounded_by_p():
    """The (BLOCK_N, 2^p) one-hot intermediate must stay inside the VMEM
    budget at any p (p=14 at the old 1024-row default was 64 MiB)."""
    for p in (8, 12, 14, 18):
        bn = hops.bounded_block_n(p, 1024)
        assert bn * (4 << p) <= hops.ONEHOT_VMEM_BYTES or bn == 8, (p, bn)
        assert bn % 8 == 0 and bn >= 8
    assert hops.bounded_block_n(14, 1024) == 64
    # ... and the bounded kernel still matches the oracle at large p
    tt = synth_encoded(5000, seed=2)
    got = np.asarray(hops.hll_fold(jnp.asarray(tt.planes), (COL_S,), 14))
    want = href.hll_fold_ref(tt.planes, (COL_S,), 14,
                             valid=tt.planes[:, COL_S_FLAGS] != 0)
    np.testing.assert_array_equal(got, want)


# --- HLL count form (core.sketches) -------------------------------------------

def _unfmix32(x):
    """The murmur3 finalizer run backwards."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x = (x * np.uint32(pow(0xC2B2AE35, -1, 1 << 32))).astype(np.uint32)
    x ^= (x >> np.uint32(13)) ^ (x >> np.uint32(26))
    x = (x * np.uint32(pow(0x85EBCA6B, -1, 1 << 32))).astype(np.uint32)
    x ^= x >> np.uint32(16)
    return x


def _planes_hashing_to(hashes):
    """Valid rows whose ``hash_columns(planes, (COL_S,))`` is ``hashes``."""
    h = _unfmix32(np.asarray(hashes, np.uint32))
    h = ((h - np.uint32(0xE6546B64))
         * np.uint32(pow(5, -1, 1 << 32))).astype(np.uint32)
    planes = np.zeros((len(h), N_PLANES), np.int32)
    planes[:, COL_S] = (_unfmix32(h) ^ np.uint32(0x9E3779B9)).view(np.int32)
    planes[:, COL_S_FLAGS] = 1
    return planes


def _count_form_case(case, n, p):
    """(planes, cols, valid) for one exactness case."""
    rng = np.random.default_rng(n * 31 + p)
    if case in ("random", "unmasked"):
        planes = rng.integers(-2**31, 2**31, (n, N_PLANES),
                              dtype=np.int64).astype(np.int32)
        valid = None if case == "unmasked" else rng.random(n) < 0.8
        return planes, (COL_S, COL_P, COL_O), valid
    if case == "all_invalid":
        planes = rng.integers(-2**31, 2**31, (n, N_PLANES),
                              dtype=np.int64).astype(np.int32)
        return planes, (COL_S, COL_P, COL_O), np.zeros(n, bool)
    # hashes placed in the first and last bucket: at the rank cap
    # (w == 0) or at a random rank, among random rows
    top = np.uint32(32 - p)
    edge = np.array([0, (1 << p) - 1], np.uint32)[rng.integers(0, 2, n)]
    low = rng.integers(0, 1 << (32 - p), n, dtype=np.uint64).astype(np.uint32)
    if case == "rank_cap":
        low[: n // 2] = 0
        edge[n // 4: n // 2] = rng.integers(0, 1 << p, n // 2 - n // 4)
    hashes = (edge << top) | low
    hashes[-n // 4:] = rng.integers(0, 1 << 32, n // 4, dtype=np.uint64)
    planes = _planes_hashing_to(hashes)
    np.testing.assert_array_equal(href.hash_columns_np(planes, (COL_S,)),
                                  hashes)
    return planes, (COL_S,), rng.random(n) < 0.9


COUNT_FORM_CASES = ([("random", n) for n in (1, 8, 1000, (1 << 16) + 5)]
                    + [("unmasked", 1000), ("all_invalid", 1000),
                       ("rank_cap", 1000), ("edge_buckets", 1000)])


@pytest.mark.parametrize("case,n", COUNT_FORM_CASES)
@pytest.mark.parametrize("p", [4, 8, 12, hll.COUNT_FORM_MAX_P])
def test_hll_count_form_equals_scatter_max(case, n, p):
    """The count form's registers ≡ the scatter-max's, bit for bit, in
    the CPU's steps of rows (more than one at 2^16 + 5 rows)."""
    assert hll.uses_count_form(p)
    planes, cols, valid = _count_form_case(case, n, p)
    v = None if valid is None else jnp.asarray(valid)
    start = jnp.asarray(np.random.default_rng(p).integers(
        0, 3, 1 << p, dtype=np.int32))
    got = np.asarray(hll.hll_update(start, jnp.asarray(planes), cols, v))
    want = np.asarray(hll.hll_update_scatter(start, jnp.asarray(planes),
                                             cols, v))
    np.testing.assert_array_equal(got, want)
    oracle = np.maximum(np.asarray(start),
                        href.hll_fold_ref(planes, cols, p, valid=valid))
    np.testing.assert_array_equal(got, oracle)
    if case == "rank_cap":
        assert got.max() == 33 - p
    if case in ("rank_cap", "edge_buckets"):
        assert got[0] > 0 and got[-1] > 0


@pytest.mark.parametrize("p", [4, 8, 12, hll.COUNT_FORM_MAX_P])
def test_hll_count_form_in_one_dot_equals_scatter_max(monkeypatch, p):
    """Where the one-hots fuse into the dot (the TPU), every row is
    folded in one dot, with a tail of none: the same registers."""
    monkeypatch.setattr(hll, "fuses_one_hots", lambda: True)
    for case in ("random", "rank_cap"):
        planes, cols, valid = _count_form_case(case, 1000, p)
        got = hll.hll_update(hll.hll_init(p), jnp.asarray(planes), cols,
                             jnp.asarray(valid))
        want = hll.hll_update_scatter(hll.hll_init(p), jnp.asarray(planes),
                                      cols, jnp.asarray(valid))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("p", [12, hll.COUNT_FORM_MAX_P,
                               hll.COUNT_FORM_MAX_P + 1])
def test_hll_update_takes_the_scatter_only_above_the_cut(p):
    """Up to ``COUNT_FORM_MAX_P`` no scatter is lowered and the pass
    counts its sketches' rows in ``scan.sketch_count_rows``; above it the
    scatter folds them and the counter stays unrecorded."""
    import jax
    from repro import spans
    from repro.core import QualityEvaluator
    shape = jax.ShapeDtypeStruct((1000, N_PLANES), jnp.int32)
    text = jax.jit(lambda a: hll.hll_update(
        hll.hll_init(p), a, (COL_S,))).lower(shape).as_text()
    assert ("stablehlo.scatter" in text) == (p > hll.COUNT_FORM_MAX_P)
    tt = synth_encoded(1000, seed=p)
    ev = QualityEvaluator(ALL_METRICS, hll_p=p)
    with spans.run() as rec:
        ev.eval_chunk(tt)
    want = 0 if p > hll.COUNT_FORM_MAX_P else 2 * tt.planes.shape[0]
    assert rec.counts.get("scan.sketch_count_rows", 0) == want


# --- fused counts+sketches megakernel ------------------------------------------

@pytest.mark.parametrize("n", [1, 8, 100, 8193, 20000])
@pytest.mark.parametrize("p", [8, 12, 14])
def test_fused_scan_counts_and_registers(n, p):
    """ONE kernel pass must reproduce the qap_count counters AND every
    sketch's hll_fold registers bit-for-bit."""
    tt = synth_encoded(n, seed=n + p)
    planes = jnp.asarray(tt.planes)
    counts, regs = fops.fused_scan(planes, FULL_PLAN.program,
                                   FULL_PLAN.n_counters,
                                   FULL_PLAN.sketch_specs, p)
    want_counts = qref.counts_ref_np(tt.planes, FULL_PLAN.program,
                                     FULL_PLAN.n_counters)
    np.testing.assert_array_equal(np.asarray(counts),
                                  want_counts.astype(np.int32))
    valid = tt.planes[:, COL_S_FLAGS] != 0
    assert set(regs) == {s for s, _ in FULL_PLAN.sketch_specs}
    for sname, cols in FULL_PLAN.sketch_specs:
        want = href.hll_fold_ref(tt.planes, cols, p, valid=valid)
        np.testing.assert_array_equal(np.asarray(regs[sname]), want, sname)


def test_fused_scan_matches_jnp_reference_path():
    tt = synth_encoded(6000, seed=9)
    planes = jnp.asarray(tt.planes)
    counts, regs = fops.fused_scan(planes, FULL_PLAN.program,
                                   FULL_PLAN.n_counters,
                                   FULL_PLAN.sketch_specs, 12)
    jc, jr = fref.fused_scan_jnp(planes, FULL_PLAN.program,
                                 FULL_PLAN.n_counters,
                                 FULL_PLAN.sketch_specs, 12)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(jc))
    for k in jr:
        np.testing.assert_array_equal(np.asarray(regs[k]),
                                      np.asarray(jr[k]), k)


def test_fused_scan_onehot_tile_is_vmem_bounded():
    for p in (8, 12, 14, 18):
        rt = fops.onehot_rows_for(p)
        assert rt * (4 << p) <= fops.ONEHOT_VMEM_BYTES or rt == 8, (p, rt)
        assert rt % 8 == 0 and rt >= 8


def test_fused_scan_no_sketches_delegates():
    """A sketch-free plan goes through the qap_count kernel — still one
    pass, empty register dict."""
    from repro.core.metrics import PAPER_METRICS
    pln = plan(get_metrics(PAPER_METRICS))
    assert not pln.sketch_specs
    tt = synth_encoded(3000, seed=1)
    counts, regs = fops.fused_scan(jnp.asarray(tt.planes), pln.program,
                                   pln.n_counters, pln.sketch_specs, 12)
    assert regs == {}
    np.testing.assert_array_equal(
        np.asarray(counts),
        qref.counts_ref_np(tt.planes, pln.program,
                           pln.n_counters).astype(np.int32))


def _random_planes(rng, n):
    """Adversarial plane tensor: arbitrary int32 ids, random validity."""
    planes = rng.integers(-2**31, 2**31 - 1, size=(n, N_PLANES),
                          dtype=np.int64).astype(np.int32)
    planes[:, COL_S_FLAGS] = rng.integers(0, 2, size=n, dtype=np.int32) \
        * rng.integers(1, 1 << 14, size=n, dtype=np.int32)
    return planes


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(1, 2000), p=st.integers(6, 14),
           seed=st.integers(0, 10**6),
           cols=st.lists(st.integers(0, N_PLANES - 1), min_size=1,
                         max_size=3, unique=True))
    def test_fused_scan_hash_and_registers_match_sketches(n, p, seed, cols):
        _check_fused_scan_vs_sketches(n, p, seed, tuple(cols))
else:
    @pytest.mark.parametrize("n,p,seed,cols", [
        (1, 6, 0, (COL_S,)), (173, 12, 3, (COL_S, COL_P, COL_O)),
        (2000, 14, 9, (COL_P, COL_O)), (64, 8, 5, (COL_O,))])
    def test_fused_scan_hash_and_registers_match_sketches_fixed(
            n, p, seed, cols):
        _check_fused_scan_vs_sketches(n, p, seed, cols)


def _check_fused_scan_vs_sketches(n, p, seed, cols):
    """Megakernel registers ≡ core/sketches.py (the jnp scatter path) on
    adversarial inputs — same murmur chain, same rank/bucket split."""
    planes_np = _random_planes(np.random.default_rng(seed), n)
    planes = jnp.asarray(planes_np)
    program = E.compile_program([E.AnyBits(COL_S_FLAGS, (1 << 15) - 1)])
    specs = (("x", cols),)
    _, regs = fops.fused_scan(planes, program, 1, specs, p)
    valid = planes_np[:, COL_S_FLAGS] != 0
    want = hll.hll_update(hll.hll_init(p), planes, cols,
                          valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(regs["x"]), np.asarray(want))
    # triangulate the shared-hash chain itself against core/sketches
    h_kernel = href.hash_columns_np(planes_np, cols)
    h_core = np.asarray(hll.hash_columns(planes, tuple(cols)))
    np.testing.assert_array_equal(h_kernel, h_core)


def test_hll_merge_idempotent_associative():
    tt = synth_encoded(5000, seed=3)
    a = href.hll_fold_ref(tt.planes[:2500], (COL_S,), 10,
                          valid=np.ones(2500, bool))
    b = href.hll_fold_ref(tt.planes[2500:], (COL_S,), 10,
                          valid=np.ones(2500, bool))
    whole = href.hll_fold_ref(tt.planes, (COL_S,), 10,
                              valid=np.ones(5000, bool))
    merged = np.maximum(a, b)
    np.testing.assert_array_equal(merged, whole)           # decomposable
    np.testing.assert_array_equal(np.maximum(merged, b), merged)  # idemp.
