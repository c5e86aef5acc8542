"""The incremental planner: diff segments against the store, rescan only
segments whose *content* changed, merge frozen partial states for the rest.

Why results are *bit-identical* to a cold run (registers included)
------------------------------------------------------------------
Every plane a metric or sketch reads is **content-determined**: counter
predicates read flag / length / datatype planes or compare term ids for
equality (invariant to id *numbering*), and since plane layout v2 the HLL
sketches hash the content-hash planes — a 32-bit hash of each term's
``Term.key()`` bytes computed at ingest — instead of the id planes.  A
frozen segment state (counter vectors + register banks) is therefore a
pure function of the segment's bytes plus the engine signature, and is
valid whenever its fingerprint still matches, *regardless of how upstream
edits renumbered the id space*.  The rescan set is exactly the segments
with no verified frozen state: new or changed content, corrupt files.
Consequences:

* **appends** rescan only the tail segment(s) — as before;
* **deletes / mutations** are now *edit-local* too: only the segments
  framing the edit rescan.  (Pre-v2, registers hashed term ids, so any
  edit that renumbered ids invalidated every downstream frozen bank —
  a 10% mutation rescanned ~50% of bytes; now it rescans ~the edit.)
* a **duplicate segment** (same bytes appearing twice) is reused from one
  state file — counts merge additively per occurrence, registers
  idempotently.

The runner can still rebuild the canonical ("cold") dictionary — without
re-reading unchanged bytes — by replaying each segment's persisted
**dictionary footprint** (its distinct term keys with metadata, in
first-appearance order) through ``TermDictionary.intern_keys_batch`` in
segment order.  Replay is no longer a reuse *gate*; it only keeps
rescanned segments encoding against a fully-populated dictionary whose
id assignment equals the cold run's — so it is **lazy**: reused
footprints are queued and interned just before the next rescan encodes,
which means a fully warm run replays nothing, and reused segments after
the last rescanned one are never replayed (``exec_stats.
footprints_replayed`` counts the ones that were).  Plans that read raw
id planes (user-registered metrics) keep the eager replay-and-compare
gate, exactly as before.

Rescans run through the ordinary ``dist.ChunkScheduler`` (any backend,
retries, optional ``prefetch`` pipelining); its ``on_chunk`` hook freezes
each newly evaluated segment's state into the store as it merges.

Mesh scale-out: segments are *independent* (each frozen state is a pure
function of its own bytes), so when the evaluator carries a device mesh
the rescan set is embarrassingly parallel — rescanned segments are
evaluated in shard-count-sized batches through
``QualityEvaluator.eval_segment_batch`` (one whole segment per device
slot, per-segment results kept unreduced so each state can still be
frozen and content-addressed exactly as in the sequential path).  The
batched executor replaces the chunk scheduler for those rescans, so
``prefetch``/``speculate`` do not apply under a mesh.
"""
from __future__ import annotations

import hashlib
from typing import Iterable, Optional, Sequence

import numpy as np

from .. import spans
from ..core.evaluator import AssessmentResult, QualityEvaluator
from ..dist import ChunkScheduler, ChunkStats
from ..rdf import TermDictionary
from ..rdf import ingest as rdf_ingest
from ..rdf.triple_tensor import (COL_O, COL_P, COL_S,
                                 PLANE_LAYOUT_VERSION)
from .segmenter import fingerprint
from .store import FORMAT_VERSION, SegmentState, SegmentStore


def engine_signature(evaluator: QualityEvaluator,
                     base_namespaces: Sequence[str] = ()) -> dict:
    """What a frozen segment state depends on.  The backend is deliberately
    absent: all backends are bit-identical (tests/test_qa.py), so a store
    written under ``jnp`` is reusable under ``fused_scan`` and vice versa.
    The plane-layout version IS present: frozen registers hash specific
    plane columns, so a store written under an older layout (e.g. pre-
    content-hash v1, whose sketches hashed term ids) must self-heal via
    the wholesale-discard path rather than be misread.
    """
    plans = [(tuple(m.name for m in p.metrics), p.n_counters, p.program,
              p.sketch_specs) for p in evaluator.plans]
    return {
        "format": FORMAT_VERSION,
        "plane_layout": PLANE_LAYOUT_VERSION,
        "metrics": [m.name for m in evaluator.metrics],
        "fused": bool(evaluator.fused),
        "hll_p": int(evaluator.hll_p),
        "base_namespaces": list(base_namespaces),
        "plans": hashlib.blake2b(repr(plans).encode(),
                                 digest_size=8).hexdigest(),
    }


_ID_PLANES = frozenset((COL_S, COL_P, COL_O))


def _expr_renumbering_invariant(e) -> bool:
    """True iff a counter expression's value is invariant under any
    injective renumbering of term ids.  Flag/length/datatype/hash planes
    are content-determined; id planes are numbering-dependent EXCEPT when
    two of them are compared for equality (same term ⇔ same id under any
    numbering)."""
    from ..core import expr as E
    if isinstance(e, (E.And, E.Or)):
        return (_expr_renumbering_invariant(e.a)
                and _expr_renumbering_invariant(e.b))
    if isinstance(e, E.Not):
        return _expr_renumbering_invariant(e.a)
    if isinstance(e, E.EqPlanes):
        return (e.plane_a in _ID_PLANES) == (e.plane_b in _ID_PLANES)
    return e.plane not in _ID_PLANES


def plans_renumbering_invariant(evaluator: QualityEvaluator) -> bool:
    """Whether every plan's counters AND sketches read only content-
    determined planes.  True for all built-ins since plane layout v2
    (sketches hash COL_*_HASH); user-registered metrics may still sketch
    or compare raw id planes, in which case frozen states are only valid
    under the exact cold id assignment and the incremental planner must
    keep the replayed-id equality gate."""
    for pln in evaluator.plans:
        for _, cols in pln.sketch_specs:
            if any(c in _ID_PLANES for c in cols):
                return False
        for e in pln.exprs:
            if not _expr_renumbering_invariant(e):
                return False
    return True


def _bucket_rows(n: int) -> int:
    """Pad row counts to power-of-two buckets (min 1024) so the jitted
    pass functions see O(log n) distinct shapes instead of one shape per
    segment — content-defined segments all differ in length, and an XLA
    recompile per segment would dwarf the scan itself.  Padding rows have
    zero flag planes, so they are invisible to every counter and sketch.
    """
    b = 1024
    while b < n:
        b <<= 1
    return b


def _footprint_ids(planes: np.ndarray) -> np.ndarray:
    """Distinct term ids of a segment in first-appearance order over the
    flattened (s0, p0, o0, s1, ...) sequence — the exact order a fresh
    per-term intern loop would meet them."""
    if planes.shape[0] == 0:
        return np.zeros(0, np.int64)
    flat = planes[:, :3].reshape(-1)
    present, first = np.unique(flat, return_index=True)
    order = np.argsort(first, kind="stable")
    return present[order].astype(np.int64)


def assess_incremental(evaluator: QualityEvaluator,
                       segments: Iterable[bytes], store_dir: str, *,
                       base_namespaces: Sequence[str] = (),
                       prefetch: int = 0,
                       straggler_factor: float = 4.0,
                       speculate: bool = False,
                       history: bool = True,
                       max_history: int = 0,
                       dataset_uri: str = "urn:repro:dataset",
                       ) -> AssessmentResult:
    """Assess ``segments`` (ordered raw byte segments of one dataset)
    against the segment store at ``store_dir``.

    Returns an ``AssessmentResult`` bit-identical to a cold assessment of
    the concatenated bytes; ``result.exec_stats`` carries
    ``segments_reused`` / ``segments_rescanned`` / ``bytes_rescanned``.
    On success the store's manifest is committed for the new dataset
    version and a quality snapshot is appended to ``history.jsonl``.
    """
    with spans.run() as rec:
        with spans.span("store.assess") as sp:
            result, store, order = _assess(
                evaluator, segments, store_dir, base_namespaces, prefetch,
                straggler_factor, speculate)
        result.exec_stats.wall_seconds = sp.seconds
        with spans.span("store.commit"):
            store.commit(order)
        if history:
            from ..core import report
            with spans.span("store.history"):
                store.append_history(report.history_entry(
                    result, dataset_uri=dataset_uri),
                    max_history=max_history)
    result.trace = rec
    return result


def _assess(ev: QualityEvaluator, segments: Iterable[bytes], store_dir: str,
            base_namespaces: Sequence[str], prefetch: int,
            straggler_factor: float, speculate: bool):
    """Assess the segments against the store, committing nothing:
    (result, store, the new version's segment order)."""
    store = SegmentStore(store_dir,
                         engine_signature(ev, base_namespaces))
    d = TermDictionary(base_namespaces)
    # Built-in metrics are content-determined since plane layout v2, so
    # unchanged bytes ⇒ reusable.  A user-registered metric may still
    # sketch or threshold raw id planes — for those plans frozen state is
    # only valid under the cold id assignment, and the replayed-id
    # equality gate stays on (PR 4 semantics: exactness over reuse).
    content_determined = plans_renumbering_invariant(ev)

    order: list[dict] = []        # segment descriptors, dataset order
    reused: list[SegmentState] = []
    rescan_meta: dict[int, dict] = {}   # cid -> frozen-state ingredients
    nbytes = {"total": 0, "rescanned": 0}
    replayed = [0]                # footprints actually interned
    deferred: list[SegmentState] = []   # reused, replay not yet needed

    def replay_deferred():
        """Intern the footprints of every reused segment queued so far —
        called just before a rescan encodes, so the rescanned segment's
        terms land at their cold ids.  Lazy replay: a fully warm run
        never calls this, and reused segments *after* the last rescan
        are never replayed at all (nothing downstream encodes against
        them) — warm re-crawls of many-segment stores skip the whole
        dictionary rebuild."""
        if not deferred:
            return
        with spans.span("store.replay"):
            for st in deferred:
                d.intern_keys_batch(st.keys, st.flags, st.lengths,
                                    st.datatypes)
        replayed[0] += len(deferred)
        deferred.clear()

    def produce():
        """Sequential segment walk: replay-or-rescan.  Runs on the
        scheduler's producer thread when pipelined; all side effects are
        read only after the scheduler joins it."""
        cid = 0
        walk = iter(segments)
        while True:
            with spans.span("store.segment"):
                seg = next(walk, None)
            if seg is None:
                return
            with spans.span("store.fingerprint"):
                fp = fingerprint(seg)
            nbytes["total"] += len(seg)
            with spans.span("store.load_state"):
                st = store.load_state(fp)
            if st is not None:
                # The footprint replay keeps the shared dictionary
                # canonical (cold-identical ids) for this run's rescans;
                # for content-determined plans it is NOT a reuse gate —
                # unchanged bytes ⇒ the frozen state is valid as-is, so
                # the replay is deferred until a rescan actually needs
                # the dictionary positioned (possibly never).
                if content_determined:
                    deferred.append(st)
                    reused.append(st)
                    order.append({"fp": fp, "n_bytes": len(seg),
                                  "n_triples": st.n_triples})
                    continue
                # id-plane-reading user metric: frozen state is only
                # valid under the exact cold id assignment, so the
                # replay stays eager and gates reuse (PR 4 semantics)
                with spans.span("store.replay"):
                    ids = d.intern_keys_batch(st.keys, st.flags,
                                              st.lengths, st.datatypes)
                replayed[0] += 1
                if np.array_equal(ids, st.ids):
                    reused.append(st)
                    order.append({"fp": fp, "n_bytes": len(seg),
                                  "n_triples": st.n_triples})
                    continue
                # shifted id environment: registers/counters are stale,
                # rescan below (the replay already positioned this
                # segment's terms at their cold ids, so re-encoding is
                # id-stable)
            replay_deferred()
            nbytes["rescanned"] += len(seg)
            tt = rdf_ingest.parse_encode(seg, dictionary=d)
            with spans.span("store.footprint"):
                ids = _footprint_ids(tt.planes)
                flags, lengths, dts, _hashes = d.plane_arrays()
                rescan_meta[cid] = {
                    "fp": fp, "n_bytes": len(seg), "n_triples": len(tt),
                    "keys": d.keys_for(ids), "flags": flags[ids],
                    "lengths": lengths[ids].astype(np.int64),
                    "datatypes": dts[ids], "ids": ids,
                }
            order.append({"fp": fp, "n_bytes": len(seg),
                          "n_triples": len(tt)})
            cid += 1
            yield tt.padded_to(_bucket_rows(len(tt)))

    # one merged state over ALL segments — the same commutative monoid the
    # chunk executor uses.  Rescanned chunks merge in as they land
    # (on_chunk), so no per-segment result is held beyond its freeze.
    state = ev.chunk_state_init()
    rescanned = [0]

    def on_chunk(cid: int, counts, regs) -> None:
        m = rescan_meta.pop(cid)
        with spans.span("store.freeze"):
            store.put_state(SegmentState(
                fingerprint=m["fp"], n_bytes=m["n_bytes"],
                n_triples=m["n_triples"],
                counts=[np.asarray(c, np.int64) for c in counts],
                regs={k: np.asarray(v, np.int32) for k, v in regs.items()},
                keys=m["keys"], flags=m["flags"], lengths=m["lengths"],
                datatypes=m["datatypes"], ids=m["ids"]))
        ev.merge_chunk(state, ("rescanned", cid), counts, regs)
        rescanned[0] += 1

    if ev.mesh is not None:
        # Embarrassingly parallel rescan: one whole segment per device
        # slot, batched through eval_segment_batch — per-segment results
        # come back unreduced so on_chunk freezes each state exactly as
        # the sequential scheduler path would.  prefetch/speculate are
        # scheduler features and do not apply here.
        if prefetch or speculate:
            import warnings
            warnings.warn(
                "prefetch/speculate are ignored for mesh rescans: the "
                "batched segment executor replaces the chunk scheduler",
                RuntimeWarning, stacklevel=3)
        stats = ChunkStats(chunks_total=0, mode="incremental+mesh",
                           passes_per_chunk=ev.passes_per_chunk,
                           devices=ev._shard_count())
        batch: list = []            # [(cid, padded tensor)]

        def flush() -> None:
            if not batch:
                return
            with spans.span("scan.eval") as sp:
                outs = ev.eval_segment_batch([tt for _, tt in batch])
            stats.chunk_eval_seconds.append(sp.seconds)
            stats.attempts += len(batch)
            for (cid, _), (counts, regs) in zip(batch, outs):
                on_chunk(cid, counts, regs)
            batch.clear()

        for cid, tt in enumerate(produce()):
            batch.append((cid, tt))
            if len(batch) >= ev._shard_count():
                flush()
        flush()
    else:
        sched = ChunkScheduler(ev, prefetch=prefetch,
                               straggler_factor=straggler_factor,
                               speculate=speculate, on_chunk=on_chunk)
        _, stats = sched.run(produce())
        stats.mode = "incremental" + ("+pipelined" if prefetch else "")

    for i, st in enumerate(reused):
        ev.merge_chunk(state, ("reused", i), st.counts, st.regs)
    n_total = sum(s["n_triples"] for s in order)
    result = ev.finalize_state(state, n_total)
    # only rescanned segments actually streamed bytes through the kernels
    result.passes = rescanned[0] * ev.passes_per_chunk

    stats.chunks_total = len(order)
    stats.segments_reused = len(reused)
    stats.segments_rescanned = rescanned[0]
    stats.bytes_total = nbytes["total"]
    stats.bytes_rescanned = nbytes["rescanned"]
    stats.footprints_replayed = replayed[0]
    result.exec_stats = stats
    return result, store, order
