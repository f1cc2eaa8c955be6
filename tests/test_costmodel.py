"""Calibrated cost model: artifact schema, source resolution, decision
clamps, bit-identity under a pinned artifact, and the tuning surfaces
(kernel block registry, scheduler defaults, accounting observability).

The load-bearing contracts:

* a heuristic model reproduces the pre-cost-model constants bit-for-bit;
* measured answers are clamped so recall can only improve (rescore floor,
  nprobe floor, int8->fp32-only precision flips, threshold band);
* one model per database keeps loop / batch / sharded plans identical for
  any *fixed* artifact;
* artifacts from a different backend degrade to the roofline fallback —
  priced with the running device's published peaks, an error on a device
  without them — never to silently-misapplied measurements.
"""
import json

import jax
import numpy as np
import pytest

from repro.kernels import ops
from repro.serving.scheduler import ContinuousScheduler, SchedulerConfig
from repro.vectordb import DirectoryVectorDB
from repro.vectordb.costmodel import (ENV_CALIBRATION, GATHER_THRESHOLD,
                                      HEURISTIC, NPROBE_FLOOR,
                                      THRESHOLD_BOUNDS, CalibrationArtifact,
                                      CostModel, model_of,
                                      resolve_calibration)
from repro.vectordb.parity import topk_mismatch
from repro.vectordb.quant import DEFAULT_RESCORE_FACTOR
from repro.vectordb.store import VectorStore

RNG = np.random.default_rng(0)
DIM = 32


def _artifact(backend=None, dim=DIM, threshold=0.2, rescore_factor=4,
              nprobe=16, **extra):
    """Minimal valid schema-1 artifact; terms chosen so the int8 scan +
    rescore is cheaper than fp32 (no precision flip) unless overridden."""
    data = {
        "schema_version": 1,
        "backend": backend if backend is not None else jax.default_backend(),
        "dim": dim,
        "terms": {
            "gather_threshold": threshold,
            "rescore_factor": rescore_factor,
            "nprobe": {"default": nprobe},
            "scan_ns": {"fp32": {"a": 50_000.0, "per_byte": 1.0},
                        "int8": {"a": 50_000.0, "per_byte": 0.1},
                        "pq": {"a": 50_000.0, "per_byte": 0.05}},
            "gather_ns": {"a": 30_000.0, "per_row": 200.0},
            "rescore_ns": {"a": 30_000.0, "per_row": 300.0},
        },
    }
    data["terms"].update(extra)
    return data


# ---------------------------------------------------------------- artifact
def test_artifact_roundtrip(tmp_path):
    art = CalibrationArtifact(_artifact())
    path = tmp_path / "sub" / "cal.json"      # save creates the directory
    art.save(str(path))
    back = CalibrationArtifact.load(str(path))
    assert back.data == art.data
    assert back.backend == jax.default_backend() and back.dim == DIM
    # the file itself is plain versioned JSON
    assert json.loads(path.read_text())["schema_version"] == 1


def test_artifact_rejects_bad_schema_version():
    bad = _artifact()
    bad["schema_version"] = 2
    with pytest.raises(ValueError, match="schema_version"):
        CalibrationArtifact(bad)
    del bad["schema_version"]
    with pytest.raises(ValueError, match="schema_version"):
        CalibrationArtifact(bad)


def test_artifact_rejects_missing_keys_and_non_dict():
    incomplete = _artifact()
    del incomplete["terms"]
    with pytest.raises(ValueError, match="missing"):
        CalibrationArtifact(incomplete)
    with pytest.raises(ValueError, match="dict"):
        CalibrationArtifact([1, 2, 3])


# -------------------------------------------------------------- resolution
def test_resolve_calibration_sources(tmp_path, monkeypatch):
    monkeypatch.delenv(ENV_CALIBRATION, raising=False)
    assert resolve_calibration(None) is HEURISTIC
    assert resolve_calibration(False) is HEURISTIC
    path = tmp_path / "cal.json"
    CalibrationArtifact(_artifact()).save(str(path))
    monkeypatch.setenv(ENV_CALIBRATION, str(path))
    assert resolve_calibration(None).source == "measured"
    # False pins heuristic even when the env var names an artifact
    assert resolve_calibration(False) is HEURISTIC
    assert resolve_calibration(str(path)).source == "measured"
    assert resolve_calibration(_artifact()).source == "measured"
    model = CostModel.from_artifact(CalibrationArtifact(_artifact()))
    assert resolve_calibration(model) is model


def test_backend_mismatch_degrades_to_roofline():
    foreign = CalibrationArtifact(_artifact(backend="not-a-real-backend"))
    # the roofline source prices with the running device's published
    # peaks: a device kind without them (this CPU) is an error, not v5e
    with pytest.raises(ValueError, match="no published peaks"):
        CostModel.from_artifact(foreign, device_kind="cpu")
    model = CostModel.from_artifact(foreign, device_kind="TPU v5 lite")
    assert model.source == "roofline"
    assert model.peaks.hbm_bytes_per_s == 819e9
    # roofline answers: analytic crossover, no tuned blocks, no scheduler
    # defaults, and the measured-only decisions pass caller values through
    assert model.gather_threshold() == pytest.approx(0.125)
    assert model.kernel_blocks() == {}
    assert model.scheduler_defaults() is None
    assert model.pick_rescore_k(10, None, 10_000) is None
    assert model.pick_precision("int8", 10_000, 10, None) == "int8"
    # but it does predict costs (> 0), unlike heuristic
    assert model.scan_ns(10_000) > 0
    assert model.estimate_batch_ns([("scan", "fp32", 500, 4)],
                                   10_000, 10, None, DIM) > 0


def test_heuristic_reproduces_hand_set_constants():
    m = HEURISTIC
    assert m.gather_threshold() == GATHER_THRESHOLD == 0.05
    assert m.default_nprobe(64) == NPROBE_FLOOR == 8
    assert m.default_nprobe(4) == 4                 # capped at n_lists
    assert m.pick_rescore_k(10, None, 10_000) is None
    assert m.pick_rescore_k(10, 25, 10_000) == 25   # explicit wins
    assert m.pick_precision("int8", 10_000, 10, None) == "int8"
    assert m.kernel_blocks() == {}
    assert m.scheduler_defaults() is None
    # heuristic has no cost terms: the observability contract is "no
    # number", never a made-up one
    assert m.estimate_batch_ns([("scan", "fp32", 500, 4)],
                               10_000, 10, None, DIM) == 0


# ------------------------------------------------------- measured + clamps
def test_measured_threshold_clamped_to_band():
    lo, hi = THRESHOLD_BOUNDS
    assert resolve_calibration(
        _artifact(threshold=5.0)).gather_threshold() == hi
    assert resolve_calibration(
        _artifact(threshold=1e-6)).gather_threshold() == lo
    assert resolve_calibration(
        _artifact(threshold=0.2)).gather_threshold() == pytest.approx(0.2)


def test_measured_rescore_factor_floored():
    k = 10
    assert resolve_calibration(_artifact(rescore_factor=1)).pick_rescore_k(
        k, None, 100_000) == DEFAULT_RESCORE_FACTOR * k
    assert resolve_calibration(_artifact(rescore_factor=8)).pick_rescore_k(
        k, None, 100_000) == 8 * k
    # explicit caller width beats the measured factor
    assert resolve_calibration(_artifact(rescore_factor=8)).pick_rescore_k(
        k, 17, 100_000) == 17


def test_measured_nprobe_floored_and_capped():
    assert resolve_calibration(_artifact(nprobe=2)).default_nprobe(64) == 8
    assert resolve_calibration(_artifact(nprobe=64)).default_nprobe(16) == 16
    assert resolve_calibration(_artifact(nprobe=32)).default_nprobe(64) == 32


def test_measured_precision_flip_is_upgrade_only():
    # int8 measured cheaper than fp32 -> request honored
    cheap_i8 = resolve_calibration(_artifact())
    assert cheap_i8.pick_precision("int8", 50_000, 10, None) == "int8"
    # int8 scan + rescore measured slower than the exact fp32 scan (the
    # no-int8-GEMM backend shape) -> upgraded to fp32
    slow_i8 = resolve_calibration(_artifact(
        scan_ns={"fp32": {"a": 10_000.0, "per_byte": 0.01},
                 "int8": {"a": 500_000.0, "per_byte": 5.0},
                 "pq": {"a": 50_000.0, "per_byte": 0.05}}))
    assert slow_i8.pick_precision("int8", 50_000, 10, None) == "fp32"
    # never flips pq (tiered-serving format), never flips under a tiered
    # store, never touches an explicit fp32 request
    assert slow_i8.pick_precision("pq", 50_000, 10, None) == "pq"
    assert slow_i8.pick_precision("int8", 50_000, 10, None,
                                  tiered=True) == "int8"
    assert slow_i8.pick_precision("fp32", 50_000, 10, None) == "fp32"


def test_pick_rescore_k_precision_floor():
    """A precision's hand-set window (PQ's is wider) is the floor under
    every model: heuristic returns it, measured may only widen it."""
    heur = CostModel.heuristic()
    assert heur.pick_rescore_k(10, None, 1000) is None
    assert heur.pick_rescore_k(10, None, 1000, floor=16) == 160
    assert heur.pick_rescore_k(10, 25, 1000, floor=16) == 25
    for factor, want in ((4, 160), (20, 200)):
        art = CalibrationArtifact(_artifact(rescore_factor=factor))
        measured = CostModel.from_artifact(art)
        assert measured.pick_rescore_k(10, None, 1000, floor=16) == want
        assert measured.pick_rescore_k(10, None, 1000) == max(factor, 4) * 10


def test_model_of_defaults_to_heuristic():
    st = VectorStore(DIM, "ip")
    assert model_of(st) is HEURISTIC
    st.cost_model = resolve_calibration(_artifact())
    assert model_of(st).source == "measured"


# ------------------------------------------------------------ bit-identity
def test_bit_identity_under_pinned_artifact():
    """Loop dsq, dsq_batch and the sharded executor read ONE model, so a
    pinned artifact that *changes* plans still keeps loop and batch
    bit-identical and sharded within a few fp32 ULP (``vectordb.parity``)."""
    art = _artifact(threshold=0.25)      # 5x the hand-set crossover
    vecs = RNG.normal(size=(1200, DIM)).astype(np.float32)
    paths = (["/a/"] * 140 + ["/b/"] * 30 + ["/c/"] * 1030)
    cal = DirectoryVectorDB(dim=DIM, calibration=art)
    heur = DirectoryVectorDB(dim=DIM, calibration=False)
    for db in (cal, heur):
        db.ingest(vecs, paths)
        db.build_ann("flat")
        db.build_ann("sharded")
    q = RNG.normal(size=(6, DIM)).astype(np.float32)
    req = ["/a/", "/b/", "/c/", "/a/", "/", "/b/"]
    batch = cal.dsq_batch(q, req, k=10)
    # the pinned threshold must actually move a decision vs the heuristic:
    # /a/ is 140/1200 = 11.7% selective — scan at 0.05, gather at 0.25
    hb = heur.dsq_batch(q, req, k=10)
    assert batch[0].plan == "gather" and hb[0].plan == "scan"
    for i, res in enumerate(batch):
        loop = cal.dsq(q[i], req[i], k=10)
        np.testing.assert_array_equal(res.ids, loop.ids)
        np.testing.assert_array_equal(res.scores, loop.scores)
        sh = cal.dsq_batch(q[i:i + 1], [req[i]], k=10, executor="sharded")[0]
        err = topk_mismatch(sh.scores, sh.ids, res.scores, res.ids)
        assert err is None, (i, err)
        # plan changes never change the answer: exact fp32 either way
        np.testing.assert_array_equal(np.sort(res.ids[0]),
                                      np.sort(hb[i].ids[0]))
        np.testing.assert_allclose(np.sort(res.scores[0]),
                                   np.sort(hb[i].scores[0]),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- observability
def test_accounting_plan_source_and_prediction():
    vecs = RNG.normal(size=(800, DIM)).astype(np.float32)
    paths = ["/x/"] * 400 + ["/y/"] * 400
    q = RNG.normal(size=(4, DIM)).astype(np.float32)
    req = ["/x/", "/y/", "/x/", "/"]
    cal = DirectoryVectorDB(dim=DIM, calibration=_artifact())
    heur = DirectoryVectorDB(dim=DIM, calibration=False)
    for db in (cal, heur):
        db.ingest(vecs, paths)
        db.build_ann("flat")
    acct = cal.dsq_batch(q, req, k=5)[0].batch
    assert acct.plan_source == "measured"
    assert acct.predicted_ann_ns > 0
    h = heur.dsq_batch(q, req, k=5)[0].batch
    assert h.plan_source == "heuristic" and h.predicted_ann_ns == 0
    # merge keeps the latest non-empty source and sums predictions
    h.merge(acct)
    assert h.plan_source == "measured"
    assert h.predicted_ann_ns == acct.predicted_ann_ns


# --------------------------------------------------------- kernel tuning
def test_kernel_tuning_installed_by_database():
    art = _artifact(kernel_blocks={
        "scoped_topk": {"block_q": 4, "block_n": 512, "us": 10.0},
        "multi_scope_topk": {"block_q": 8, "block_n": 256, "us": 20.0}})
    try:
        db = DirectoryVectorDB(dim=DIM, calibration=art)
        assert db.store.cost_model.source == "measured"
        got = ops.get_block_overrides()
        assert got["scoped_topk"] == (4, 512)
        assert got["multi_scope_topk"] == (8, 256)
        # the tuned shape changes nothing observable: results match defaults
        X = RNG.normal(size=(700, DIM)).astype(np.float32)
        Q = RNG.normal(size=(3, DIM)).astype(np.float32)
        mask = RNG.random(700) < 0.5
        v1, i1 = ops.scoped_topk(Q, X, mask, k=7)
        ops.set_block_overrides({})
        v2, i2 = ops.scoped_topk(Q, X, mask, k=7)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                                   rtol=1e-6, atol=1e-6)
    finally:
        ops.set_block_overrides({})


# ------------------------------------------------------------- scheduler
def test_scheduler_defaults_from_artifact():
    from repro.serving.scheduler import ScheduledDSQ
    art = _artifact(scheduler={"max_batch": 7, "max_wait_ms": 2.0,
                               "service_us": {"1": 100.0, "8": 300.0}})
    try:
        db = DirectoryVectorDB(dim=DIM, calibration=art)
        db.ingest(RNG.normal(size=(64, DIM)).astype(np.float32),
                  ["/s/"] * 64)
        db.build_ann("flat")
        sched = ScheduledDSQ(db, k=3)
        assert sched.scheduler.cfg.max_batch == 7
        assert sched.scheduler.cfg.max_wait_ms == pytest.approx(2.0)
        assert sched.scheduler.cfg.adaptive is True
        # explicit cfg still wins over the model's defaults
        own = ScheduledDSQ(db, k=3, cfg=SchedulerConfig(max_batch=3))
        assert own.scheduler.cfg.max_batch == 3
        heur = DirectoryVectorDB(dim=DIM, calibration=False)
        heur.ingest(RNG.normal(size=(64, DIM)).astype(np.float32),
                    ["/s/"] * 64)
        heur.build_ann("flat")
        stock = ScheduledDSQ(heur, k=3)
        assert stock.scheduler.cfg.max_batch == 32
        assert stock.scheduler.cfg.max_wait_ms == pytest.approx(4.0)
        assert stock.scheduler.cfg.adaptive is False
    finally:
        ops.set_block_overrides({})


def test_adaptive_wait_tracks_service_time():
    """Adaptive mode refines max_wait_ms toward the EWMA of service time,
    clamped to [min_wait_ms, the configured SLO ceiling]."""
    cfg = SchedulerConfig(max_batch=4, max_wait_ms=8.0, adaptive=True,
                          min_wait_ms=0.5)
    fake = [0.0]

    def clock():
        return fake[0]

    def execute(payloads, staged):
        fake[0] += 0.002                  # every batch "takes" 2ms
        return [p for p in payloads]

    sched = ContinuousScheduler(execute, cfg=cfg, clock=clock)
    for rounds in range(3):
        for i in range(4):
            sched.submit(i)
        assert sched.pump() == 4
    assert sched._service_ewma_s > 0
    assert sched.cfg.max_wait_ms == pytest.approx(2.0, rel=0.3)
    assert cfg.min_wait_ms <= sched.cfg.max_wait_ms <= 8.0
    # a long stall pushes the wait up but never past the SLO ceiling
    def slow(payloads, staged):
        fake[0] += 1.0
        return [p for p in payloads]

    sched.execute_fn = slow
    for i in range(4):
        sched.submit(i)
    sched.pump()
    assert sched.cfg.max_wait_ms == pytest.approx(8.0)
