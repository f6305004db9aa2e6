"""perf/ learned cost model: fit/predict/persistence, the cold-start
contract (empty corpus → every consumer reproduces today's heuristics
bit for bit, regression-tested per call site), the sweep's
OOM-halving, residual recording (histogram + goodput), journal facts harvesting, and params threading.

The suite runs with TRANSMOGRIFAI_PERF_MODEL=0 (conftest); tests that
exercise the model opt in per-test via the `perf_env` fixture, which
also isolates the corpus in tmp_path and resets the cached model."""

import json
import math
import os

import numpy as np
import pytest

from transmogrifai_tpu import perf
from transmogrifai_tpu.perf.smoke import synth_corpus


@pytest.fixture
def perf_env(tmp_path, monkeypatch):
    """Enable the perf model with an isolated corpus; restore after."""
    monkeypatch.setenv("TRANSMOGRIFAI_PERF_MODEL", "1")
    perf.set_params(perf.PerfModelParams(corpus_dir=str(tmp_path),
                                         min_rows=4))
    perf.set_model(None)
    yield tmp_path
    perf.set_model(None)
    perf.set_params(None)


def _warm_model(rows_by_target, min_rows=1):
    """A CostModel fitted on handcrafted rows per target."""
    model = perf.CostModel(min_rows=min_rows)
    for target, rows in rows_by_target.items():
        model.fit_target(target, rows)
    return model


def _block_rows(iters_values, scale=0.01, n_rows=240, n_cols=6, n_folds=2):
    """block_runtime rows whose value is proportional to max_iter."""
    rows = []
    for it in iters_values:
        for rep in range(3):
            feats = perf.block_features("logistic", (it, False), 2,
                                        n_rows, n_cols, n_folds)
            rows.append({"features": feats, "value": scale * it})
    return rows


# --------------------------------------------------------------------------- #
# model core                                                                  #
# --------------------------------------------------------------------------- #

class TestCostModel:
    def test_fit_recovers_multiplicative_law(self, perf_env):
        corpus = perf.get_corpus()
        synth_corpus(corpus)
        for target in ("block_runtime", "ingest", "serving_bucket"):
            mape = perf.holdout_mape(corpus, target)
            assert mape is not None and mape < 0.35, (target, mape)

    def test_prediction_error_bars_bracket_value(self, perf_env):
        corpus = perf.get_corpus()
        synth_corpus(corpus)
        model = perf.fit_corpus(corpus)
        p = model.predict("block_runtime", {
            "n_configs": 4, "n_rows": 100_000, "n_cols": 50,
            "n_folds": 3, "dtype_bytes": 4, "fam_logistic": 1.0,
            "iters": 32})
        assert p is not None
        assert p.lo < p.value < p.hi
        assert p.n > 0
        # the law says 3e-8 * 4 * 32 * 1e5 = 0.384
        assert 0.25 < p.value < 0.55

    def test_save_load_roundtrip_bitwise(self, perf_env, tmp_path):
        corpus = perf.get_corpus()
        synth_corpus(corpus)
        model = perf.fit_corpus(corpus)
        path = str(tmp_path / "model.json")
        model.save(path)
        loaded = perf.CostModel.load(path)
        feats = {"n_configs": 2, "n_rows": 50_000, "n_cols": 50,
                 "n_folds": 3, "dtype_bytes": 4, "fam_logistic": 1.0,
                 "iters": 8}
        a = model.predict("block_runtime", feats)
        b = loaded.predict("block_runtime", feats)
        assert a.to_json() == b.to_json()

    def test_cold_targets_predict_none(self, perf_env):
        model = perf.CostModel()
        assert model.predict("block_runtime", {"n_configs": 1}) is None
        # below the min_rows floor is still cold
        model2 = perf.CostModel(min_rows=50)
        model2.fit_target("block_runtime", _block_rows((4, 8)))
        assert model2.predict("block_runtime",
                              {"n_configs": 2, "iters": 4}) is None

    def test_disabled_kill_switch(self, perf_env, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_PERF_MODEL", "0")
        assert perf.get_model() is None
        assert perf.get_corpus() is None

    def test_corpus_torn_tail_tolerated(self, perf_env):
        corpus = perf.get_corpus()
        corpus.append("ingest", {"bytes_wire": 1e6}, 1.0)
        with open(corpus.path, "a") as fh:
            fh.write('{"target": "ingest", "features"')  # torn line
        corpus.append("ingest", {"bytes_wire": 2e6}, 2.0)
        assert len(corpus.rows("ingest")) == 2


# --------------------------------------------------------------------------- #
# consumer 1: scheduler ordering + width sizing                               #
# --------------------------------------------------------------------------- #

def _mk_blocks():
    from transmogrifai_tpu.parallel.scheduler import _Block
    # three logistic compile groups, equal config counts, different iters
    return [_Block(0, ("logistic", (4, False)), [0, 1]),
            _Block(0, ("logistic", (64, False)), [2, 3]),
            _Block(0, ("logistic", (16, False)), [4, 5])]


class TestSchedulerPlan:
    def _plan(self, blocks):
        from transmogrifai_tpu.parallel.scheduler import GridScheduler
        sched = GridScheduler(mesh=None)
        X = np.zeros((240, 6), np.float32)
        y = np.zeros(240, np.float32)
        folds = [(np.ones(240), np.ones(240))] * 2
        return sched._plan(blocks, X, y, folds)

    def test_cold_order_is_count_lpt(self, perf_env):
        perf.set_model(perf.CostModel())  # explicitly cold
        planned = self._plan(_mk_blocks())
        # today's heuristic: (-len, job, repr(key)) — ascending iters
        assert [b.key[1][0] for b in planned] == [16, 4, 64]
        assert all(b.pred_s is None for b in planned)

    def test_warm_order_is_predicted_lpt(self, perf_env):
        perf.set_model(_warm_model(
            {"block_runtime": _block_rows((4, 16, 64))}))
        planned = self._plan(_mk_blocks())
        assert [b.key[1][0] for b in planned] == [64, 16, 4]
        assert all(b.pred_s is not None for b in planned)
        preds = [b.pred_s for b in planned]
        assert preds == sorted(preds, reverse=True)

    def test_one_cold_block_degrades_whole_plan(self, perf_env):
        from transmogrifai_tpu.parallel.scheduler import _Block
        perf.set_model(_warm_model(
            {"block_runtime": _block_rows((4, 16, 64))}))
        blocks = _mk_blocks() + [_Block(1, ("generic", "abcd1234"), [0])]
        # generic unfitted targets are fine; but a block the model
        # CAN'T price (different... same target, still priced) — force
        # coldness via an unfitted model for contrast
        planned = self._plan(blocks)
        # generic block IS priced by the shared target (features degrade
        # to shape facts), so the plan stays warm — every block priced
        assert all(b.pred_s is not None for b in planned)

    def test_warm_oversize_block_splits_toward_target(self, perf_env,
                                                      monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_PERF_TARGET_BLOCK_S", "1.0")
        # value = 2.0 * iters seconds → the 64-iter 2-config block
        # predicts ~128s >> 2×1s target and must split into singles
        perf.set_model(_warm_model(
            {"block_runtime": _block_rows((4, 16, 64), scale=2.0)}))
        planned = self._plan(_mk_blocks())
        assert len(planned) == 6  # every 2-config block split
        assert all(len(b.idxs) == 1 for b in planned)
        # grid indices all survive exactly once
        assert sorted(i for b in planned for i in b.idxs) == list(range(6))


# --------------------------------------------------------------------------- #
# _run_groups_resilient: OOM halving, and the rows it records                 #
# --------------------------------------------------------------------------- #

def _oom_groups():
    """run_one that device-OOMs whenever more than one config is live."""
    calls = []

    def run_one(static, idxs):
        if len(idxs) > 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory "
                               "allocating histogram buffers")
        calls.append(list(idxs))
    return {("grp",): [0, 1, 2, 3]}, run_one, calls


def _facts_cb(n_rows=1000):
    def facts(static, idxs):
        return perf.block_features("forest", (20, 32, False, 6),
                                   len(idxs), n_rows, 50, 3)
    return facts


def _run_resilient(groups, run_one, facts):
    from transmogrifai_tpu.obs import goodput
    from transmogrifai_tpu.obs.trace import TRACER
    from transmogrifai_tpu.parallel.sweep import _run_groups_resilient
    commits = []
    with TRACER.span("run:test-oom", category="run", new_trace=True) as root:
        _run_groups_resilient(
            groups, run_one,
            commit=lambda idxs, s=None, f=None: commits.append(list(idxs)),
            family="forest", facts=facts)
    report = goodput.build_report(root, TRACER.trace_spans(root.trace_id))
    return commits, report


class TestOomHalving:
    def test_cold_pays_oom_redo_via_halving(self, perf_env):
        perf.set_model(perf.CostModel())
        groups, run_one, calls = _oom_groups()
        commits, report = _run_resilient(groups, run_one, _facts_cb())
        assert sorted(i for c in calls for i in c) == [0, 1, 2, 3]
        # the halving fallback burned real badput
        assert report.counts.get("oom_redos", 0) >= 1
        assert report.buckets["oom_redo_s"] > 0
        # every half that fitted committed, and recorded its runtime row
        assert sorted(i for c in commits for i in c) == [0, 1, 2, 3]
        assert len(perf.get_corpus().rows("block_runtime")) == len(commits)

    def test_blocks_record_runtime_rows_even_cold(self, perf_env):
        perf.set_model(perf.CostModel())
        groups = {("g",): [0, 1]}
        commits, _ = _run_resilient(groups, lambda s, i: None, _facts_cb())
        assert commits == [[0, 1]]
        rows = perf.get_corpus().rows("block_runtime")
        assert len(rows) == 1
        assert rows[0]["features"]["n_configs"] == 2.0


# --------------------------------------------------------------------------- #
# consumer 3: upload workers/depth                                            #
# --------------------------------------------------------------------------- #

class _FakeStore:
    n_rows = 200_000
    n_features = 50


class TestUploadPlan:
    def test_cold_plan_is_todays_defaults(self, perf_env):
        from transmogrifai_tpu.data.pipeline import IngestStats
        from transmogrifai_tpu.parallel import bigdata as bd
        perf.set_model(perf.CostModel())
        stats = IngestStats()
        w, d = bd._resolve_upload_plan(_FakeStore(), 4096, None, None, stats)
        assert (w, d) == (bd.UPLOAD_WORKERS, bd.UPLOAD_DEPTH)
        assert stats.plan == "" and stats.predicted_wall_s == 0.0

    def test_explicit_values_always_win(self, perf_env):
        from transmogrifai_tpu.data.pipeline import IngestStats
        from transmogrifai_tpu.parallel import bigdata as bd
        perf.set_model(_warm_model({"ingest": self._ingest_rows()}))
        stats = IngestStats()
        w, d = bd._resolve_upload_plan(_FakeStore(), 4096, 3, 7, stats)
        assert (w, d) == (3, 7)

    @staticmethod
    def _ingest_rows():
        rows = []
        for workers in (1, 2, 4, 8):
            for depth in (1, 2, 4, 8):
                wall = 100.0 / math.sqrt(workers) + 10.0 / depth
                rows.append({"features": perf.ingest_features(
                    2e7, workers, depth, 49), "value": wall})
        return rows

    def test_warm_plan_picks_predicted_fastest(self, perf_env):
        from transmogrifai_tpu.data.pipeline import IngestStats
        from transmogrifai_tpu.parallel import bigdata as bd
        perf.set_model(_warm_model({"ingest": self._ingest_rows()}))
        stats = IngestStats()
        w, d = bd._resolve_upload_plan(_FakeStore(), 4096, None, None, stats)
        assert (w, d) == (8, 8)  # monotone-decreasing law
        assert stats.plan == "model" and stats.predicted_wall_s > 0

    def test_pipeline_records_ingest_row(self, perf_env):
        from transmogrifai_tpu.data.pipeline import (
            IngestStats, run_chunk_pipeline)
        perf.set_model(perf.CostModel())
        stats = IngestStats()

        def prep(i):
            stats.note_cast(0.0, 1000)
            return i

        run_chunk_pipeline(range(4), prep, lambda p: None,
                           workers=1, depth=1, stats=stats)
        rows = perf.get_corpus().rows("ingest")
        assert len(rows) == 1
        assert rows[0]["features"]["chunks"] == 4.0


# --------------------------------------------------------------------------- #
# consumer 4: serving ladder                                                  #
# --------------------------------------------------------------------------- #

class TestServingLadder:
    @staticmethod
    def _bucket_rows(per_row_s=2e-5, base=0.002):
        rows = []
        for b in (1, 2, 4, 8, 16, 32, 64):
            for _ in range(3):
                rows.append({"features": {"bucket": float(b)},
                             "value": base + per_row_s * b})
        return rows

    def test_cold_ladder_is_power_of_two(self, perf_env):
        from transmogrifai_tpu.serving.batcher import (
            bucket_ladder, derive_ladder)
        assert derive_ladder(64, 1, [1, 2, 3], None) == bucket_ladder(64, 1)
        # warm model but no sizes observed yet: also today's ladder
        model = _warm_model({"serving_bucket": self._bucket_rows()})
        assert derive_ladder(64, 1, [], model) == bucket_ladder(64, 1)
        # model without the serving target fitted: today's ladder
        assert derive_ladder(64, 1, [1, 2],
                             perf.CostModel()) == bucket_ladder(64, 1)

    def test_warm_flat_latency_collapses_rungs(self, perf_env):
        from transmogrifai_tpu.serving.batcher import (
            bucket_ladder, derive_ladder)
        # latency flat in bucket size → padding is free → rungs collapse
        model = _warm_model({"serving_bucket": self._bucket_rows(
            per_row_s=0.0, base=0.005)})
        ladder = derive_ladder(64, 1, [1, 2, 3, 40], model)
        assert ladder[-1] == 64  # the cap is always reachable
        assert len(ladder) < len(bucket_ladder(64, 1))

    def test_warm_steep_latency_keeps_traffic_rungs(self, perf_env):
        from transmogrifai_tpu.serving.batcher import derive_ladder
        model = _warm_model({"serving_bucket": self._bucket_rows(
            per_row_s=5e-3, base=1e-4)})
        sizes = [3] * 60 + [24] * 30 + [60] * 10
        ladder = derive_ladder(64, 1, sizes, model)
        assert ladder[-1] == 64
        assert len(ladder) >= 4  # steep cost: rungs survive
        # every request size has a rung within 2x (no huge padding)
        for s in (3, 24, 60):
            b = min(x for x in ladder if x >= s)
            assert b <= 2 * s + 8


# --------------------------------------------------------------------------- #
# residual recording + goodput + journal facts                                #
# --------------------------------------------------------------------------- #

class TestResiduals:
    def test_note_records_histogram_and_event(self, perf_env):
        from transmogrifai_tpu.obs import goodput
        from transmogrifai_tpu.obs.metrics import get_registry
        from transmogrifai_tpu.obs.trace import TRACER
        with TRACER.span("run:residual", category="run",
                         new_trace=True) as root:
            perf.note("block_runtime", {"n_configs": 1},
                      perf.Prediction(2.0, 1.5, 2.5, 10), 1.0)
        report = goodput.build_report(root, TRACER.trace_spans(root.trace_id))
        assert report.perf.get("predictions") == 1
        assert report.perf["mean_abs_rel_err"] == pytest.approx(1.0)
        assert report.perf["by_target"] == {"block_runtime": 1}
        reg = get_registry().to_json()
        assert "perf_model_abs_rel_err" in reg

    def test_sweep_journal_records_facts_and_harvests(self, perf_env,
                                                      tmp_path):
        import jax.numpy as jnp

        from transmogrifai_tpu.evaluators import (
            BinaryClassificationEvaluator)
        from transmogrifai_tpu.models import OpLogisticRegression
        from transmogrifai_tpu.parallel.sweep import run_sweep
        from transmogrifai_tpu.runtime.journal import SweepJournal
        from transmogrifai_tpu.selector.validators import OpCrossValidation
        from transmogrifai_tpu.stages.base import FitContext
        rng = np.random.default_rng(5)
        n = 120
        X = jnp.asarray(rng.normal(size=(n, 4)).astype(np.float32))
        y_np = (rng.normal(size=n) > 0).astype(np.float32)
        y = jnp.asarray(y_np)
        folds = OpCrossValidation(n_folds=2, seed=3).splits(y_np)
        path = str(tmp_path / "fam.journal")
        journal = SweepJournal(path, meta={"sig": "t"})
        est = OpLogisticRegression(max_iter=4)
        grids = [{"reg_param": r} for r in (0.01, 0.1)]
        run_sweep(est, grids, X, y, folds,
                  BinaryClassificationEvaluator(), FitContext(n_rows=n),
                  journal=journal)
        recs = journal.records()
        assert len(recs) == 2
        facts = recs[0]["facts"]
        assert facts is not None
        assert facts["fam_logistic"] == 1.0
        assert facts["n_configs"] == 2.0
        assert facts["n_cols"] == 4.0
        assert facts["block_s"] > 0
        assert "block_key" in facts
        # a RELOADED journal (resume path) still carries the facts
        reloaded = SweepJournal(path, meta={"sig": "t"})
        assert reloaded.records()[0]["facts"]["block_key"] == \
            facts["block_key"]
        # the live run already recorded this block (stamped with the
        # SAME block_key the journal carries): harvesting the run's own
        # journal must not duplicate it
        corpus = perf.get_corpus()
        live = corpus.rows("block_runtime")
        assert len(live) == 1 and live[0].get("block_key") == \
            facts["block_key"]
        assert perf.harvest_journal([path], corpus) == 0
        assert len(corpus.rows("block_runtime")) == 1
        # a corpus WITHOUT the live rows (another machine / lost rows)
        # harvests exactly one row per unique block — idempotently
        fresh = perf.CostCorpus(str(tmp_path / "fresh-corpus"))
        assert perf.harvest_journal([path], fresh) == 1
        assert perf.harvest_journal([path], fresh) == 0
        rows = fresh.rows("block_runtime")
        assert len(rows) == 1
        assert rows[0]["features"]["fam_logistic"] == 1.0
        assert "block_key" not in rows[0]["features"]
        assert rows[0]["block_key"] == facts["block_key"]


# --------------------------------------------------------------------------- #
# params threading                                                            #
# --------------------------------------------------------------------------- #

class TestParams:
    def test_opparams_roundtrip(self):
        from transmogrifai_tpu.workflow.params import OpParams
        p = OpParams.from_json({
            "perf_model": {"enabled": True, "corpus_dir": "/tmp/x",
                           "target_block_s": 12.5, "min_rows": 16}})
        assert p.perf_model.corpus_dir == "/tmp/x"
        assert p.perf_model.target_block_s == 12.5
        assert p.perf_model.min_rows == 16
        back = OpParams.from_json(p.to_json())
        assert back.perf_model.to_json() == p.perf_model.to_json()

    def test_params_scope_install_and_restore(self, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_PERF_MODEL", "1")
        from transmogrifai_tpu.perf.params import (
            get_params, params_scope, resolved_corpus_dir)
        base = get_params()
        with params_scope({"corpus_dir": "/tmp/scope-test"}):
            assert resolved_corpus_dir() == "/tmp/scope-test"
        assert get_params() is base
        # None scope is a no-op (ambient params stay active)
        with params_scope(None):
            assert get_params() is base

    def test_env_kill_switch_beats_params(self, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_PERF_MODEL", "0")
        from transmogrifai_tpu.perf.params import enabled
        perf.set_params(perf.PerfModelParams(enabled=True))
        try:
            assert not enabled()
        finally:
            perf.set_params(None)

    def test_serving_params_auto_ladder_roundtrip(self):
        from transmogrifai_tpu.workflow.params import ServingParams
        sp = ServingParams.from_json({"auto_ladder": True})
        assert sp.auto_ladder is True
        assert sp.to_config().auto_ladder is True
        assert ServingParams.from_json(sp.to_json()).auto_ladder is True


# --------------------------------------------------------------------------- #
# fleet corpus merge (replica shards, one directory)                          #
# --------------------------------------------------------------------------- #

class TestCorpusFleetMerge:
    def test_merge_total_order_breaks_int_second_ties(self, perf_env,
                                                      monkeypatch):
        """Replica shards on a fleet store carry identical int-second
        `ts` constantly; the (ts, replica, seq) total order must keep
        the merged view stable across fresh readers instead of leaving
        same-second interleaving to shard listing order."""
        import transmogrifai_tpu.perf.corpus as corpus_mod
        monkeypatch.setattr(corpus_mod.time, "time", lambda: 1700000000.5)
        ca = corpus_mod.CostCorpus(str(perf_env), replica="a")
        cb = corpus_mod.CostCorpus(str(perf_env), replica="b")
        # interleave appends so FILE order disagrees with replica order
        for i, c in enumerate([cb, ca, cb, ca, ca, cb]):
            assert c.append("block_runtime", {"x": 1.0}, float(i), tag=i)
        want = [1, 3, 4, 0, 2, 5]  # all of a's rows (by seq), then b's
        for _ in range(2):  # fresh readers agree with each other
            reader = corpus_mod.CostCorpus(str(perf_env))
            got = [r["tag"] for r in reader.rows("block_runtime")]
            assert got == want

    def test_harvest_after_live_recording_dedupes_across_shards(
            self, perf_env, monkeypatch):
        """A sweep host live-records its block row into ITS replica
        shard; harvesting that host's journal from ANOTHER replica must
        see the key through the merged view and skip it — even when
        every row carries the same int-second ts."""
        import transmogrifai_tpu.perf.corpus as corpus_mod
        from transmogrifai_tpu.perf.corpus import harvest_journal
        monkeypatch.setattr(corpus_mod.time, "time", lambda: 1700000000.5)
        live = corpus_mod.CostCorpus(str(perf_env), replica="h0")
        assert live.append("block_runtime", {"n_configs": 2.0}, 0.5,
                           source="live", block_key="bk1")
        journal = perf_env / "run.journal-wh0_0.jsonl"
        recs = [
            {"grid": {"i": 0}, "facts": {"block_key": "bk1",
                                         "block_s": 0.5, "n_configs": 2}},
            {"grid": {"i": 1}, "facts": {"block_key": "bk2",
                                         "block_s": 0.7, "n_configs": 2}},
        ]
        journal.write_text(
            "\n".join(json.dumps(r) for r in recs) + "\n", encoding="utf-8")
        harvester = corpus_mod.CostCorpus(str(perf_env), replica="hx")
        # bk1 is already live-recorded in h0's shard: only bk2 lands
        assert harvest_journal([str(journal)], corpus=harvester) == 1
        rows = harvester.rows("block_runtime")
        keys = [r.get("block_key") for r in rows]
        assert keys == ["bk1", "bk2"]
        assert rows[1]["source"] == "journal"
        # re-harvest is a no-op: both keys visible through the merge
        assert harvest_journal([str(journal)], corpus=harvester) == 0
        assert len(harvester.rows("block_runtime")) == 2
