"""Distributed sweep scheduler (parallel/scheduler.py) + journal shards.

The conftest forces 8 virtual CPU devices (the reference's `local[2]`
trick), so the work-stealing schedule, kill/resume, and steal paths all
exercise for real — and because every virtual device shares one host,
per-worker blocks must reproduce the single-device sweep BIT FOR BIT.
"""

import glob
import json
import os

import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
from transmogrifai_tpu.models import OpLinearSVC, OpLogisticRegression
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.parallel.smoke import _cols as _smoke_cols
from transmogrifai_tpu.parallel.smoke import _selector as _smoke_selector
from transmogrifai_tpu.runtime.journal import ShardedSweepJournal
from transmogrifai_tpu.selector import ModelSelector
from transmogrifai_tpu.selector.validators import OpCrossValidation
from transmogrifai_tpu.stages.base import FitContext

N = 240


@pytest.fixture(scope="module")
def cols():
    # shared with the multichip smoke: ONE copy of the synthetic data
    # and of the carefully tuned 3-blocks-of-2 grid — the kill/resume
    # block arithmetic in both files depends on that grid shape
    return _smoke_cols(N)


def _selector(ckpt=None):
    return _smoke_selector(ckpt)


def _fit(selector, cols, mesh=None):
    return selector.fit_model(cols, FitContext(n_rows=N, seed=7, mesh=mesh))


def _rows(model):
    return {(r.model, json.dumps(r.grid, sort_keys=True)): r.fold_metrics
            for r in model.summary.validation_results}


def _need_devices(n=8):
    import jax
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")


# --------------------------------------------------------------------------- #
# journal shards                                                              #
# --------------------------------------------------------------------------- #

def test_sharded_journal_multi_writer_merge(tmp_path):
    base = str(tmp_path / "fam.journal")
    j = ShardedSweepJournal(base, meta={"sig": "abc"})
    j.shard(0).append({"a": 1}, [0.5, 0.6], duration_s=1.0)
    j.shard(3).append({"a": 2}, [0.7, 0.8], duration_s=2.0)
    # merged reads across shards, through any shard's view
    assert j.lookup({"a": 1}) == [0.5, 0.6]
    assert j.shard(3).lookup({"a": 1}) == [0.5, 0.6]
    assert j.duration_of({"a": 2}) == 2.0
    assert len(j) == 2
    assert sorted(os.path.basename(p) for p in glob.glob(base + "-w*")) == \
        ["fam.journal-w0.jsonl", "fam.journal-w3.jsonl"]
    # a fresh instance (resume) discovers and merges every shard
    j2 = ShardedSweepJournal(base, meta={"sig": "abc"})
    assert j2.lookup({"a": 1}) == [0.5, 0.6]
    assert j2.lookup({"a": 2}) == [0.7, 0.8]


def test_sharded_journal_torn_tail_repaired_per_shard(tmp_path):
    base = str(tmp_path / "fam.journal")
    j = ShardedSweepJournal(base, meta={"sig": "s"})
    j.shard(0).append({"a": 1}, [0.5])
    j.shard(1).append({"a": 2}, [0.6])
    # tear shard 1's tail mid-append (kill mid-write)
    with open(base + "-w1.jsonl", "ab") as fh:
        fh.write(b'{"key": "torn')
    j2 = ShardedSweepJournal(base, meta={"sig": "s"})
    assert j2.lookup({"a": 1}) == [0.5]
    assert j2.lookup({"a": 2}) == [0.6]  # intact prefix survives
    # the repaired shard accepts appends again
    j2.shard(1).append({"a": 3}, [0.7])
    j3 = ShardedSweepJournal(base, meta={"sig": "s"})
    assert j3.lookup({"a": 3}) == [0.7]


def test_sharded_journal_merges_legacy_single_file(tmp_path):
    from transmogrifai_tpu.runtime.journal import SweepJournal
    base = str(tmp_path / "fam.journal")
    old = SweepJournal(base, meta={"sig": "s"})
    old.append({"a": 1}, [0.9])
    j = ShardedSweepJournal(base, meta={"sig": "s"})
    assert j.lookup({"a": 1}) == [0.9]  # read-only merge of the old file
    j.shard(0).append({"a": 2}, [0.8])
    # the legacy file was not appended to
    assert SweepJournal(base, meta={"sig": "s"}).lookup({"a": 2}) is None


def test_sharded_journal_meta_mismatch_rotates_shard(tmp_path):
    base = str(tmp_path / "fam.journal")
    ShardedSweepJournal(base, meta={"sig": "old"}).shard(0).append(
        {"a": 1}, [0.5])
    j = ShardedSweepJournal(base, meta={"sig": "new"})
    assert j.lookup({"a": 1}) is None  # stale shard must not resume


# --------------------------------------------------------------------------- #
# static signatures                                                           #
# --------------------------------------------------------------------------- #

def test_static_signature_matches_handler_grouping():
    from transmogrifai_tpu.models import OpRandomForestClassifier
    from transmogrifai_tpu.parallel.sweep import static_signature

    lr = OpLogisticRegression()
    s1 = static_signature(lr, {"reg_param": 0.1, "max_iter": 8})
    s2 = static_signature(lr, {"reg_param": 0.01, "max_iter": 8})
    s3 = static_signature(lr, {"reg_param": 0.1, "max_iter": 4})
    s4 = static_signature(lr, {"reg_param": 0.1, "max_iter": 8,
                               "elastic_net_param": 0.5})
    assert s1 == s2 and s1 != s3 and s1 != s4  # reg traced; iter/enet static

    rf = OpRandomForestClassifier(n_trees=4, max_bins=16)
    # depths 5 and 6 share the 6-bucket; 12 compiles apart
    r5 = static_signature(rf, {"max_depth": 5})
    r6 = static_signature(rf, {"max_depth": 6})
    r12 = static_signature(rf, {"max_depth": 12})
    assert r5 == r6 and r6 != r12

    class Unknown:
        params: dict = {}
    u1 = static_signature(Unknown(), {"p": 1})
    u2 = static_signature(Unknown(), {"p": 2})
    assert u1[0] == "generic" and u1 != u2  # per-config blocks


# --------------------------------------------------------------------------- #
# the schedule                                                                #
# --------------------------------------------------------------------------- #

def test_scheduled_two_family_sweep_bit_identical(cols):
    """Acceptance: a 2-family grid sweep scheduled across an 8-wide
    sweep mesh returns the bit-identical winner (metrics JSON-roundtrip
    exact) to the single-device sweep."""
    _need_devices(8)
    base = _rows(_fit(_selector(), cols))
    mesh = make_mesh(8, sweep=8)
    sched = _rows(_fit(_selector(), cols, mesh=mesh))
    assert set(base) == set(sched)
    for k in base:
        assert json.dumps(base[k]) == json.dumps(sched[k]), k


def test_scheduled_sweep_composes_data_axis(cols):
    """sweep=4 × data=2: each worker lane owns a (1, 2) sub-mesh and
    run_sweep's data path shards its rows — the 2-D composition. Metric
    parity is allclose here (cross-device psum reduction order)."""
    _need_devices(8)
    base = _rows(_fit(_selector(), cols))
    mesh = make_mesh(8, sweep=4)
    sched = _rows(_fit(_selector(), cols, mesh=mesh))
    assert set(base) == set(sched)
    for k in base:
        np.testing.assert_allclose(base[k], sched[k], rtol=2e-4, err_msg=k)


def test_scheduler_kill_resume_reruns_only_inflight_block(cols, tmp_path):
    """Acceptance: killing one worker mid-grid preempts the schedule
    (drain journals every other in-flight block); resuming re-runs only
    the killed worker's in-flight block — asserted from the journal
    shards — and reproduces the bit-identical winner."""
    _need_devices(8)
    from transmogrifai_tpu.runtime.faults import (
        SITE_WORKER_BLOCK, FaultPlan, FaultSpec, InjectedKill)

    mesh = make_mesh(8, sweep=8)
    clean = _fit(_selector(), cols, mesh=mesh)
    ckpt = str(tmp_path / "ckpt")

    def shard_records():
        return sum(max(0, sum(1 for _ in open(p)) - 1)
                   for p in glob.glob(f"{ckpt}/*.journal-w*.jsonl"))

    # kill at the LAST of the 3 block claims: the other two blocks are
    # already in flight and drain to their journals
    plan = FaultPlan([FaultSpec(SITE_WORKER_BLOCK, at=3, kind="kill")])
    with pytest.raises(InjectedKill):
        with plan.active():
            _fit(_selector(ckpt), cols, mesh=mesh)
    journaled = shard_records()
    assert journaled == 4  # 6 configs total - the 2-config in-flight block

    resumed = _fit(_selector(ckpt), cols, mesh=mesh)
    assert shard_records() - journaled == 2  # ONLY the lost block re-ran
    assert resumed.summary.best_grid == clean.summary.best_grid
    b, r = _rows(clean), _rows(resumed)
    for k in b:
        assert json.dumps(b[k]) == json.dumps(r[k]), k


def test_resumed_block_best_accounts_for_prekill_blocks(cols, tmp_path):
    """A family whose grids split into several scheduler blocks: after a
    kill+resume, the records appended by the resumed block carry a
    best-so-far annotation that accounts for the blocks journaled BEFORE
    the kill — run_sweep seeds its tracker from journal.rows(), which
    sees the whole family journal, not just the one re-run block."""
    _need_devices(8)
    from transmogrifai_tpu.runtime.faults import (
        SITE_WORKER_BLOCK, FaultPlan, FaultSpec, InjectedKill)

    mesh = make_mesh(8, sweep=8)
    ckpt = str(tmp_path / "ckpt")

    def one_family(c=None):
        # ONE family, 2 blocks: LPT tie-break runs the (16, False) block
        # first, and more iters converge better, so the family best lives
        # in the FIRST (pre-kill) block — an unseeded tracker on the
        # resumed (max_iter=2) block could not name it
        lr = [{"reg_param": r, "max_iter": it}
              for it in (16, 2) for r in (0.01, 0.1)]
        return ModelSelector(
            models=[(OpLogisticRegression(), lr)],
            validator=OpCrossValidation(n_folds=2, seed=11),
            evaluator=BinaryClassificationEvaluator(),
            checkpoint_dir=c)

    plan = FaultPlan([FaultSpec(SITE_WORKER_BLOCK, at=2, kind="kill")])
    with pytest.raises(InjectedKill):
        with plan.active():
            _fit(one_family(ckpt), cols, mesh=mesh)
    shards = glob.glob(f"{ckpt}/*.journal-w*.jsonl")
    pre = {}
    for p in shards:
        for line in open(p):
            rec = json.loads(line)
            if rec.get("fold_metrics"):
                pre[json.dumps(rec["grid"], sort_keys=True)] = float(
                    np.mean(rec["fold_metrics"]))
    assert len(pre) == 2, f"expected one 2-config block journaled: {pre}"

    _fit(one_family(ckpt), cols, mesh=mesh)
    best_means = []
    for p in glob.glob(f"{ckpt}/*.journal-w*.jsonl"):
        for line in open(p):
            rec = json.loads(line)
            grid = json.dumps(rec.get("grid"), sort_keys=True)
            if rec.get("best") and grid not in pre:  # appended on resume
                best_means.append(float(rec["best"]["mean"]))
    assert best_means, "resume appended no best-annotated records"
    assert max(best_means) >= max(pre.values()), (
        "resumed journal `best` ignores the pre-kill block")


def test_scheduler_steals_block_of_retired_worker(cols):
    """A worker-level error retires one lane; its in-flight block is
    requeued and a survivor steals it — the sweep completes exactly."""
    _need_devices(8)
    from transmogrifai_tpu.obs import goodput as obs_goodput
    from transmogrifai_tpu.obs.trace import TRACER
    from transmogrifai_tpu.runtime.faults import (
        SITE_WORKER_BLOCK, FaultPlan, FaultSpec)

    mesh = make_mesh(8, sweep=8)
    base = _rows(_fit(_selector(), cols))
    plan = FaultPlan([FaultSpec(SITE_WORKER_BLOCK, at=1, kind="error")])
    with TRACER.span("run:test-steal", category="run",
                     new_trace=True) as root:
        with plan.active():
            stolen = _rows(_fit(_selector(), cols, mesh=mesh))
    for k in base:
        assert json.dumps(base[k]) == json.dumps(stolen[k]), k
    report = obs_goodput.build_report(root, TRACER.trace_spans(root.trace_id))
    assert report.counts.get("workers_retired") == 1
    assert report.mesh.get("requeues", 0) >= 1


def test_scheduler_drops_failing_family_keeps_others(cols):
    """A family whose blocks raise an ordinary Exception fails alone:
    the selector's family-drop policy (OpValidator.scala:344-347) still
    applies under the scheduler."""
    _need_devices(8)
    from transmogrifai_tpu.models import OpNaiveBayes

    mesh = make_mesh(8, sweep=8)
    # NB raises on negative features (Spark parity) — a family-level error
    sel = ModelSelector(
        models=[(OpLogisticRegression(max_iter=8),
                 [{"reg_param": 0.01}, {"reg_param": 0.1}]),
                (OpNaiveBayes(), [{"smoothing": 1.0}])],
        validator=OpCrossValidation(n_folds=2, seed=11),
        evaluator=BinaryClassificationEvaluator())
    model = _fit(sel, cols, mesh=mesh)
    fams = {r.model for r in model.summary.validation_results}
    assert fams == {"OpLogisticRegression"}


def test_scheduler_mesh_utilization_in_goodput(cols):
    _need_devices(8)
    from transmogrifai_tpu.obs import goodput as obs_goodput
    from transmogrifai_tpu.obs.trace import TRACER

    mesh = make_mesh(8, sweep=8)
    with TRACER.span("run:test-mesh", category="run",
                     new_trace=True) as root:
        _fit(_selector(), cols, mesh=mesh)
    report = obs_goodput.build_report(root, TRACER.trace_spans(root.trace_id))
    assert report.mesh, "no mesh rollup in the goodput report"
    assert 0.0 < report.mesh["utilization_frac"] <= 1.0
    assert report.mesh["workers"] == 8
    assert report.mesh["blocks"] == 3
    assert report.mesh["schedules"] == 1
    assert "mesh" in report.to_json()


def test_scheduler_env_optout_uses_sharded_path(cols, monkeypatch):
    """TRANSMOGRIFAI_DISTRIBUTED_SWEEP=0 falls back to the grid-axis
    vmap sharding path (no scheduler spans)."""
    _need_devices(8)
    from transmogrifai_tpu.obs.trace import TRACER

    monkeypatch.setenv("TRANSMOGRIFAI_DISTRIBUTED_SWEEP", "0")
    mesh = make_mesh(8, sweep=8)
    with TRACER.span("run:test-optout", category="run",
                     new_trace=True) as root:
        model = _fit(_selector(), cols, mesh=mesh)
    spans = TRACER.trace_spans(root.trace_id)
    assert not [s for s in spans if s.category == "scheduler"]
    assert np.isfinite([r.mean_metric
                        for r in model.summary.validation_results]).all()


# --------------------------------------------------------------------------- #
# mesh + params plumbing                                                      #
# --------------------------------------------------------------------------- #

def test_multislice_mesh_rejects_nondivisible_device_count():
    from transmogrifai_tpu.parallel.mesh import make_multislice_mesh
    with pytest.raises(ValueError, match="do not divide"):
        make_multislice_mesh(n_slices=3)  # 8 % 3 != 0
    # explicit devices_per_slice still allows a subset
    mesh = make_multislice_mesh(n_slices=3, devices_per_slice=2)
    assert mesh.devices.size == 6
    with pytest.raises(ValueError, match="data_per_slice"):
        make_multislice_mesh(n_slices=2, data_per_slice=3)  # 4 % 3 != 0
    with pytest.raises(ValueError):
        make_multislice_mesh(n_slices=0)


def test_mesh_params_roundtrip_and_build():
    from transmogrifai_tpu.workflow.params import MeshParams, OpParams

    p = OpParams.from_json({"mesh": {"n_devices": 8, "sweep": 4}})
    assert p.mesh == MeshParams(n_devices=8, sweep=4)
    assert OpParams.from_json(p.to_json()).mesh == p.mesh
    mesh = p.mesh.build()
    assert dict(mesh.shape) == {"sweep": 4, "data": 2}
    ms = MeshParams(n_slices=2, data_per_slice=2).build()
    assert dict(ms.shape) == {"sweep": 4, "data": 2}


def test_mesh_params_build_rejects_bad_combinations():
    """A config asking for devices it cannot use fails loudly instead of
    silently training on a subset (or silently ignoring `sweep`)."""
    from transmogrifai_tpu.workflow.params import MeshParams

    with pytest.raises(ValueError, match="does not divide"):
        MeshParams(n_devices=8, n_slices=3).build()
    with pytest.raises(ValueError, match="sweep"):
        MeshParams(n_devices=8, sweep=8, n_slices=2).build()
    with pytest.raises(ValueError, match="data_per_slice"):
        # only the multislice layout reads data_per_slice — on the flat
        # mesh the requested data sharding would be silently dropped
        MeshParams(n_devices=8, data_per_slice=2).build()


def test_single_device_resume_reads_mesh_journal_shards(cols, tmp_path):
    """Resume symmetry: a sweep journaled by MESH workers then resumed
    WITHOUT a mesh (post-preemption fallback) must skip every
    mesh-completed block — and the result stays bit-identical."""
    _need_devices(8)
    from transmogrifai_tpu.runtime.faults import (
        SITE_WORKER_BLOCK, FaultPlan, FaultSpec, InjectedKill)

    mesh = make_mesh(8, sweep=8)
    ckpt = str(tmp_path / "ckpt")
    plan = FaultPlan([FaultSpec(SITE_WORKER_BLOCK, at=3, kind="kill")])
    with pytest.raises(InjectedKill):
        with plan.active():
            _fit(_selector(ckpt), cols, mesh=mesh)

    def records():
        # both shard files AND base .journal files (a family whose only
        # block was the killed one journals its resume re-run there)
        return sum(max(0, sum(1 for _ in open(p)) - 1)
                   for p in glob.glob(f"{ckpt}/*.journal*"))

    journaled = records()
    assert journaled == 4

    resumed = _fit(_selector(ckpt), cols, mesh=None)  # single-device resume
    assert records() - journaled == 2  # only the lost block's configs re-ran
    base = _rows(_fit(_selector(), cols))
    r = _rows(resumed)
    for k in base:
        assert json.dumps(base[k]) == json.dumps(r[k]), k


def test_sharded_journal_glob_metachar_dir(tmp_path):
    """Shard discovery must survive [, ], * in the checkpoint path."""
    d = tmp_path / "ckpt[2026]"
    d.mkdir()
    base = str(d / "fam.journal")
    ShardedSweepJournal(base, meta={"sig": "s"}).shard(2).append(
        {"a": 1}, [0.5])
    j = ShardedSweepJournal(base, meta={"sig": "s"})
    assert j.lookup({"a": 1}) == [0.5]
    assert ShardedSweepJournal.has_shards(base)


def test_scheduled_block_retries_transient_error(cols):
    """An error flagged `transient=True` INSIDE a scheduled block
    retries via the selector's RetryPolicy instead of
    dropping the family — distribution must not be less fault-tolerant
    than the single-device path."""
    _need_devices(8)
    from transmogrifai_tpu.runtime.faults import (
        SITE_RUN_BLOCK, FaultPlan, FaultSpec)

    mesh = make_mesh(8, sweep=8)
    base = _rows(_fit(_selector(), cols))
    # fires inside run_sweep's block execution on some worker, once
    plan = FaultPlan([FaultSpec(SITE_RUN_BLOCK, at=1, kind="error",
                                transient=True)])
    with plan.active():
        retried = _rows(_fit(_selector(), cols, mesh=mesh))
    assert set(base) == set(retried)  # family survived
    for k in base:
        assert json.dumps(base[k]) == json.dumps(retried[k]), k


def test_sharded_journal_host_qualified_shards_and_refresh(tmp_path):
    """Pod hosts write host-qualified shards (`-w<host>_<lane>`); a
    fresh refresh() on one host discovers shards another host wrote
    AFTER this journal was opened — the cross-host completion log."""
    base = str(tmp_path / "fam.journal")
    mine = ShardedSweepJournal(base, meta={"sig": "s"})
    mine.shard("h0_0").append({"a": 1}, [0.5], duration_s=1.0)
    # another "host" opens the same base later and writes its shard
    theirs = ShardedSweepJournal(base, meta={"sig": "s"})
    theirs.shard("h1_0").append({"a": 2}, [0.7], duration_s=2.0)
    # mine opened before h1's shard existed: refresh pulls it in
    assert mine.refresh() >= 1
    assert mine.lookup({"a": 2}) == [0.7]
    names = sorted(os.path.basename(p) for p in glob.glob(base + "-w*"))
    assert names == ["fam.journal-wh0_0.jsonl", "fam.journal-wh1_0.jsonl"]
    # numeric tokens still parse as ints (legacy single-host shards)
    mine.shard(2).append({"a": 3}, [0.9], duration_s=1.0)
    assert ShardedSweepJournal(base, meta={"sig": "s"}).lookup({"a": 3}) \
        == [0.9]


def test_sharded_journal_illegal_shard_token_rejected(tmp_path):
    base = str(tmp_path / "fam.journal")
    j = ShardedSweepJournal(base, meta={"sig": "s"})
    import pytest
    with pytest.raises(ValueError):
        j.shard("h0/../../etc")


def test_journal_parse_cache_hits_on_unchanged_file(tmp_path):
    """Re-opening an unchanged journal shard must serve rows from the
    (ino, size, mtime) parse cache; an append invalidates the key."""
    from transmogrifai_tpu.runtime import journal as journal_mod
    base = str(tmp_path / "fam.journal")
    j = ShardedSweepJournal(base, meta={"sig": "s"})
    j.shard(0).append({"a": 1}, [0.5], duration_s=1.0)
    # a fresh instance parses the shard once and caches the result...
    j2 = ShardedSweepJournal(base, meta={"sig": "s"})
    assert j2.lookup({"a": 1}) == [0.5]
    path = os.path.abspath(glob.glob(base + "-w*")[0])
    st = os.stat(path)
    with journal_mod._PARSE_CACHE_LOCK:
        stat_key, _state = journal_mod._PARSE_CACHE[path]
    assert stat_key == (st.st_ino, st.st_size, st.st_mtime_ns)
    # append moves size/mtime: the stale key must not be served
    j2.shard(0).append({"a": 2}, [0.7], duration_s=1.0)
    j3 = ShardedSweepJournal(base, meta={"sig": "s"})
    assert j3.lookup({"a": 2}) == [0.7]
