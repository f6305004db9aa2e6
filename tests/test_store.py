"""store/: content-addressed artifact store (put/get/stat, corruption
rejection, TTL+LRU gc, prefetch), filesystem CAS state cells, and the
lease-based shared tenant quota built on them."""

import json
import os
import threading
import time

import pytest

from transmogrifai_tpu.obs.metrics import MetricsRegistry
from transmogrifai_tpu.store import (
    ArtifactStore, LeaseTable, LocalDirBackend, SharedQuota, StateCell,
    StoreCorruptError, cache_root, resolve_dir, store_configured)
from transmogrifai_tpu.store.artifact import MANIFEST


def _store(tmp_path, **kw):
    return ArtifactStore(LocalDirBackend(str(tmp_path / "store")),
                         registry=MetricsRegistry(), **kw)


def _put(store, key, payload=b"abc123", meta=None):
    def stage(tmp):
        with open(os.path.join(tmp, "payload.bin"), "wb") as fh:
            fh.write(payload)
    return store.put(key, stage, meta=meta or {"kind": "test"})


# --------------------------------------------------------------------- #
# config resolution                                                     #
# --------------------------------------------------------------------- #

class TestConfig:
    def test_store_env_moves_every_kind(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_STORE_DIR", str(tmp_path))
        assert store_configured()
        assert cache_root() == str(tmp_path)
        assert resolve_dir("feature_cache") == str(tmp_path / "feature_cache")
        assert resolve_dir("perf") == str(tmp_path / "perf")

    def test_subsystem_env_beats_store_root(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_STORE_DIR", str(tmp_path))
        monkeypatch.setenv("TRANSMOGRIFAI_FEATURE_CACHE_DIR", "/elsewhere")
        assert resolve_dir(
            "feature_cache",
            env="TRANSMOGRIFAI_FEATURE_CACHE_DIR") == "/elsewhere"

    def test_explicit_beats_everything(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_STORE_DIR", str(tmp_path))
        assert resolve_dir("perf", explicit="/mine") == "/mine"

    def test_default_is_inside_the_checkout(self, monkeypatch):
        # never $HOME: learned state must not pass between two
        # checkouts on one machine
        monkeypatch.delenv("TRANSMOGRIFAI_STORE_DIR", raising=False)
        assert not store_configured()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cache_root() == os.path.join(repo, ".transmogrifai_store")

    def test_consumers_follow_store_root(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_STORE_DIR", str(tmp_path))
        monkeypatch.delenv("TRANSMOGRIFAI_FEATURE_CACHE_DIR",
                           raising=False)
        monkeypatch.delenv("TRANSMOGRIFAI_PERF_CORPUS_DIR", raising=False)
        from transmogrifai_tpu.data.feature_cache import default_cache_dir
        from transmogrifai_tpu.perf.params import resolved_corpus_dir
        assert default_cache_dir() == str(tmp_path / "feature_cache")
        assert resolved_corpus_dir() == str(tmp_path / "perf")


# --------------------------------------------------------------------- #
# artifact roundtrip + verification                                     #
# --------------------------------------------------------------------- #

class TestArtifactStore:
    def test_put_get_stat_roundtrip(self, tmp_path):
        store = _store(tmp_path)
        path = _put(store, "k1", b"hello world", meta={"kind": "tape"})
        assert os.path.isfile(os.path.join(path, MANIFEST))
        got = store.get("k1")
        assert got == path
        with open(os.path.join(got, "payload.bin"), "rb") as fh:
            assert fh.read() == b"hello world"
        info = store.stat("k1")
        assert info.key == "k1" and info.bytes == 11 and info.files == 1
        assert info.meta["kind"] == "tape"
        assert store.keys() == ["k1"]

    def test_miss_is_none_not_error(self, tmp_path):
        store = _store(tmp_path)
        assert store.get("absent") is None
        assert store.stat("absent") is None

    def test_bit_flip_rejected(self, tmp_path):
        store = _store(tmp_path)
        path = _put(store, "k1", b"x" * 256)
        p = os.path.join(path, "payload.bin")
        blob = bytearray(open(p, "rb").read())
        blob[100] ^= 0xFF
        with open(p, "wb") as fh:
            fh.write(bytes(blob))
        with pytest.raises(StoreCorruptError) as ei:
            store.get("k1")
        assert "checksum mismatch" in ei.value.reason

    def test_truncation_rejected_even_without_verify(self, tmp_path):
        store = _store(tmp_path)
        path = _put(store, "k1", b"x" * 256)
        p = os.path.join(path, "payload.bin")
        with open(p, "r+b") as fh:
            fh.truncate(10)
        with pytest.raises(StoreCorruptError) as ei:
            store.get("k1", verify=False)
        assert "truncated" in ei.value.reason

    def test_key_mismatch_and_garbage_manifest(self, tmp_path):
        store = _store(tmp_path)
        path = _put(store, "k1")
        m = json.load(open(os.path.join(path, MANIFEST)))
        m["key"] = "other"
        with open(os.path.join(path, MANIFEST), "w") as fh:
            json.dump(m, fh)
        with pytest.raises(StoreCorruptError):
            store.get("k1")
        with open(os.path.join(path, MANIFEST), "w") as fh:
            fh.write("{torn")
        with pytest.raises(StoreCorruptError):
            store.get("k1")

    def test_illegal_keys_rejected(self, tmp_path):
        store = _store(tmp_path)
        for bad in ("../escape", "", ".hidden", "a/b"):
            with pytest.raises(ValueError):
                store.backend.path_of(bad)

    def test_failed_stage_leaves_nothing(self, tmp_path):
        store = _store(tmp_path)

        def stage(tmp):
            with open(os.path.join(tmp, "half.bin"), "wb") as fh:
                fh.write(b"partial")
            raise RuntimeError("staging died")

        with pytest.raises(RuntimeError):
            store.put("k1", stage)
        assert store.get("k1") is None
        assert store.keys() == []
        # no stranded staging dirs either
        root = store.backend.root
        assert [n for n in os.listdir(root)
                if n.startswith(".stage-")] == []

    def test_metrics_count_hits_misses_corrupt(self, tmp_path):
        reg = MetricsRegistry()
        store = ArtifactStore(LocalDirBackend(str(tmp_path / "s")),
                              registry=reg)
        _put(store, "k1")
        store.get("k1")
        store.get("nope")
        assert reg.find("store_hits_total",
                        backend="localdir").value == 1.0
        assert reg.find("store_misses_total",
                        backend="localdir").value == 1.0
        assert reg.find("store_puts_total",
                        backend="localdir").value == 1.0


# --------------------------------------------------------------------- #
# prefetch                                                              #
# --------------------------------------------------------------------- #

class TestPrefetch:
    def test_prefetch_verifies_then_get_skips_rehash(self, tmp_path,
                                                     monkeypatch):
        store = _store(tmp_path)
        _put(store, "k1", b"y" * 1024)
        t = store.prefetch("k1")
        assert t is not None
        t.join(5.0)
        # after a verified prefetch the next get must not re-hash
        import transmogrifai_tpu.store.artifact as art

        def no_hash(path):
            raise AssertionError("get re-hashed after verified prefetch")

        monkeypatch.setattr(art, "sha256_file", no_hash)
        assert store.get("k1") is not None
        # the voucher is consume-once: a second get re-verifies
        with pytest.raises(AssertionError):
            store.get("k1")

    def test_prefetch_finds_corruption(self, tmp_path):
        store = _store(tmp_path)
        path = _put(store, "k1", b"y" * 1024)
        p = os.path.join(path, "payload.bin")
        blob = bytearray(open(p, "rb").read())
        blob[7] ^= 0x01
        with open(p, "wb") as fh:
            fh.write(bytes(blob))
        t = store.prefetch("k1")
        t.join(5.0)
        with pytest.raises(StoreCorruptError):
            store.get("k1")

    def test_prefetch_absent_returns_none(self, tmp_path):
        assert _store(tmp_path).prefetch("absent") is None


# --------------------------------------------------------------------- #
# gc: TTL + LRU                                                         #
# --------------------------------------------------------------------- #

class TestGC:
    def test_ttl_evicts_stale_keeps_fresh(self, tmp_path):
        store = _store(tmp_path)
        _put(store, "old")
        _put(store, "new")
        # age the "old" access clock far past the TTL
        old_touch = store._touch_path("old")
        past = time.time() - 3600
        os.utime(old_touch, (past, past))
        out = store.gc(ttl_s=60, max_bytes=None)
        assert out["evicted"] == ["old"]
        assert store.keys() == ["new"]

    def test_lru_evicts_down_to_budget(self, tmp_path):
        store = _store(tmp_path)
        now = time.time()
        for i, key in enumerate(("a", "b", "c")):
            _put(store, key, b"z" * 100)
            t = now - (100 - i)  # a oldest, c newest
            os.utime(store._touch_path(key), (t, t))
        out = store.gc(ttl_s=None, max_bytes=250)
        assert out["bytes"] <= 250
        assert store.keys() == ["b", "c"]  # LRU victim was "a"

    def test_replayed_artifact_stays_resident(self, tmp_path):
        store = _store(tmp_path)
        now = time.time()
        for key in ("hot", "cold"):
            _put(store, key, b"z" * 100)
            t = now - 100
            os.utime(store._touch_path(key), (t, t))
        store.get("hot")  # replay refreshes the access clock
        out = store.gc(ttl_s=None, max_bytes=150)
        assert store.keys() == ["hot"]
        assert out["evicted"] == ["cold"]

    def test_gc_reclaims_corrupt_artifacts(self, tmp_path):
        store = _store(tmp_path)
        path = _put(store, "k1")
        with open(os.path.join(path, MANIFEST), "w") as fh:
            fh.write("not json")
        out = store.gc(ttl_s=None, max_bytes=None)
        assert out["evicted"] == ["k1"]
        assert store.keys() == []


# --------------------------------------------------------------------- #
# state cells (filesystem CAS)                                          #
# --------------------------------------------------------------------- #

class TestStateCell:
    def test_read_never_written(self, tmp_path):
        assert StateCell(str(tmp_path), "c").read() == (0, None)

    def test_versioned_write_read(self, tmp_path):
        cell = StateCell(str(tmp_path), "c")
        assert cell.try_write(0, {"n": 1}) is True
        assert cell.read() == (1, {"n": 1})
        # stale-version write loses the CAS
        assert cell.try_write(0, {"n": 99}) is False
        assert cell.try_write(1, {"n": 2}) is True
        assert cell.read() == (2, {"n": 2})

    def test_update_loop_and_prune(self, tmp_path):
        cell = StateCell(str(tmp_path), "c")
        for _ in range(10):
            cell.update(lambda v: {"n": (v or {}).get("n", 0) + 1})
        version, value = cell.read()
        assert version == 10 and value == {"n": 10}
        kept = [n for n in os.listdir(cell.dir) if n.startswith("c.v")]
        assert len(kept) <= 4  # keep-window pruned

    def test_concurrent_updates_lose_nothing(self, tmp_path):
        cell = StateCell(str(tmp_path), "c")
        n_threads, n_each = 4, 25

        def worker():
            for _ in range(n_each):
                cell.update(lambda v: {"n": (v or {}).get("n", 0) + 1},
                            retries=500)

        threads = [threading.Thread(target=worker, name=f"cas-{i}")
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert cell.read()[1] == {"n": n_threads * n_each}

    def test_illegal_name_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            StateCell(str(tmp_path), "../x")


# --------------------------------------------------------------------- #
# shared quota                                                          #
# --------------------------------------------------------------------- #

class TestSharedQuota:
    def test_k_replica_sum_bounded_by_burst(self, tmp_path):
        """Two replicas on one cell can jointly admit at most the burst
        budget when no time passes (refill is wall-clock driven)."""
        root = str(tmp_path)
        q1 = SharedQuota(root, replica="r1", registry=MetricsRegistry())
        q2 = SharedQuota(root, replica="r2", registry=MetricsRegistry())
        rate, burst = 0.000001, 100.0
        admitted = 0
        for q in (q1, q2) * 30:
            if q.try_spend("acme", 10, rate, burst):
                admitted += 10
        assert admitted == 100

    def test_denied_then_refill_eta_positive(self, tmp_path):
        q = SharedQuota(str(tmp_path), registry=MetricsRegistry())
        rate, burst = 0.000001, 10.0
        assert q.try_spend("t", 10, rate, burst) is True
        assert q.try_spend("t", 10, rate, burst) is False
        assert q.refill_eta_s("t", 10, rate) > 0

    def test_infinite_rate_always_admits(self, tmp_path):
        q = SharedQuota(str(tmp_path), registry=MetricsRegistry())
        assert q.try_spend("t", 10**9, float("inf"), 1.0) is True

    def test_lease_makes_hot_path_local(self, tmp_path):
        reg = MetricsRegistry()
        q = SharedQuota(str(tmp_path), replica="r1", lease_frac=0.5,
                        registry=reg)
        rate, burst = 0.000001, 100.0
        for _ in range(5):  # 5 spends of 10 inside one 50-token lease
            assert q.try_spend("t", 10, rate, burst)
        syncs = reg.find("router_quota_syncs_total", replica="r1")
        assert syncs.value == 1.0  # one withdraw served all five

    def test_snapshot_shape(self, tmp_path):
        q = SharedQuota(str(tmp_path), replica="rX",
                        registry=MetricsRegistry())
        q.try_spend("t", 1, 100.0, 100.0)
        snap = q.snapshot()
        assert snap["replica"] == "rX"
        assert "t" in snap["tenants"]
        assert snap["tenants"]["t"]["shared"]["rate"] == 100.0


# --------------------------------------------------------------------- #
# lease table (pod block claims)                                        #
# --------------------------------------------------------------------- #

class TestLeaseTable:
    def test_register_is_idempotent_union(self, tmp_path):
        a = LeaseTable(str(tmp_path), "s", owner="a")
        b = LeaseTable(str(tmp_path), "s", owner="b")
        a.register(["k1", "k2"])
        b.register(["k2", "k3"])  # first writer wins per key
        snap = a.snapshot()
        assert sorted(snap) == ["k1", "k2", "k3"]
        assert all(v["state"] == "pool" for v in snap.values())

    def test_acquire_complete_lifecycle(self, tmp_path):
        t = LeaseTable(str(tmp_path), "s", owner="h0", ttl_s=30.0)
        t.register(["k"])
        assert t.acquire("k") == "acquired"
        assert t.acquire("k") == "held"  # own live lease: idempotent
        assert t.snapshot()["k"]["attempts"] == 1  # held never re-counts
        assert t.complete("k") is True
        assert t.acquire("k") == "done"
        assert t.pending() == (0, float("inf"))

    def test_live_foreign_lease_is_busy(self, tmp_path):
        a = LeaseTable(str(tmp_path), "s", owner="a", ttl_s=30.0)
        b = LeaseTable(str(tmp_path), "s", owner="b", ttl_s=30.0)
        a.register(["k"])
        assert a.acquire("k") == "acquired"
        assert b.acquire("k") == "busy"
        n, expiry = b.pending()
        assert n == 1 and 0.0 < expiry <= 30.0

    def test_ttl_expiry_takeover_attempts(self, tmp_path):
        a = LeaseTable(str(tmp_path), "s", owner="a", ttl_s=0.05)
        b = LeaseTable(str(tmp_path), "s", owner="b", ttl_s=30.0)
        a.register(["k"])
        assert a.acquire("k") == "acquired"
        time.sleep(0.06)
        assert b.acquire("k") == "takeover"
        assert b.takeovers == 1
        snap = b.snapshot()["k"]
        assert snap["owner"] == "b" and snap["attempts"] == 2
        # the revoked owner's late renew/complete must NOT clobber b
        assert a.renew("k") is False
        assert a.complete("k") is False
        assert b.snapshot()["k"]["owner"] == "b"

    def test_failed_is_terminal_for_everyone(self, tmp_path):
        a = LeaseTable(str(tmp_path), "s", owner="a", ttl_s=30.0)
        b = LeaseTable(str(tmp_path), "s", owner="b", ttl_s=30.0)
        a.register(["k"])
        assert a.acquire("k") == "acquired"
        assert a.fail("k", "family exploded") is True
        assert b.acquire("k") == "failed"
        snap = b.snapshot()["k"]
        assert snap["state"] == "failed"
        assert "family exploded" in snap["error"]

    def test_claim_prefers_own_plan_slice(self, tmp_path):
        t = LeaseTable(str(tmp_path), "s", owner="h0")
        t.register(["a", "b", "c"])
        assert t.claim(prefer=["b"]) == "b"
        assert t.claim() == "a"  # sorted scan for the rest
        assert t.claim() == "c"
        assert t.claim() is None  # all leased-and-live


# --------------------------------------------------------------------- #
# cross-PROCESS coordination (two real interpreters, one store dir)     #
# --------------------------------------------------------------------- #

_CAS_CHILD = """
import sys
from transmogrifai_tpu.store.state import StateCell
cell = StateCell(sys.argv[1], "podcas")
for _ in range(int(sys.argv[2])):
    cell.update(lambda v: {"n": (v or {}).get("n", 0) + 1}, retries=2000)
"""

_VICTIM_CHILD = """
import os
import sys
from transmogrifai_tpu.store.state import LeaseTable
t = LeaseTable(sys.argv[1], "sweep", owner="victim", ttl_s=float(sys.argv[2]))
t.register(["blk"])
assert t.acquire("blk") == "acquired"
os._exit(9)  # die holding the lease: no release, no renewer
"""


class TestCrossProcess:
    def test_two_processes_cas_lose_nothing(self, tmp_path):
        """Two INTERPRETERS CAS-updating one cell through the shared
        directory lose no updates — the os.link publish is the only
        arbiter, there is no in-process lock to hide behind."""
        import subprocess
        import sys as _sys
        n_each = 20
        procs = [subprocess.Popen(
            [_sys.executable, "-c", _CAS_CHILD, str(tmp_path), str(n_each)])
            for _ in range(2)]
        for p in procs:
            assert p.wait(timeout=120) == 0
        assert StateCell(str(tmp_path), "podcas").read()[1] == \
            {"n": 2 * n_each}

    def test_killed_lease_holder_ttl_observed_by_survivor(self, tmp_path):
        """A holder killed mid-block (os._exit — no release, exactly a
        SIGKILLed host) leaves a live lease; a survivor in another
        process sees `busy` until the TTL runs out, then takes over
        with the attempt count recording the re-run."""
        import subprocess
        import sys as _sys
        ttl = 1.0
        p = subprocess.run(
            [_sys.executable, "-c", _VICTIM_CHILD, str(tmp_path), str(ttl)],
            timeout=120)
        assert p.returncode == 9  # died as scripted, lease still live
        survivor = LeaseTable(str(tmp_path), "sweep", owner="survivor",
                              ttl_s=ttl)
        snap = survivor.snapshot()["blk"]
        assert snap["state"] == "leased" and snap["owner"] == "victim"
        deadline = time.time() + 30.0
        status = survivor.acquire("blk")
        while status == "busy" and time.time() < deadline:
            _, expiry = survivor.pending()
            time.sleep(min(max(expiry, 0.01), 0.25))
            status = survivor.acquire("blk")
        assert status == "takeover"
        snap = survivor.snapshot()["blk"]
        assert snap["owner"] == "survivor" and snap["attempts"] == 2
        assert survivor.complete("blk") is True
        assert survivor.pending() == (0, float("inf"))
