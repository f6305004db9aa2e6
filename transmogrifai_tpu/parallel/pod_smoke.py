"""pod-smoke: multi-HOST sweep execution over the shared lease table.

The CI gate for the pod tier (`make pod-smoke`) — and the measured half
of `python bench.py pod`. Where `parallel/smoke.py` proves the
single-host work-stealing scheduler on one process's forced host mesh,
this module launches REAL separate scheduler processes (one per "host")
against one shared store dir and proves the cross-host contracts:

1. **bit-identical winner**: a 2-host pod sweep (each host a fresh
   process on a forced >1-slice `make_multislice_mesh` host mesh,
   claim-racing blocks through the `store.state.LeaseTable`) must
   reproduce the single-host scheduled sweep's metric matrix exactly
   (JSON-string equality) on EVERY host — each host's own rows plus the
   other host's rows merged from the host-qualified journal shards;
2. **kill-one-host TTL reclaim**: a host killed (InjectedKill) while
   holding a block lease stops renewing; the survivor process observes
   the TTL expiry, takes the block over, and finishes the sweep with
   EXACTLY the dead host's in-flight block re-run — asserted from the
   per-host journal shard record counts AND the lease table's per-block
   attempt counters;
3. **measurement**: single-host vs 2-host wall clock (speedup) + the
   fleet-wide ``mesh_utilization_frac`` from rolling each host's
   `GoodputReport.mesh` through `obs.goodput.fleet_mesh_rollup`.

The parent process never initializes JAX — it orchestrates child
processes (``--child``), reads their JSON payloads, and inspects the
shared store. Run: ``python -m transmogrifai_tpu.parallel.pod_smoke``.

This is a CPU test tool: every child forces its own CPU host mesh, and
several children run at once. It says nothing about a chip and must not
be pointed at one (a chip belongs to one process at a time; the chip
check is ``python chip_smoke.py``).
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

# smoke-scale workload: 4 LR max_iter groups + 1 SVC group = 5 blocks
# of 2 configs each (see parallel/smoke.py `_selector`)
SMOKE_MAX_ITERS = (8, 4, 6, 3)
SMOKE_ROWS = 240
SMOKE_WORKERS = int(os.environ.get("TRANSMOGRIFAI_POD_SMOKE_WORKERS", "2"))
KILL_TTL_S = 2.0


def _barrier(path: str, host: str, n: int, timeout_s: float = 180.0) -> None:
    """File-based start barrier: every host touches its marker, then
    polls (capped exponential backoff, deadline-bounded) until all `n`
    markers exist — so the speedup measurement times hosts that really
    ran concurrently, not a staggered pipeline."""
    os.makedirs(path, exist_ok=True)
    open(os.path.join(path, f"{host}.ready"), "w").close()
    deadline = time.monotonic() + timeout_s
    delay = 0.01
    while time.monotonic() < deadline:
        if len(glob.glob(os.path.join(path, "*.ready"))) >= n:
            return
        time.sleep(delay)
        delay = min(delay * 1.5, 0.25)
    raise TimeoutError(f"pod barrier: {host} waited {timeout_s}s for "
                       f"{n} hosts at {path}")


def _shard_records(ckpt_dir: str, host: Optional[str] = None) -> int:
    """Journal records across the shared store's shard files — scoped to
    one host's ``-w<host>_<lane>.jsonl`` shards when `host` is given."""
    pat = f"*.journal-w{host}_*.jsonl" if host else "*.journal-w*.jsonl"
    n = 0
    for p in glob.glob(os.path.join(ckpt_dir, pat)):
        with open(p) as fh:
            n += max(0, sum(1 for _ in fh) - 1)  # minus header
    return n


# -- child process ------------------------------------------------------------ #

def _child(cfg: Dict[str, Any]) -> int:
    """One pod host: forced host devices, a >1-slice mesh, and the
    env-gated `HostScheduler` path through the selector. Prints one
    JSON payload line."""
    from transmogrifai_tpu.parallel.smoke import ensure_host_devices
    ensure_host_devices(8)

    os.environ["TRANSMOGRIFAI_POD_STORE"] = cfg["store"]
    os.environ["TRANSMOGRIFAI_POD_HOST"] = cfg["host"]
    os.environ["TRANSMOGRIFAI_POD_SWEEP"] = cfg["sweep"]
    os.environ["TRANSMOGRIFAI_POD_WORKERS"] = str(cfg["workers"])
    os.environ["TRANSMOGRIFAI_POD_TTL_S"] = str(cfg["ttl_s"])

    from transmogrifai_tpu.obs import goodput as obs_goodput
    from transmogrifai_tpu.obs.trace import TRACER
    from transmogrifai_tpu.parallel.mesh import make_multislice_mesh
    from transmogrifai_tpu.parallel.smoke import _cols, _fit, _rows, _selector

    max_iters = tuple(cfg["max_iters"])
    n_rows = int(cfg["n_rows"])
    cols = _cols(n_rows)
    # slice boundaries on the sweep axis: lanes are whole slices' rows,
    # so block execution stays inside a slice (ICI) and only the lease
    # table + journal shards cross hosts (DCN)
    mesh = make_multislice_mesh(2, data_per_slice=1)
    n_slices = 2

    # warm this process's compile caches off the clock (throwaway
    # trace: its mesh_utilization event must not leak into the measured
    # rollup), without touching the shared store
    with TRACER.span("run:pod-warmup", category="run", new_trace=True):
        _fit(_selector(max_iters=max_iters), cols, n_rows)

    if cfg.get("kill_at"):
        from transmogrifai_tpu.runtime.faults import (
            SITE_WORKER_BLOCK, FaultPlan, FaultSpec, InjectedKill)
        plan = FaultPlan([FaultSpec(SITE_WORKER_BLOCK,
                                    at=int(cfg["kill_at"]), kind="kill")])
        killed = False
        try:
            with plan.active():
                _fit(_selector(cfg["ckpt"], max_iters=max_iters),
                     cols, n_rows, mesh=mesh)
        except InjectedKill:
            killed = True
        print(json.dumps({"host": cfg["host"], "killed": killed}))
        return 0

    if cfg.get("barrier"):
        _barrier(cfg["barrier"], cfg["host"], int(cfg["hosts"]))
    with TRACER.span("run:pod-bench", category="run",
                     new_trace=True) as root:
        t0 = time.perf_counter()
        sweep = _rows(_fit(_selector(cfg["ckpt"], max_iters=max_iters),
                           cols, n_rows, mesh=mesh))
        t_fit = time.perf_counter() - t0
    report = obs_goodput.build_report(
        root, TRACER.trace_spans(root.trace_id))
    print(json.dumps({
        "host": cfg["host"], "t_fit_s": round(t_fit, 3),
        "n_slices": n_slices, "workers": int(cfg["workers"]),
        "n_results": len(sweep["rows"]),
        "winner": json.dumps(sweep, sort_keys=True),
        "mesh": report.mesh,
    }))
    return 0


def _spawn(cfg: Dict[str, Any], extra_env: Dict[str, str]):
    env = dict(os.environ)
    env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, "-m", "transmogrifai_tpu.parallel.pod_smoke",
         "--child", json.dumps(cfg)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _finish(proc) -> Dict[str, Any]:
    out, err = proc.communicate(timeout=900)
    payload = None
    for line in out.splitlines():
        if line.startswith("{"):
            payload = json.loads(line)
    if proc.returncode != 0 or payload is None:
        raise RuntimeError(
            f"pod child failed (rc={proc.returncode}):\n{out}\n{err}")
    return payload


# -- parent: measured run ----------------------------------------------------- #

def run_pod(n_hosts: int = 2, workers: int = SMOKE_WORKERS,
            max_iters=SMOKE_MAX_ITERS, n_rows: int = SMOKE_ROWS,
            ttl_s: float = 30.0) -> Dict[str, Any]:
    """Single-host baseline vs `n_hosts` concurrent host processes on
    one shared store: winner parity + measured speedup + the fleet
    mesh-utilization rollup. Shared by the smoke gate and `bench.py
    pod` (which passes more blocks so the packing measurement is not
    dominated by per-process startup)."""
    from transmogrifai_tpu.obs.goodput import fleet_mesh_rollup

    with tempfile.TemporaryDirectory(prefix="pod-smoke-") as tmp:
        store = os.path.join(tmp, "store")
        corpus = os.path.join(tmp, "corpus")
        base_cfg = {"workers": workers, "max_iters": list(max_iters),
                    "n_rows": n_rows, "ttl_s": ttl_s}

        # baseline: ONE host process over its own store — same lane
        # count, same scheduler, so the speedup isolates what the extra
        # hosts add (the fleet perf corpus stays shared: per-replica
        # shards, merged reads)
        single = _finish(_spawn(
            {**base_cfg, "host": "base", "store": store, "sweep": "base",
             "ckpt": os.path.join(tmp, "ckpt-base")},
            {"TRANSMOGRIFAI_PERF_CORPUS_DIR": corpus,
             "TRANSMOGRIFAI_PERF_REPLICA": "base",
             "TRANSMOGRIFAI_PERF_MODEL": "1"}))
        n_cfgs = single["n_results"]
        assert n_cfgs == 2 * (len(max_iters) + 1), single

        ckpt = os.path.join(tmp, "ckpt-pod")
        barrier = os.path.join(tmp, "barrier")
        procs = [_spawn(
            {**base_cfg, "host": f"h{i}", "store": store, "sweep": "pod",
             "ckpt": ckpt, "barrier": barrier, "hosts": n_hosts},
            {"TRANSMOGRIFAI_PERF_CORPUS_DIR": corpus,
             "TRANSMOGRIFAI_PERF_REPLICA": f"h{i}",
             "TRANSMOGRIFAI_PERF_MODEL": "1"})
            for i in range(n_hosts)]
        hosts = [_finish(p) for p in procs]

        for h in hosts:
            assert h["n_results"] == n_cfgs, h
            assert h["winner"] == single["winner"], (
                f"host {h['host']} winner diverged from single-host")
        fleet = fleet_mesh_rollup([h["mesh"] for h in hosts])
        t_single = float(single["t_fit_s"])
        t_pod = max(float(h["t_fit_s"]) for h in hosts)
        blocks = int(fleet.get("blocks", 0))
        # host_cpus contextualizes the MEASURED speedup: n_hosts fresh
        # interpreters time-slicing fewer cores than hosts cannot beat
        # one process, so a sub-1 number on a starved box is the honest
        # reading, not a scheduler defect (winner parity + lease
        # arithmetic above are the correctness gates either way).
        return {
            "n_hosts": n_hosts, "workers_per_host": workers,
            "n_slices_per_host": hosts[0]["n_slices"],
            "blocks": blocks,
            "host_cpus": os.cpu_count() or 1,
            "sweep_single_host_measured_s": round(t_single, 3),
            f"sweep_pod{n_hosts}_measured_s": round(t_pod, 3),
            "pod_speedup": round(t_single / max(t_pod, 1e-9), 3),
            "fleet_mesh_utilization_frac":
                fleet["mesh_utilization_frac"],
            "fleet_mesh": fleet,
            "winner_exact": True,
        }


def _smoke_kill_host(payload: Dict[str, Any]) -> None:
    """Kill host `killer` (1 lane) at its SECOND block claim: block 1 is
    journaled + done, block 2 dies leased. The survivor — a fresh
    process started after the death — must see the lease TTL-expire,
    take over, and finish with exactly that one block re-run."""
    from transmogrifai_tpu.store.state import LeaseTable

    n_blocks, cfg_per_block = 5, 2
    total_cfgs = n_blocks * cfg_per_block
    with tempfile.TemporaryDirectory(prefix="pod-kill-") as tmp:
        store = os.path.join(tmp, "store")
        ckpt = os.path.join(tmp, "ckpt")
        base_cfg = {"store": store, "sweep": "kill", "ckpt": ckpt,
                    "max_iters": list(SMOKE_MAX_ITERS),
                    "n_rows": SMOKE_ROWS, "ttl_s": KILL_TTL_S}
        # cold cost model on BOTH hosts: the block arithmetic below
        # assumes count-LPT plans (no model-driven splits)
        env = {"TRANSMOGRIFAI_PERF_MODEL": "0"}

        killer = _finish(_spawn(
            {**base_cfg, "host": "killer", "workers": 1, "kill_at": 2},
            env))
        assert killer["killed"], "fault plan failed to kill the host"
        at_kill = _shard_records(ckpt)
        assert at_kill == cfg_per_block, (
            f"killed host should have journaled exactly its first "
            f"block: {at_kill}/{total_cfgs} configs")

        survivor = _finish(_spawn(
            {**base_cfg, "host": "survivor", "workers": SMOKE_WORKERS},
            env))
        assert survivor["n_results"] == total_cfgs, survivor
        rerun = _shard_records(ckpt) - at_kill
        assert rerun == total_cfgs - cfg_per_block, (
            f"survivor re-ran {rerun} configs, expected exactly the "
            f"{total_cfgs - cfg_per_block} the dead host never "
            "journaled (its in-flight block + its queue)")
        # lease-table forensics: exactly ONE block needed a second
        # attempt (the TTL takeover of the dead host's in-flight lease)
        snap = LeaseTable(store, "kill", owner="audit").snapshot()
        assert len(snap) == n_blocks, snap
        attempts = sorted(b["attempts"] for b in snap.values())
        assert attempts == [1] * (n_blocks - 1) + [2], attempts
        taken = [k for k, b in snap.items() if b["attempts"] == 2]
        assert all(b["state"] == "done" for b in snap.values()), snap
        payload.update(
            kill_ttl_reclaim="ok", blocks_journaled_at_kill=1,
            blocks_taken_over=len(taken),
            lease_ttl_s=KILL_TTL_S)


def _smoke() -> int:
    payload: Dict[str, Any] = {}
    payload.update(run_pod())
    _smoke_kill_host(payload)
    print(json.dumps({"pod_smoke": "ok", **payload}))
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(_child(json.loads(sys.argv[sys.argv.index("--child") + 1])))
    sys.exit(_smoke())
