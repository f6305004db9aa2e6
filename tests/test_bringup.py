"""PR 21 bring-up contracts: nothing between the user and the chip
keeps going without one, and nothing a run learns or builds leaks in
from outside the checkout."""

import inspect
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, **kw):
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else code_or_argv)
    return subprocess.run(argv, cwd=REPO, capture_output=True, text=True,
                          timeout=300, **kw)


# -- compile cache -------------------------------------------------------- #

def test_compile_cache_env_wins_and_no_code_path_sets_the_dir(monkeypatch):
    import jax

    from transmogrifai_tpu.serving.fleet import FleetConfig
    from transmogrifai_tpu.serving.service import ServingConfig
    from transmogrifai_tpu.utils import compile_cache as cc
    from transmogrifai_tpu.workflow.params import ServingParams

    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    monkeypatch.setattr(cc, "_dir", None)  # as in a fresh process
    try:
        assert cc.enable_compile_cache() == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
    # no in-code path is left to ignore: the function takes none, and
    # the config classes that used to feed it carry no directory field
    assert list(inspect.signature(cc.enable_compile_cache).parameters) \
        == ["min_compile_s"]
    for cls in (ServingConfig, ServingParams, FleetConfig):
        assert "compile_cache_dir" not in cls.__dataclass_fields__
    assert "TRANSMOGRIFAI_TPU_CACHE" not in open(cc.__file__).read()


def test_default_cache_and_store_are_fixed_paths_inside_the_checkout(
        tmp_path):
    from transmogrifai_tpu.store.config import DEFAULT_ROOT
    assert DEFAULT_ROOT == os.path.join(REPO, ".transmogrifai_store")
    default_dir = os.path.join(DEFAULT_ROOT, "xla-cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".transmogrifai_store/" in fh.read().split()
    # fresh processes, because the directory is chosen once per process
    # (conftest chose this one's) — and without either variable, which
    # the driver may have set: the DEFAULT is what is under test
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "TRANSMOGRIFAI_STORE_DIR")}
    script = (
        "import json, os, sys, jax\n"
        "from transmogrifai_tpu.utils.compile_cache import "
        "enable_compile_cache\n"
        "from transmogrifai_tpu.stages.base import StageRegistry\n"
        "from transmogrifai_tpu.workflow.serialization import "
        "_ensure_stage_library\n"
        "first = enable_compile_cache()\n"
        "os.environ['TRANSMOGRIFAI_STORE_DIR'] = sys.argv[1]\n"
        "again = enable_compile_cache()\n"
        "_ensure_stage_library()\n"
        "print(json.dumps({'first': first, 'again': again, 'cfg': "
        "jax.config.jax_compilation_cache_dir, 'sanity': "
        "StageRegistry.get('SanityCheckerModel').__name__}))\n")
    out = _run([sys.executable, "-c", script, str(tmp_path)], env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    # a store root that moves mid-process does not re-point a cache JAX
    # has already opened
    assert got["first"] == got["again"] == got["cfg"] == default_dir
    # a model-only process (cli serve) resolves every stage a default
    # transmogrify -> SanityChecker pipeline saves
    assert got["sanity"] == "SanityCheckerModel"
    # one rule for every kind of shared state: a process that STARTS
    # under a store root keeps its compile cache there
    out = _run([sys.executable, "-c", script, str(tmp_path / "later")],
               env={**env, "TRANSMOGRIFAI_STORE_DIR": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["first"] == got["again"] == got["cfg"] \
        == str(tmp_path / "xla-cache")


# -- bench.py: no fallback, no assumed peak, no exit 0 on failure --------- #

@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench as mod
    return mod


def test_probe_backend_raises_instead_of_switching_platform(
        bench, monkeypatch):
    import jax
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    monkeypatch.delenv("BENCH_SMOKE", raising=False)
    # full size on the CPU: fails, does not shrink the workload
    with pytest.raises(RuntimeError, match="accelerator"):
        bench.probe_backend()

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        bench.probe_backend()
    assert not [u for u in updates if u and u[0] == "jax_platforms"]


def test_peak_bandwidth_is_a_table_not_a_default(bench, monkeypatch):
    import jax
    assert bench._peak_hbm_bytes_per_s() is None  # CPU: no roofline

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert bench._peak_hbm_bytes_per_s() == 819e9
    Dev.device_kind = "TPU v99"
    with pytest.raises(RuntimeError, match="TPU v99"):
        bench._peak_hbm_bytes_per_s()
    assert "BENCH_PEAK_HBM_GBPS" not in open(bench.__file__).read()


def test_bench_failed_mode_exits_nonzero(bench, monkeypatch, capsys):
    def boom():
        raise ValueError("phase failed")
    monkeypatch.setitem(bench.MODES, "serve", boom)
    monkeypatch.setattr(sys, "argv", ["bench.py", "serve"])
    assert bench.main() == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["metric"] == "bench_error" and "phase failed" in last["error"]
    assert "os._exit" not in open(bench.__file__).read()


# -- sweep: retry narrowing ----------------------------------------------- #

def test_sweep_retry_policy_retries_only_declared_transients():
    from transmogrifai_tpu.selector.model_selector import ModelSelector
    policy = ModelSelector._sweep_retry_policy()
    JaxRuntimeError = type("JaxRuntimeError", (RuntimeError,), {})
    assert not policy.is_transient(JaxRuntimeError("INTERNAL: compile"))
    assert not policy.is_transient(OSError("io"))
    flagged = JaxRuntimeError("dropped")
    flagged.transient = True
    assert policy.is_transient(flagged)


# -- native kernels: built from the committed source, keyed by its hash --- #

@pytest.mark.skipif(shutil.which("cc") is None, reason="no C compiler")
def test_native_build_follows_the_source_hash_not_mtime(tmp_path,
                                                        monkeypatch):
    import ctypes

    from transmogrifai_tpu.native import build
    monkeypatch.setattr(build, "_DIR", str(tmp_path))
    monkeypatch.setattr(build, "_libs", {})
    src = tmp_path / "answer.c"
    sig = [("answer", [], ctypes.c_int)]

    src.write_text("int answer(void) { return 1; }\n")
    assert build._load("answer", sig).answer() == 1
    (old,) = tmp_path.glob("_answer-*.so")
    # a library NEWER than the source must still be rebuilt when the
    # source changes (an mtime comparison would keep the stale one)
    future = time.time() + 3600
    os.utime(old, (future, future))
    src.write_text("int answer(void) { return 2; }\n")
    build._libs.clear()
    assert build._load("answer", sig).answer() == 2
    (new,) = tmp_path.glob("_answer-*.so")
    assert new != old and not old.exists()


# -- chip_smoke.py --------------------------------------------------------- #

def test_chip_smoke_parent_stays_off_jax():
    out = _run("import sys, chip_smoke\n"
               "bad = [m for m in ('jax', 'jaxlib', 'numpy', "
               "'transmogrifai_tpu') if m in sys.modules]\n"
               "assert not bad, bad\n")
    assert out.returncode == 0, out.stderr[-2000:]


def test_chip_smoke_fails_without_a_tpu_and_prints_no_result(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = _run([sys.executable, "chip_smoke.py"], env=env)
    assert out.returncode != 0
    assert "no TPU" in out.stdout
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    # and in a directory that holds the script and nothing else
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    alone = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                           capture_output=True, text=True, timeout=60)
    assert alone.returncode != 0
    assert not [ln for ln in alone.stdout.splitlines()
                if ln.startswith("{")]


def test_chip_smoke_failed_phase_is_a_nonzero_exit(monkeypatch, capsys):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    def failing_child(self, name):
        raise chip_smoke.PhaseFailed(f"{name}: made to fail")
    monkeypatch.setattr(chip_smoke.Parent, "jax_child", failing_child)
    assert chip_smoke.Parent(rehearsal=True).run() == 1
    out = capsys.readouterr().out
    assert "chip_smoke FAILED: PhaseFailed: train_score: made to fail" in out
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_chip_smoke_pass_ends_with_exactly_ok_and_device(
        monkeypatch, capsys, tmp_path):
    """The chip check reads the LAST stdout line of a pass: an object with
    exactly `ok` and `device`, device with exactly platform/kind/count.
    The per-phase summary (ending `"claim": null`) is the line before."""
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "HERE", str(tmp_path))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    facts = ("train_wall_s", "train_breakdown", "train_xla", "n_fits",
             "best", "holdout_aupr", "score_cold_s", "score_warm_s",
             "fused_vs_unfused", "stream_vs_fused", "bytes_limit", "native",
             "compile_cache", "store_at_start")
    monkeypatch.setattr(
        chip_smoke.Parent, "jax_child",
        lambda self, name: {"device": dict(device), **dict.fromkeys(facts)})
    monkeypatch.setattr(chip_smoke.Parent, "serve", lambda self: {})
    assert chip_smoke.Parent(rehearsal=False).run() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    summary = json.loads(lines[-2])
    assert summary["claim"] is None and "ok" not in summary
    assert summary["phases"]["mesh"] == "skipped: 1 device"
