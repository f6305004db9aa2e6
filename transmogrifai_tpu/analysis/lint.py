"""AST-based JAX-pitfall linter over stage/kernel source.

Where `analysis/opcheck.py` validates a WIRED graph, this pass reads the
source of the stages themselves for the pitfalls that only show up as
silent slowness or nondeterminism once XLA is in the loop:

- ``L001 numpy-in-device``: ``np.``/``numpy.`` use inside a jittable
  stage's ``device_apply``/``device_apply_with`` body. Host numpy inside
  a traced function either breaks the trace or (worse) silently constant-
  folds per compile. Whitelisted: pure constants and dtype names
  (``np.inf``, ``np.pi``, ``np.float32``, ...).
- ``L002 traced-branch``: Python ``if``/``while`` (or ternary) testing a
  traced value inside a device body — a branch on the ``dev``/``enc``
  parameters or a value subscripted out of them. Under ``jax.jit`` this
  raises a ConcretizationTypeError or, with weak typing, silently bakes
  one branch into the compiled program. Testing the *container* itself
  (``if enc:``) is static and allowed.
- ``L003 unhashable-static``: a parameter listed in ``static_argnames``
  whose default value is a mutable literal (list/dict/set) — unhashable
  statics fail at call time, and mutable defaults silently share state
  between traces.
- ``L004 nondeterminism-in-fit``: wall-clock or global-RNG calls
  (``time.time``, ``datetime.now``, ``np.random.rand``, seedless
  ``default_rng()``, ``random.random``, ``uuid.uuid4``) inside ``fit``/
  ``fit_model``/``device_apply`` bodies. Fits must be replayable from
  the FitContext seed.
- ``L005 host-prepare-device-input``: ``host_prepare`` subscripting an
  input column whose declared ``in_types`` kind is device
  (scalar/vector/prediction) — the compiled scorer passes None for
  device-kind columns on the host phase, so that read crashes or
  silently degrades (the contract documented in stages/base.py).
- ``L006 fixed-batch-dim``: a ``reshape``/``broadcast_to`` inside a
  device body whose LEADING target dim is an int literal > 1. The
  serving batcher pads batches to a LADDER of bucket sizes and the
  streaming tail re-pads to the warm shape, so device code that bakes a
  specific leading batch dim into a shape is wrong the moment a
  different bucket arrives — derive it from ``x.shape[0]`` (or use
  ``-1``) instead.
- ``L007 serial-ingest``: a per-iteration ``jnp.asarray``/``jnp.array``/
  ``jax.device_put`` inside a Python ``for`` loop that iterates a chunk
  stream (an ``iter_chunks(...)``/``stream(...)`` call, or a plain
  ``chunks``/``batches`` iterable). One synchronous host→device
  transfer per loop body serializes host prep against the wire,
  and an un-depth-bounded ``device_put`` loop also lets dispatch run
  arbitrarily far ahead of real transfer, breaking deadline math.
  Route bulk uploads through ``data/pipeline.run_chunk_pipeline``
  (worker prepare + bounded-depth overlapped writes) instead.
- ``L008 unbounded-fault-handling``: the two anti-patterns the
  ``runtime/`` fault-tolerance layer replaces. (a) a broad swallow —
  bare ``except:`` / ``except Exception:`` whose body is ONLY
  ``pass``/``continue``/``...`` — hides the failure entirely: either
  narrow the exception type, handle it (even a ``log.debug`` with
  ``exc_info`` counts: the failure stays observable), or let it
  propagate into a ``runtime.retry.RetryPolicy``. (b) an unbounded
  ``while True`` retry loop — a handler inside the loop that neither
  re-raises, ``break``s, nor ``return``s, so a PERSISTENT error spins
  forever; bound it with ``RetryPolicy`` (attempts + backoff +
  transient classification) instead.

- ``L009 wallclock-duration``: subtraction arithmetic on a
  ``time.time()`` call — the wall-clock-for-durations bug. An NTP step
  or suspend/resume silently corrupts any interval measured as
  ``time.time() - t0`` (negative phase timings, goodput buckets that
  exceed wall time); use ``time.perf_counter()`` (or
  ``time.monotonic()`` for deadlines). Bare ``time.time()`` reads
  stay legal: an epoch TIMESTAMP (``started_at``, log stamps) is what
  the wall clock is for.

- ``L010 uncached-rebuild``: two or more device-matrix builder calls
  (``device_matrix`` / ``device_binned`` / ``dual_device_matrices``)
  on the SAME store variable inside one function scope with none of
  them carrying a ``cache=`` policy. Each uncached call re-streams the
  whole store host→device — at 10M×500 a ~10 GB wire per repeat —
  while the content-addressed feature cache
  (`data/feature_cache.py`) replays the wire artifact with zero store
  reads. Pass ``cache=`` (a policy string or `FeatureCacheParams`) so
  the rebuild is a deliberate choice, not an accident.

- ``L011 per-device-dispatch``: the two host-in-the-loop multichip
  anti-patterns. (a) a Python ``for`` loop over the device list
  (``jax.devices()`` / ``jax.local_devices()`` / a ``devices``
  iterable) doing per-device ``device_put``/``jnp.asarray`` — one
  synchronous transfer per chip serializes what a single
  ``device_put(x, NamedSharding(mesh, spec))`` ships as one sharded
  placement (and the scheduler in `parallel/scheduler.py` exists so
  per-worker placement happens once per lane, not per dispatch).
  (b) a host callback (``jax.pure_callback`` / ``io_callback`` /
  ``jax.debug.callback`` / ``host_callback.call``) inside a function
  wrapped by ``shard_map``/``pjit`` — every shard's execution stalls
  on a host round-trip per step, turning an SPMD program into a
  host-bound serial one; move the host work outside the mapped
  computation (or into the scheduler's host-side worker loop).

- ``L012 legacy-global-rng``: any call through numpy's module-level
  legacy RNG surface (``np.random.rand`` / ``randn`` / ``normal`` /
  ``seed`` / ``shuffle`` / ...), or a seedless
  ``np.random.default_rng()``, ANYWHERE outside ``testkit/`` — not just
  inside fit bodies (that narrower case is L004). The module-level
  functions share ONE hidden global ``RandomState``: any import-order
  or thread-interleaving change silently reorders every draw, so drift
  sampling, refit shuffling, and journal-resumed continual cycles stop
  replaying deterministically. Use a seeded
  ``np.random.default_rng(seed)`` ``Generator`` instead (`testkit/` is
  exempt: test fixtures own their processes).

- ``L013 magic-knob``: a NEW module-level hand-set tuning knob — an
  ALL-CAPS constant whose name says it tunes throughput
  (``WORKERS``/``DEPTH``/``QUEUE``/``BATCH``/``WAIT``/``TIMEOUT``/
  ``BUDGET``/``TARGET``/``RETRIES``/``WIDTH``/``CHUNK``/``THREADS``)
  assigned a bare numeric literal in a ``data/``/``parallel/``/
  ``serving/`` hot path. The learned cost model (`perf/`) exists so
  these decisions come from measurements through the params/env
  plumbing; a fresh ``WORKERS = 4`` bypasses both and fossilizes one
  machine's guess. The documented env-tunable sites that predate the
  model are allowlisted (`_L013_ALLOW`); everything new must route
  through `PerfModelParams`/`OpParams`/an env knob instead.

- ``L014 per-request-service``: a ``ScoringService``/``FleetService``
  (or ``.from_path``) constructed inside a LOOP body or an HTTP
  request-handler method (``do_GET``/``do_POST``/``handle*``).
  Constructing a service is the expensive path by design — model load,
  compiled-scorer build, AOT warmup of every bucket, shared-program
  registration — so a per-request or per-iteration construction defeats
  the warmup AND the fleet's shared-program registry (every instance
  re-traces its own programs instead of adopting the resident ones).
  Construct once, `start()`, and route requests through it.

- ``L015 unnamed-thread``: a ``threading.Thread(...)`` constructed in
  package code (outside ``testkit/``/tests) without a ``name=``. The
  serving watchdog, hang diagnostics, and span attribution all key off
  thread names — an anonymous ``Thread-23`` in a stack dump or trace
  is unattributable exactly when a wedged scoring loop or supervisor
  needs diagnosing. Name every long-lived OR short-lived thread for
  what it does (``scoring-batcher-1``, ``fleet-watchdog``,
  ``continual-loop``).

- ``L016 closure-constant-array``: a ``device_apply``/``predict_arrays``
  body converting ``self.<attr>`` to a device array
  (``jnp.asarray(self.W)``) in a class WITHOUT ``device_constants()``.
  The converted array is a closure constant of the compiled scoring
  program: megabyte-scale fitted state gets value-baked into the XLA
  executable (every fleet tenant then compiles its own bucket programs
  instead of sharing one). Route fitted arrays through
  ``device_constants()``/``device_apply_with`` — the known-small
  scalar/index sites are allowlisted in ``_L016_ALLOW``.

- ``L017 dynamic-event-name``: a span/event NAME built with an f-string
  or ``+`` concatenation at a tracing call site (``record_event`` /
  ``emit_event`` / ``add_event`` / ``.span(...)`` / ``.event(...)`` /
  ``.child(...)``). Event and span names are CARDINALITY keys: the
  flight-recorder ring, the goodput rollup's by-name buckets, and any
  Prometheus series derived from them all assume a small closed name
  set — a name interpolating a request id, tenant, or path mints
  unbounded distinct names and quietly breaks all three. Put the
  variable part in an ATTRIBUTE (``record_event("cache_hit",
  key=key)``), not the name. Bounded-by-construction dynamic names
  (worker lanes, run types, site labels) are allowlisted by their
  literal prefix in ``_L017_ALLOW_PREFIXES``.

- ``L018 per-row-serving-loop``: a Python ``for`` statement iterating a
  rows-shaped iterable (``rows`` / ``*_rows``) inside a serving
  hot-path function (name containing ``score``/``assemble``/``demux``/
  ``parse`` in a ``serving/`` module). The compiled row codec
  (`data/rowcodec.py`, allowlisted) exists precisely so the serving
  data plane never pays per-row Python — a fresh ``for r in rows:``
  dict loop on the request path reintroduces the parse cost PR 15
  removed (the pre-codec loop dominated the serving p50). Route rows
  through ``rowcodec.encode_rows``/``Dataset.from_rows`` (codec-backed)
  or operate on columns.

- ``L019 blocking-under-lock``: ``time.sleep`` or blocking file I/O
  (``open`` / ``os.makedirs`` / ``os.replace`` / ``os.fsync`` /
  ``Path.write_text``-family / ``json.dump`` / ``pickle.dump``)
  lexically inside a ``with <lock>:`` block. A lock's critical section
  prices every contender: one slow disk under ``self._lock`` stalls
  every thread that touches that lock — the serving watchdog reads this
  as a stall and restarts a healthy worker. Stage the data under the
  lock, do the I/O after release (see
  ``serving/resilience.py:_flush_flight_dumps`` for the pattern).
  Deliberately serialized writers (WAL appends, append-only logs) annotate
  the site ``# conc-ok: C003`` / ``# conc-ok: L019`` — the same escape
  hatch the whole-program auditor (``analysis/concurrency.py``, which
  also sees lock-holding CALLERS of the I/O) honors, so one annotation
  satisfies both tools. Smoke/chaos drivers and tests are allowlisted.

- ``L020 store-bypass-write``: a direct write (``open(..., "w")`` /
  ``np.save``/``np.savez`` / ``Path.write_text``-family) whose path
  expression is built from an artifact-store location (a call to
  ``path_of``/``default_cache_dir``/``cache_root``/``resolve_dir``/
  ``resolved_dir``/``resolved_corpus_dir``, or a ``cache_dir``/
  ``store_dir``/``artifact_dir`` variable). Artifacts in those
  namespaces carry sha256 manifests written LAST by
  ``store.ArtifactStore.put``/``seal_and_commit`` — a bypass write
  either lands an unverifiable file (readers reject the artifact) or
  mutates a sealed one (checksum mismatch on next load). Stage files
  and commit through the store; deliberate sidecars (access clocks,
  append-only shard logs, the store internals themselves) annotate the
  site ``# store-ok: <why>``. ``store/artifact.py``, smoke drivers and
  tests are allowlisted.

- ``L021 blind-poll-loop``: a constant-argument ``time.sleep`` lexically
  inside a ``while`` loop. A fixed-delay poll is wrong at both ends of
  the distribution — too fast, and K replicas hammering one shared cell
  (a ``store.state`` lease table, a journal dir, a readiness file) turn
  the store into a CAS storm that scales with fleet size; too slow, and
  a cross-host handoff (lease expiry, barrier release) eats the full
  period as idle wall time. Poll loops must derive their delay from the
  thing they wait on — a TTL/deadline (``min(next_expiry, ttl)``, the
  scheduler's ``_pod_takeover``), capped exponential backoff
  (``StateCell.update``), or an ``Event.wait(timeout=...)``/
  ``Condition.wait(timeout=...)`` that a writer can wake early.
  Computed delays pass by construction (only literal constants flag);
  a deliberate fixed-cadence loop annotates the site
  ``# conc-ok: L021``. Smoke/chaos drivers and tests are allowlisted.

- ``L022 unlogged-actuation``: a call to a serving actuation API
  (``rebucket``/``rearm_auto_rebucket``/``set_pressure``/
  ``set_fidelity_route``/``set_route_override``) outside the autopilot
  controller from a function that never emits a flight-recorder event
  (no ``record_event``/``request_dump`` call in scope). The serving
  control loop's audit trail is the flight recorder: every route flip,
  admission-threshold write, or ladder re-derivation must name the
  burn window/prediction (or the operator action) that justified it,
  or a post-incident dump cannot explain why traffic moved. Emit an
  event beside the call, or annotate a deliberate silent site with
  ``# autopilot-ok: <why>``. ``serving/autopilot.py``, smoke/chaos
  drivers and tests are allowlisted.

- ``L023 dropped-trace-context``: a span-opening or event-emission call
  (``TRACER.span``/``Span``/``RequestTrace``/``record_event``/
  ``emit_event``/``add_event``) in ``serving/``/``parallel/``/
  ``continual/`` that passes a MANUAL trace id — a string literal,
  f-string, concatenation, or a fresh ``new_run_id()``/
  ``new_trace_id()``/``uuid*`` — instead of joining the ambient
  contextvar parent. A hand-built trace id severs the cross-process
  stitch: the span lands in the trace shard under an id
  ``merge_fleet_trace`` will never be asked for, and the request's
  remote leg goes missing from the merged timeline. Join the current
  trace (omit ``trace_id``; pass a ``TraceContext``/parent span;
  ``new_trace=True`` roots deliberately), or annotate
  ``# trace-ok: <why>``. Smoke/chaos drivers and tests are
  allowlisted.

Classes that set ``jittable = False`` in their body are exempt from
L001/L002 (their device_apply runs eagerly on host, where numpy and
Python control flow are legal).

Run: ``python -m transmogrifai_tpu.lint <paths...>`` — exit 1 on GATING
findings (error-severity, unsuppressed); files that fail to parse are
reported as L000 warnings and do not gate. ``--json`` emits the same
envelope ``analysis/concurrency.py`` uses (file/line/rule/severity/
suppression). Also available via the ``lint`` subcommand of
``transmogrifai_tpu.cli``.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

_DEVICE_FNS = ("device_apply", "device_apply_with")
_FIT_FNS = ("fit", "fit_model", "fit_arrays") + _DEVICE_FNS

_NP_CONST_WHITELIST = {
    "pi", "e", "inf", "nan", "newaxis", "euler_gamma",
    "float16", "float32", "float64", "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64", "bool_", "bfloat16",
    "finfo", "iinfo",
}

# exact dotted names only: `random.x` must not match jax.random.x /
# np.random.x (keyed jax RNG is deterministic; np.random handled apart)
_NONDET_EXACT = {
    "random.random", "random.randint", "random.choice", "random.shuffle",
    "random.uniform", "random.randrange", "random.sample",
    "uuid.uuid4", "uuid.uuid1",
}
# suffix-matched (module aliases like `dt.datetime.now` still resolve)
_NONDET_SUFFIX = {
    "time.time", "time.time_ns",
    "datetime.now", "datetime.utcnow", "datetime.today", "date.today",
}
_NONDET_NP_RANDOM = {
    "rand", "randn", "randint", "random", "choice", "shuffle",
    "permutation", "uniform", "normal", "random_sample",
}

# L012: the full module-level legacy-RNG surface (shared hidden global
# RandomState) — everything L004 flags plus state management and the
# distribution samplers continual refit/drift code reaches for
_LEGACY_NP_RANDOM = _NONDET_NP_RANDOM | {
    "seed", "get_state", "set_state", "standard_normal", "sample",
    "exponential", "poisson", "beta", "gamma", "binomial", "multinomial",
    "bytes", "lognormal", "geometric",
}


def _rng_seedless(call: ast.Call) -> bool:
    """True when a `default_rng(...)` call visibly seeds from OS
    entropy: no args at all, or a LITERAL None seed (positional or
    `seed=None` — both are spelled-out nondeterminism). A `**kwargs`
    splat is statically unknowable and given the benefit of the
    doubt."""
    if call.args:
        a = call.args[0]
        return isinstance(a, ast.Constant) and a.value is None
    for kw in call.keywords:
        if kw.arg is None:      # **splat: unknowable, trusted
            return False
        if kw.arg == "seed":
            return isinstance(kw.value, ast.Constant) and \
                kw.value.value is None
    return True

_DEVICE_KINDS = ("scalar", "vector", "prediction")

# L007: chunk-stream iterators (call names / bare iterable names) and the
# per-iteration host→device transfer calls that serialize against them
_INGEST_ITER_CALLS = {"iter_chunks", "stream"}
_INGEST_ITER_NAMES = {"chunks", "batches"}
_SERIAL_UPLOAD_CALLS = {"jnp.asarray", "jnp.array", "jax.numpy.asarray",
                        "jax.numpy.array", "jax.device_put", "device_put"}

# L010: the out-of-core device-matrix builders the feature cache fronts
_MATRIX_BUILDER_CALLS = {"device_matrix", "device_binned",
                         "dual_device_matrices"}

# L011: device-list iterables (calls or bare names) and SPMD wrappers
_DEVICE_ITER_CALLS = {"devices", "local_devices"}
_DEVICE_ITER_NAMES = {"devices", "local_devices", "mesh_devices"}
_SPMD_WRAPPERS = {"shard_map", "pjit"}
# exact-suffix host-callback forms (a bare `.callback` method must not
# false-positive, so `callback` only matches under the jax.debug module)
_HOST_CALLBACK_LAST = {"pure_callback", "io_callback"}
_HOST_CALLBACK_DOTTED_SUFFIX = ("debug.callback", "host_callback.call")


@dataclass
class LintFinding:
    path: str
    line: int
    code: str
    message: str
    # "error" findings gate CI; "warning" (parse-skipped files) are
    # reported but never fail the run. `suppression` names the mechanism
    # ("annotation") when an escape hatch silenced an error finding.
    severity: str = "error"
    suppression: Optional[str] = None

    @property
    def gating(self) -> bool:
        return self.severity == "error" and self.suppression is None

    def __str__(self) -> str:
        s = f"{self.path}:{self.line}: {self.code} {self.message}"
        if self.suppression is not None:
            s += f" [suppressed: {self.suppression}]"
        elif self.severity != "error":
            s += f" [{self.severity}]"
        return s


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for an Attribute/Name chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_mutable_literal(node: ast.AST) -> bool:
    return isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                             ast.DictComp, ast.SetComp))


def _own_jittable(cls: ast.ClassDef) -> Optional[bool]:
    """The class body's own `jittable = ...` value (Assign or AnnAssign),
    or None when it doesn't set one."""
    for stmt in cls.body:
        targets: List[ast.expr] = []
        value: Optional[ast.expr] = None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        for tgt in targets:
            if isinstance(tgt, ast.Name) and tgt.id == "jittable":
                if isinstance(value, ast.Constant) and \
                        isinstance(value.value, bool):
                    return value.value
                return None  # computed value: assume nothing
    return None


def _class_is_host(cls: ast.ClassDef,
                   classes: Optional[Dict[str, ast.ClassDef]] = None,
                   _seen: Tuple[str, ...] = ()) -> bool:
    """True when the stage is host-path: its body sets jittable=False, it
    subclasses HostTransformer, or a same-module base is itself host. An
    explicit jittable=True in the body overrides any inherited host-ness."""
    own = _own_jittable(cls)
    if own is not None:
        return own is False
    for base in cls.bases:
        dotted = _dotted(base)
        if dotted is None:
            continue
        last = dotted.rsplit(".", 1)[-1]
        if last == "HostTransformer":
            return True
        if classes is not None and last in classes and last not in _seen:
            if _class_is_host(classes[last], classes, _seen + (last,)):
                return True
    return False


def _class_in_types(cls: ast.ClassDef) -> Optional[List[Optional[str]]]:
    """Type NAMES from an `in_types = (T.X, T.Y)` class-body assignment;
    Ellipsis entries become '...'. None when undeclared/opaque."""
    for stmt in cls.body:
        if isinstance(stmt, ast.Assign):
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "in_types":
                    v = stmt.value
                    if not isinstance(v, (ast.Tuple, ast.List)):
                        return None
                    out: List[Optional[str]] = []
                    for e in v.elts:
                        # the repo convention spells variadic as the NAME
                        # `Ellipsis` (parsed as ast.Name), literal `...`
                        # parses as a Constant — both mean variadic
                        if (isinstance(e, ast.Constant)
                                and e.value is Ellipsis) or \
                                (isinstance(e, ast.Name)
                                 and e.id == "Ellipsis"):
                            out.append("...")
                        else:
                            d = _dotted(e)
                            out.append(d.rsplit(".", 1)[-1] if d else None)
                    return out
    return None


def _kind_of_type_name(name: Optional[str]) -> Optional[str]:
    if name in (None, "..."):
        return None
    try:
        from transmogrifai_tpu import types as T
        from transmogrifai_tpu.data.columns import kind_of
        return kind_of(T.feature_type_by_name(name))
    except Exception:
        return None


def _static_argnames(fn: ast.FunctionDef) -> Set[str]:
    """static_argnames/static_argnums declared by jit decorators on `fn`."""
    names: Set[str] = set()
    params = [a.arg for a in fn.args.args]
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        target = _dotted(dec.func)
        calls = [dec]
        # @partial(jax.jit, static_argnames=...) nests the jit reference
        if target in ("partial", "functools.partial") and dec.args:
            inner = _dotted(dec.args[0])
            if inner not in ("jax.jit", "jit"):
                continue
        elif target not in ("jax.jit", "jit"):
            continue
        for call in calls:
            for kw in call.keywords:
                if kw.arg == "static_argnames":
                    for e in ast.walk(kw.value):
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, str):
                            names.add(e.value)
                if kw.arg == "static_argnums":
                    for e in ast.walk(kw.value):
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, int) and \
                                0 <= e.value < len(params):
                            names.add(params[e.value])
    return names


def _jit_decorated(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        d = _dotted(dec if not isinstance(dec, ast.Call) else dec.func)
        if d in ("jax.jit", "jit"):
            return True
        if isinstance(dec, ast.Call) and d in ("partial",
                                               "functools.partial"):
            if dec.args and _dotted(dec.args[0]) in ("jax.jit", "jit"):
                return True
    return False


class _FileLinter(ast.NodeVisitor):
    def __init__(self, path: str,
                 classes: Optional[Dict[str, ast.ClassDef]] = None):
        self.path = path
        self.findings: List[LintFinding] = []
        self._class_stack: List[ast.ClassDef] = []
        self._classes = classes or {}  # module classes, for base resolution
        self._time_aliases = {"time"}  # `import time as _time` et al.

    def _emit(self, node: ast.AST, code: str, message: str) -> None:
        self.findings.append(LintFinding(
            self.path, getattr(node, "lineno", 0), code, message))

    # -- structure ------------------------------------------------------- #

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node)
        self.generic_visit(node)
        self._class_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        cls = self._class_stack[-1] if self._class_stack else None
        host_class = cls is not None and _class_is_host(cls, self._classes)
        in_method = cls is not None
        if node.name in _DEVICE_FNS and in_method and not host_class:
            self._check_device_body(node)
        if node.name in _FIT_FNS and in_method:
            self._check_nondeterminism(node)
        if node.name == "host_prepare" and in_method and cls is not None \
                and not host_class:
            # host-path stages (jittable=False) always see materialized
            # columns — the None contract only binds device stages
            self._check_host_prepare(node, cls)
        statics = _static_argnames(node)
        if statics:
            self._check_static_defaults(node, statics)
        if _jit_decorated(node):
            self._check_traced_branches(
                node, traced_params={a.arg for a in node.args.args}
                - statics - {"self"})
        self._check_uncached_rebuild(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_For(self, node: ast.For) -> None:
        self._check_serial_ingest(node)
        self._check_per_device_loop(node)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        self._check_swallowed_exception(node)
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp) -> None:
        self._check_wallclock_duration(node)
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if alias.name == "time":
                self._time_aliases.add(alias.asname or "time")
        self.generic_visit(node)

    # -- L009 -------------------------------------------------------------- #

    def _is_walltime_call(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        dotted = _dotted(node.func)
        if dotted is None:
            return False
        # exact module call through any recorded alias of the `time`
        # module — NOT arbitrary `.time()` methods (datetime.time etc.
        # must not false-positive)
        parts = dotted.rsplit(".", 1)
        return (len(parts) == 2 and parts[0] in self._time_aliases
                and parts[1] in ("time", "time_ns")) or \
            dotted.endswith(".time.time")

    def _check_wallclock_duration(self, node: ast.BinOp) -> None:
        """Subtraction involving a `time.time()` call measures a
        DURATION on the wall clock: a clock step corrupts it. Timestamps
        (bare reads) are fine; interval math belongs on
        `time.perf_counter()`."""
        if not isinstance(node.op, ast.Sub):
            return
        if self._is_walltime_call(node.left) or \
                self._is_walltime_call(node.right):
            self._emit(
                node, "L009",
                "`time.time()` subtraction measures a duration on the "
                "wall clock — an NTP step/suspend corrupts it; use "
                "time.perf_counter() for intervals (keep time.time() "
                "for epoch timestamps only)")

    def visit_While(self, node: ast.While) -> None:
        self._check_unbounded_retry(node)
        self.generic_visit(node)

    # -- L008 -------------------------------------------------------------- #

    @staticmethod
    def _handler_is_broad(node: ast.ExceptHandler) -> bool:
        """bare `except:` or a clause catching Exception/BaseException."""
        if node.type is None:
            return True
        types = (node.type.elts if isinstance(node.type, ast.Tuple)
                 else [node.type])
        for t in types:
            dotted = _dotted(t)
            if dotted and dotted.rsplit(".", 1)[-1] in (
                    "Exception", "BaseException"):
                return True
        return False

    @staticmethod
    def _body_swallows(body: List[ast.stmt]) -> bool:
        """True when the handler body is ONLY pass/continue/`...`."""
        for stmt in body:
            if isinstance(stmt, (ast.Pass, ast.Continue)):
                continue
            if isinstance(stmt, ast.Expr) and \
                    isinstance(stmt.value, ast.Constant) and \
                    stmt.value.value is Ellipsis:
                continue
            return False
        return bool(body)

    def _check_swallowed_exception(self, node: ast.ExceptHandler) -> None:
        if self._handler_is_broad(node) and self._body_swallows(node.body):
            self._emit(
                node, "L008",
                "broad exception swallow (`except Exception: pass`) — the "
                "failure vanishes silently; narrow the type, record it "
                "(log with exc_info), or route the call through "
                "runtime.retry.RetryPolicy")

    @staticmethod
    def _handler_exits(handler: ast.ExceptHandler) -> bool:
        """Does the handler body (own scope only) raise/break/return?"""
        stack: List[ast.AST] = list(handler.body)
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                continue
            if isinstance(sub, (ast.Raise, ast.Break, ast.Return)):
                return True
            stack.extend(ast.iter_child_nodes(sub))
        return False

    def _check_unbounded_retry(self, node: ast.While) -> None:
        """`while True:` containing a handler that never exits the loop:
        a persistent error retries forever with no attempt bound."""
        if not (isinstance(node.test, ast.Constant)
                and node.test.value is True):
            return
        stack: List[ast.AST] = list(node.body)
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                continue  # nested scopes run on their own terms
            if isinstance(sub, ast.ExceptHandler):
                if not self._handler_exits(sub):
                    self._emit(
                        sub, "L008",
                        "unbounded `while True` retry: this handler "
                        "neither re-raises, breaks, nor returns, so a "
                        "persistent error loops forever — bound it with "
                        "runtime.retry.RetryPolicy (attempts + backoff + "
                        "transient classification)")
                continue  # handler internals already judged
            stack.extend(ast.iter_child_nodes(sub))

    # -- L010 -------------------------------------------------------------- #

    def _check_uncached_rebuild(self, fn: ast.FunctionDef) -> None:
        """Repeated device-matrix builds from the same store variable in
        one scope with no `cache=` policy on any of them: each repeat
        re-streams the whole store host→device when the feature cache
        would replay the built wire tape instead."""
        groups: Dict[str, List[Tuple[ast.Call, bool]]] = {}
        # own scope only: nested defs get their own visit (and their own
        # store bindings), so walking into them would double-report
        stack: List[ast.AST] = list(fn.body)
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(sub))
            if not isinstance(sub, ast.Call):
                continue
            dotted = _dotted(sub.func)
            if dotted is None or \
                    dotted.rsplit(".", 1)[-1] not in _MATRIX_BUILDER_CALLS:
                continue
            if not sub.args:
                continue
            store = _dotted(sub.args[0])
            if store is None:
                continue
            cached = any(kw.arg == "cache" for kw in sub.keywords)
            groups.setdefault(store, []).append((sub, cached))
        for store, calls in groups.items():
            uncached = [c for c, cached in sorted(
                calls, key=lambda p: p[0].lineno) if not cached]
            if len(uncached) < 2:
                continue
            for call in uncached[1:]:
                self._emit(
                    call, "L010",
                    f"repeated device-matrix build from `{store}` in "
                    f"`{fn.name}` with no cache= policy — every call "
                    "re-streams the whole store host→device; pass "
                    "cache= (policy string or FeatureCacheParams) so "
                    "repeats replay the data/feature_cache.py wire "
                    "artifact instead of re-uploading")

    # -- L011 (a): per-device upload loops ---------------------------------- #

    @staticmethod
    def _is_device_iter(it: ast.AST) -> bool:
        # unwrap enumerate(...) — `for i, d in enumerate(devices)`
        if isinstance(it, ast.Call) and _dotted(it.func) == "enumerate" \
                and it.args:
            it = it.args[0]
        if isinstance(it, ast.Call):
            dotted = _dotted(it.func)
            return dotted is not None and \
                dotted.rsplit(".", 1)[-1] in _DEVICE_ITER_CALLS
        dotted = _dotted(it)
        return dotted is not None and \
            dotted.rsplit(".", 1)[-1] in _DEVICE_ITER_NAMES

    def _check_per_device_loop(self, node: ast.For) -> None:
        """Per-device Python loops doing host→device transfers: N
        synchronous RPCs where one sharded `device_put` ships a single
        placement over the whole mesh."""
        if not self._is_device_iter(node.iter):
            return
        stack: List[ast.AST] = list(node.body)
        while stack:
            sub = stack.pop()
            if isinstance(sub, ast.For) and self._is_device_iter(sub.iter):
                continue  # nested device loops report on their own visit
            stack.extend(ast.iter_child_nodes(sub))
            if not isinstance(sub, ast.Call):
                continue
            dotted = _dotted(sub.func)
            if dotted in _SERIAL_UPLOAD_CALLS:
                self._emit(
                    sub, "L011",
                    f"per-device `{dotted}` inside a loop over the "
                    "device list — one synchronous transfer per chip "
                    "serializes placement; ship it as ONE "
                    "`device_put(x, NamedSharding(mesh, spec))` (or let "
                    "parallel/scheduler.py place per worker lane, once)")

    # -- L007 -------------------------------------------------------------- #

    @staticmethod
    def _is_ingest_iter(it: ast.AST) -> bool:
        if isinstance(it, ast.Call):
            dotted = _dotted(it.func)
            return dotted is not None and \
                dotted.rsplit(".", 1)[-1] in _INGEST_ITER_CALLS
        return isinstance(it, ast.Name) and it.id in _INGEST_ITER_NAMES

    def _check_serial_ingest(self, node: ast.For) -> None:
        """Per-iteration host→device transfers inside a chunk-stream
        loop: the serial-ingest anti-pattern `data/pipeline.py` exists
        to replace."""
        if not self._is_ingest_iter(node.iter):
            return
        # skip NESTED chunk-stream loops: visit_For reaches them too,
        # and walking into their bodies here would report each transfer
        # twice
        stack: List[ast.AST] = list(node.body)
        while stack:
            sub = stack.pop()
            if isinstance(sub, ast.For) and self._is_ingest_iter(sub.iter):
                continue
            stack.extend(ast.iter_child_nodes(sub))
            if not isinstance(sub, ast.Call):
                continue
            dotted = _dotted(sub.func)
            if dotted in _SERIAL_UPLOAD_CALLS:
                self._emit(
                    sub, "L007",
                    f"per-iteration `{dotted}` inside a chunk-stream "
                    "`for` loop — one synchronous (or un-depth-"
                    "bounded) host→device transfer per chunk "
                    "serializes host prep against the wire; route "
                    "the upload through data/pipeline."
                    "run_chunk_pipeline (bounded-depth overlapped "
                    "writes) instead")

    # -- L001 + L002 over device bodies ----------------------------------- #

    def _check_device_body(self, fn: ast.FunctionDef) -> None:
        params = [a.arg for a in fn.args.args if a.arg != "self"]
        # device_apply(self, enc, dev) / device_apply_with(self, c, enc, dev)
        traced = set(params)
        self._check_numpy_use(fn)
        self._check_traced_branches(fn, traced_params=traced)
        self._check_fixed_batch_dim(fn)

    def _check_numpy_use(self, fn: ast.FunctionDef) -> None:
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Attribute) and \
                    isinstance(sub.value, ast.Name) and \
                    sub.value.id in ("np", "numpy") and \
                    sub.attr not in _NP_CONST_WHITELIST:
                self._emit(
                    sub, "L001",
                    f"numpy call `{sub.value.id}.{sub.attr}` inside "
                    f"`{fn.name}` — host numpy breaks/escapes the XLA "
                    "trace; use jax.numpy, or move the work to "
                    "host_prepare")

    def _check_traced_branches(self, fn: ast.FunctionDef,
                               traced_params: Set[str]) -> None:
        if not traced_params:
            return
        tainted = set(traced_params)
        # one level of value flow: x = dev[0] / v = enc["k"] taints x/v
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) and \
                    isinstance(sub.value, ast.Subscript):
                base = sub.value.value
                if isinstance(base, ast.Name) and base.id in traced_params:
                    for tgt in sub.targets:
                        if isinstance(tgt, ast.Name):
                            tainted.add(tgt.id)

        def test_is_traced(test: ast.AST) -> bool:
            # `if enc:` (container truthiness) and `if dev[0] is None:`
            # (identity vs None) are static under tracing — what breaks is
            # a VALUE comparison/read: `if x > 0`, `while dev[1]:` etc.
            exempt: set = set()
            for n in ast.walk(test):
                if isinstance(n, ast.Compare) and all(
                        isinstance(o, (ast.Is, ast.IsNot)) for o in n.ops):
                    for m in ast.walk(n):
                        exempt.add(id(m))
            for n in ast.walk(test):
                if id(n) in exempt:
                    continue
                if isinstance(n, ast.Subscript) and \
                        isinstance(n.value, ast.Name) and \
                        n.value.id in tainted:
                    return True
                if isinstance(n, ast.Compare):
                    for m in ast.walk(n):
                        if isinstance(m, ast.Name) and m.id in tainted:
                            return True
                # bare truthiness of a VALUE pulled out of a param
                # (`x = dev[0]` then `if x:`) raises
                # TracerBoolConversionError; bare truthiness of the param
                # itself stays exempt (container/pytree args are common)
                if isinstance(n, ast.Name) and n.id in tainted and \
                        n.id not in traced_params:
                    return True
            return False

        for sub in ast.walk(fn):
            if isinstance(sub, (ast.If, ast.While, ast.IfExp)) and \
                    test_is_traced(sub.test):
                kind = type(sub).__name__.lower()
                self._emit(
                    sub, "L002",
                    f"Python `{kind}` on a traced value inside "
                    f"`{fn.name}` — use jnp.where/lax.cond (branching on "
                    "tracers fails or bakes one path into the compile)")

    # -- L006 -------------------------------------------------------------- #

    _MODULE_RESHAPE_BASES = ("jnp", "np", "numpy", "jax", "lax")

    def _check_fixed_batch_dim(self, fn: ast.FunctionDef) -> None:
        """Flag reshape/broadcast_to whose leading TARGET dim is an int
        literal > 1 inside a device body: bucket padding varies the
        leading batch dim per dispatch, so a baked-in batch size either
        crashes on the first off-size bucket or silently mis-shapes."""
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call) or \
                    not isinstance(sub.func, ast.Attribute):
                continue
            attr = sub.func.attr
            if attr not in ("reshape", "broadcast_to"):
                continue
            base = sub.func.value
            module_form = (
                isinstance(base, ast.Name)
                and base.id in self._MODULE_RESHAPE_BASES) or \
                (isinstance(base, ast.Attribute)
                 and base.attr == "numpy")  # jax.numpy.reshape
            # method form x.reshape(shape...): shape is args[0];
            # module form jnp.reshape(x, shape): shape is args[1]
            idx = 1 if module_form else 0
            if attr == "broadcast_to" and not module_form:
                continue  # no ndarray method broadcast_to in jnp
            if len(sub.args) <= idx:
                continue
            shape = sub.args[idx]
            lead = (shape.elts[0]
                    if isinstance(shape, (ast.Tuple, ast.List))
                    and shape.elts else shape)
            if isinstance(lead, ast.Constant) and \
                    isinstance(lead.value, int) and lead.value > 1:
                self._emit(
                    sub, "L006",
                    f"`{attr}` in `{fn.name}` pins the leading dim to "
                    f"{lead.value} — device code must not assume a fixed "
                    "leading batch dim (bucket padding varies it); derive "
                    "it from x.shape[0] or use -1")

    # -- L003 -------------------------------------------------------------- #

    def _check_static_defaults(self, fn: ast.FunctionDef,
                               statics: Set[str]) -> None:
        args = fn.args.args
        defaults = fn.args.defaults
        offset = len(args) - len(defaults)
        pairs = [(args[offset + i].arg, d) for i, d in enumerate(defaults)]
        # keyword-only statics carry their defaults in kw_defaults
        pairs += [(a.arg, d) for a, d in zip(fn.args.kwonlyargs,
                                             fn.args.kw_defaults)
                  if d is not None]
        for name, d in pairs:
            if name in statics and _is_mutable_literal(d):
                self._emit(
                    d, "L003",
                    f"static arg `{name}` of `{fn.name}` has a mutable "
                    "default — statics must be hashable (tuple/frozenset/"
                    "scalar), and mutable defaults alias across traces")

    # -- L004 -------------------------------------------------------------- #

    def _check_nondeterminism(self, fn: ast.FunctionDef) -> None:
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            dotted = _dotted(sub.func)
            if dotted is None:
                continue
            if dotted in _NONDET_EXACT or dotted in _NONDET_SUFFIX or \
                    any(dotted.endswith("." + c) for c in _NONDET_SUFFIX):
                self._emit(
                    sub, "L004",
                    f"nondeterministic call `{dotted}` inside `{fn.name}` "
                    "— fits must replay from the FitContext seed")
                continue
            parts = dotted.split(".")
            if len(parts) >= 3 and parts[-2] == "random" and \
                    parts[0] in ("np", "numpy"):
                if parts[-1] in _NONDET_NP_RANDOM:
                    self._emit(
                        sub, "L004",
                        f"global-state RNG `{dotted}` inside `{fn.name}` "
                        "— use np.random.default_rng(ctx.seed)")
                elif parts[-1] == "default_rng" and _rng_seedless(sub):
                    self._emit(
                        sub, "L004",
                        f"seedless `{dotted}()` inside `{fn.name}` — pass "
                        "the FitContext seed")

    # -- L005 -------------------------------------------------------------- #

    def _check_host_prepare(self, fn: ast.FunctionDef,
                            cls: ast.ClassDef) -> None:
        in_types = _class_in_types(cls)
        if not in_types:
            return
        params = [a.arg for a in fn.args.args if a.arg != "self"]
        if not params:
            return
        cols_param = params[0]
        variadic = len(in_types) == 2 and in_types[1] == "..."
        for node in ast.walk(fn):
            # only DIRECT dereferences `cols[i].attr` violate the contract;
            # `c = cols[i]` followed by a None-guard is the sanctioned idiom
            if not (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Subscript)):
                continue
            sub = node.value
            if not (isinstance(sub.value, ast.Name)
                    and sub.value.id == cols_param):
                continue
            idx = sub.slice
            if not (isinstance(idx, ast.Constant)
                    and isinstance(idx.value, int)):
                continue
            i = idx.value
            tname = in_types[0] if variadic else (
                in_types[i] if 0 <= i < len(in_types) else None)
            kind = _kind_of_type_name(tname)
            if kind in _DEVICE_KINDS:
                self._emit(
                    sub, "L005",
                    f"host_prepare reads cols[{i}] which is declared "
                    f"{tname} ({kind} kind) — device-kind columns may be "
                    "None on the compiled host phase; read them in "
                    "device_apply via `dev` instead")


# -- L011 (b): host callbacks inside shard_map/pjit bodies ------------------ #

def _is_host_callback(call: ast.Call) -> Optional[str]:
    dotted = _dotted(call.func)
    if dotted is None:
        return None
    if dotted.rsplit(".", 1)[-1] in _HOST_CALLBACK_LAST:
        return dotted
    if any(dotted == s or dotted.endswith("." + s)
           for s in _HOST_CALLBACK_DOTTED_SUFFIX):
        return dotted
    return None


def _spmd_wrapped_bodies(tree: ast.AST):
    """(wrapper_name, body_node) for every function an `shard_map(...)`/
    `pjit(...)` call or decorator wraps: inline lambdas, module/nested
    defs referenced by name, and decorated defs (incl. the
    `@partial(shard_map, ...)` form)."""
    fns = {}
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns.setdefault(n.name, n)
    seen: Set[int] = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Call):
            dotted = _dotted(n.func)
            # @partial(shard_map, mesh=...) nests the wrapper reference
            if dotted in ("partial", "functools.partial") and n.args:
                dotted = _dotted(n.args[0])
                args = n.args[1:]
            else:
                args = n.args
            if dotted is None or \
                    dotted.rsplit(".", 1)[-1] not in _SPMD_WRAPPERS:
                continue
            wrapper = dotted.rsplit(".", 1)[-1]
            for a in args[:1]:
                body = a if isinstance(a, ast.Lambda) else \
                    fns.get(a.id) if isinstance(a, ast.Name) else None
                if body is not None and id(body) not in seen:
                    seen.add(id(body))
                    yield wrapper, body
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in n.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                dotted = _dotted(d)
                if dotted in ("partial", "functools.partial") and \
                        isinstance(dec, ast.Call) and dec.args:
                    dotted = _dotted(dec.args[0])
                if dotted is not None and \
                        dotted.rsplit(".", 1)[-1] in _SPMD_WRAPPERS and \
                        id(n) not in seen:
                    seen.add(id(n))
                    yield dotted.rsplit(".", 1)[-1], n


def _check_spmd_callbacks(tree: ast.AST, path: str) -> List[LintFinding]:
    findings: List[LintFinding] = []
    for wrapper, body in _spmd_wrapped_bodies(tree):
        name = getattr(body, "name", "<lambda>")
        for sub in ast.walk(body):
            if not isinstance(sub, ast.Call):
                continue
            cb = _is_host_callback(sub)
            if cb is not None:
                findings.append(LintFinding(
                    path, getattr(sub, "lineno", 0), "L011",
                    f"host callback `{cb}` inside `{name}`, which "
                    f"`{wrapper}` maps over the mesh — every shard "
                    "stalls on a host round-trip per step, serializing "
                    "the SPMD program; move the host work outside the "
                    "mapped computation"))
    return findings


# -- L012: legacy global-RNG calls (file-wide, testkit-exempt) -------------- #

def _check_legacy_np_random(tree: ast.AST, path: str) -> List[LintFinding]:
    """Flag every call through numpy's module-level legacy RNG (and
    seedless `default_rng()`) anywhere in the file. `testkit/` files are
    exempt — fixtures own their process and seed at the call site."""
    if "testkit" in os.path.normpath(path).split(os.sep):
        return []
    findings: List[LintFinding] = []
    for sub in ast.walk(tree):
        if not isinstance(sub, ast.Call):
            continue
        dotted = _dotted(sub.func)
        if dotted is None:
            continue
        parts = dotted.split(".")
        if len(parts) < 3 or parts[-2] != "random" or \
                parts[0] not in ("np", "numpy"):
            continue
        if parts[-1] in _LEGACY_NP_RANDOM:
            findings.append(LintFinding(
                path, getattr(sub, "lineno", 0), "L012",
                f"legacy global-RNG call `{dotted}` — the module-level "
                "np.random functions share one hidden RandomState, so "
                "any import/thread reordering silently reshuffles every "
                "draw; use a seeded np.random.default_rng(seed) "
                "Generator"))
        elif parts[-1] == "default_rng" and _rng_seedless(sub):
            findings.append(LintFinding(
                path, getattr(sub, "lineno", 0), "L012",
                f"seedless `{dotted}()` — drift sampling and refit "
                "shuffling must replay deterministically across "
                "journal-resumed runs; pass an explicit seed"))
    return findings


# -- L013: hand-set magic tuning knobs in hot paths -------------------------- #

import re as _re

_L013_DIRS = ("data", "parallel", "serving")
_L013_KNOB_WORDS = ("WORKERS", "DEPTH", "QUEUE", "BATCH", "WAIT",
                    "TIMEOUT", "BUDGET", "TARGET", "RETRIES", "WIDTH",
                    "CHUNK", "THREADS", "POLL", "FEEDERS", "LADDER")
_L013_NAME_RE = _re.compile(r"^[A-Z][A-Z0-9_]*$")
# documented env-tunable sites that predate the cost model: each is
# overridable per call (builder kwargs) and via BENCH_*/TRANSMOGRIFAI_*
# env knobs, and the model now fills the unset axes — keyed by file
# basename so a rename forces a fresh look
_L013_ALLOW = {
    ("bigdata.py", "UPLOAD_CHUNK_ROWS"),
    ("bigdata.py", "HIST_CHUNK_ROWS"),
    ("bigdata.py", "UPLOAD_WORKERS"),
    ("bigdata.py", "UPLOAD_DEPTH"),
    ("columnar_store.py", "DEFAULT_CHUNK_ROWS"),
}


def _check_magic_knobs(tree: ast.AST, path: str) -> List[LintFinding]:
    """Flag new module-level numeric tuning-knob constants in the
    data//parallel//serving/ hot paths that bypass the params/env/cost-
    model plumbing (allowlisted: the documented env-tunable sites)."""
    parts = os.path.normpath(path).split(os.sep)
    if not any(d in parts for d in _L013_DIRS):
        return []
    base = os.path.basename(path)
    findings: List[LintFinding] = []

    def pairs(node):
        """(target Name, value node) pairs for plain, annotated, and
        tuple assignments — `WORKERS: int = 4` and
        `WORKERS, DEPTH = 4, 8` are the same knob in other spellings."""
        if isinstance(node, ast.AnnAssign) and node.value is not None:
            if isinstance(node.target, ast.Name):
                yield node.target, node.value
            return
        if not isinstance(node, ast.Assign):
            return
        for target in node.targets:
            if isinstance(target, ast.Name):
                yield target, node.value
            elif isinstance(target, ast.Tuple) and \
                    isinstance(node.value, ast.Tuple) and \
                    len(target.elts) == len(node.value.elts):
                for t, v in zip(target.elts, node.value.elts):
                    if isinstance(t, ast.Name):
                        yield t, v

    for node in getattr(tree, "body", []):  # module top level only
        for target, v in pairs(node):
            name = target.id
            if not _L013_NAME_RE.match(name):
                continue
            if not any(w in name for w in _L013_KNOB_WORDS):
                continue
            if not (isinstance(v, ast.Constant)
                    and isinstance(v.value, (int, float))
                    and not isinstance(v.value, bool)):
                continue  # env-derived/computed values are the fix, not a hit
            if (base, name) in _L013_ALLOW:
                continue
            findings.append(LintFinding(
                path, node.lineno, "L013",
                f"hand-set tuning knob `{name} = {v.value!r}` in a hot "
                "path bypasses the params/env plumbing and the learned "
                "cost model (perf/) — thread it through "
                "PerfModelParams/OpParams or an env knob so "
                "measurements, not one machine's guess, drive it"))
    return findings


# -- L014: per-request/per-iteration service construction -------------------- #

_L014_SERVICES = ("ScoringService", "FleetService", "FleetMemberService")
_L014_HANDLER_RE = _re.compile(r"^(do_[A-Z]+|handle\w*)$")


def _l014_service_call(call: ast.Call) -> Optional[str]:
    """The service class name when `call` constructs one (direct
    constructor or the `from_path` classmethod), else None."""
    dotted = _dotted(call.func)
    if dotted is None:
        return None
    parts = dotted.split(".")
    if parts[-1] in _L014_SERVICES:
        return parts[-1]
    if len(parts) >= 2 and parts[-1] == "from_path" \
            and parts[-2] in _L014_SERVICES:
        return parts[-2]
    return None


def _check_service_construction(tree: ast.AST,
                                path: str) -> List[LintFinding]:
    """Flag ScoringService/FleetService construction inside loop bodies
    or request-handler methods — per-request service construction pays
    model load + compile + full-ladder AOT warmup on the latency path
    and bypasses the fleet's shared-program registry."""
    findings: List[LintFinding] = []

    def visit(node: ast.AST, loop_depth: int, handler: Optional[str]):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a nested def resets loop context (the loop runs the DEF,
            # not the construction) but keeps handler context only for
            # its own name
            handler = node.name if _L014_HANDLER_RE.match(node.name) \
                else None
            loop_depth = 0
        elif isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            loop_depth += 1
        elif isinstance(node, ast.Call):
            svc = _l014_service_call(node)
            if svc is not None and (loop_depth > 0 or handler):
                where = ("loop body" if loop_depth > 0
                         else f"request handler `{handler}`")
                findings.append(LintFinding(
                    path, getattr(node, "lineno", 0), "L014",
                    f"`{svc}(...)` constructed inside a {where} — "
                    "service construction loads the model, builds the "
                    "compiled scorer, and AOT-warms every bucket, so a "
                    "per-request/per-iteration instance defeats warmup "
                    "and the fleet's shared-program registry; construct "
                    "once outside and route requests through it"))
        for child in ast.iter_child_nodes(node):
            visit(child, loop_depth, handler)

    visit(tree, 0, None)
    return findings


# -- L015: unnamed threads in package code ----------------------------------- #

_L015_EXEMPT_DIRS = ("testkit", "tests")


def _check_unnamed_threads(tree: ast.AST, path: str) -> List[LintFinding]:
    """Flag `threading.Thread(...)` constructions missing `name=` in
    package code — unnamed threads make watchdog/hang diagnostics and
    span attribution useless (which thread is the wedged one?)."""
    parts = os.path.normpath(path).split(os.sep)
    if any(d in parts for d in _L015_EXEMPT_DIRS):
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted not in ("threading.Thread", "Thread"):
            continue
        if any(kw.arg == "name" for kw in node.keywords):
            continue
        if any(kw.arg is None for kw in node.keywords):
            continue  # **kwargs may carry the name; can't prove it doesn't
        findings.append(LintFinding(
            path, getattr(node, "lineno", 0), "L015",
            "`threading.Thread(...)` without a `name=` — unnamed "
            "threads make watchdog/hang diagnostics and span "
            "attribution useless; name it for what it runs "
            "(e.g. name=\"scoring-batcher\")"))
    return findings


# -- L016: closure-captured fitted arrays on the compiled scoring path ------- #

# known-small fitted state (a handful of scalars / (d,)-scale index
# vectors) where per-call staging is noise — everything NEW that
# converts `self.<attr>` to a device array inside a compiled-path body
# must either route through device_constants() or be allowlisted here
_L016_ALLOW = {
    # (class, attr): ~100-entry quantile table / kept-index vector —
    # kilobytes, not the megabyte tables the lint exists for
    ("PercentileCalibratorModel", "quantiles"),
    ("DropIndicesByTransformer", "_indices"),
    ("SanityCheckerModel", "indices"),
}
_L016_METHODS = ("device_apply", "predict_arrays")
_L016_CASTS = ("jnp.asarray", "jnp.array", "jax.numpy.asarray",
               "jax.numpy.array")


def _check_closure_constants(tree: ast.AST, path: str) -> List[LintFinding]:
    """Flag `jnp.asarray(self.X)` inside `device_apply`/`predict_arrays`
    bodies of Transformer classes that do NOT define
    `device_constants()`: the converted array is a closure constant of
    the compiled scoring program — megabyte-scale fitted state gets
    value-baked into the XLA executable (every tenant compiles its own
    program, serving/fleet.py). Route big fitted arrays through
    `device_constants()`/`device_apply_with` so they flow as traced jit
    arguments instead."""
    parts = os.path.normpath(path).split(os.sep)
    if any(d in parts for d in ("testkit", "tests")):
        return []
    findings: List[LintFinding] = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        method_names = {n.name for n in cls.body
                        if isinstance(n, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))}
        if "device_constants" in method_names:
            continue  # already lifted
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    or fn.name not in _L016_METHODS:
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                if _dotted(node.func) not in _L016_CASTS:
                    continue
                arg = node.args[0]
                if not (isinstance(arg, ast.Attribute)
                        and isinstance(arg.value, ast.Name)
                        and arg.value.id == "self"):
                    continue
                if (cls.name, arg.attr) in _L016_ALLOW:
                    continue
                findings.append(LintFinding(
                    path, getattr(node, "lineno", 0), "L016",
                    f"`{cls.name}.{fn.name}` converts `self.{arg.attr}` "
                    f"to a device array inside a compiled-path body — a "
                    f"closure constant value-baked into the XLA program "
                    f"and re-staged per dispatch; route fitted arrays "
                    f"through device_constants()/device_apply_with (or "
                    f"allowlist known-small state in _L016_ALLOW)"))
    return findings


# -- L017: unbounded span/event name cardinality ------------------------------ #

# bare/dotted function names whose FIRST argument is an event name
_L017_FUNCS = ("record_event", "emit_event", "add_event")
# method names whose first argument is a span/event name (Tracer.span,
# Tracer.span_at, Span.event, RequestTrace.child/child_at,
# RunProfile.phase)
_L017_METHODS = ("span", "span_at", "event", "child", "child_at")
# bounded-by-construction dynamic name families: the interpolated part
# is a worker index, run type, retry/ingest site label, profile phase,
# model family, (compile:) another span's name plus the jitted
# function's, or (pull:, upload:) a transfer site: fixed text at the
# call plus a family, stage class or prediction field — closed sets
# fixed at build time, not wire-derived values.
# Everything NEW must either use a literal name (variability goes in
# attributes) or extend this list with a justified prefix.
_L017_ALLOW_PREFIXES = (
    "retry:", "sweep:worker:", "sweep:family:", "sweep:dispatch:",
    "sweep:fetch:", "compile:", "ingest:", "run:", "phase:", "stage:",
    "pull:", "upload:",
)


def _l017_dynamic_name(arg: ast.AST) -> bool:
    """True when `arg` builds a string dynamically: an f-string with
    interpolation, or a ``+`` concatenation involving a string
    literal."""
    if isinstance(arg, ast.JoinedStr):
        return any(isinstance(v, ast.FormattedValue) for v in arg.values)
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
        sides = (arg.left, arg.right)
        return any(isinstance(s, ast.Constant) and isinstance(s.value, str)
                   for s in sides) or any(
            _l017_dynamic_name(s) for s in sides)
    return False


def _l017_literal_prefix(arg: ast.AST) -> str:
    """The leading literal text of a dynamic name (the f-string's first
    constant chunk / the concatenation's left literal), for the
    allowlist check."""
    if isinstance(arg, ast.JoinedStr) and arg.values:
        head = arg.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
        if isinstance(arg.left, ast.Constant) \
                and isinstance(arg.left.value, str):
            return arg.left.value
        return _l017_literal_prefix(arg.left)
    return ""


def _check_event_name_cardinality(tree: ast.AST,
                                  path: str) -> List[LintFinding]:
    """Flag span/event names built with f-strings or ``+`` concatenation
    outside the allowlisted bounded families — unbounded event-name
    cardinality breaks the flight-recorder ring's usefulness, the
    goodput by-name rollups, and Prometheus label hygiene."""
    parts = os.path.normpath(path).split(os.sep)
    if any(d in parts for d in ("testkit", "tests")):
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        dotted = _dotted(node.func)
        if dotted is None:
            continue
        leaf = dotted.split(".")[-1]
        is_attr = isinstance(node.func, ast.Attribute)
        if leaf in _L017_FUNCS:
            pass
        elif is_attr and leaf in _L017_METHODS:
            pass
        else:
            continue
        name_arg = node.args[0]
        if not _l017_dynamic_name(name_arg):
            continue
        # the literal head must fully CONTAIN an allowlist entry
        # (prefix.startswith(entry)); the reverse direction would let
        # any 1-char head that happens to start an entry (f"r{x}" vs
        # "retry:") smuggle unbounded names past the check
        prefix = _l017_literal_prefix(name_arg)
        if prefix and any(prefix.startswith(a)
                          for a in _L017_ALLOW_PREFIXES):
            continue
        findings.append(LintFinding(
            path, getattr(node, "lineno", 0), "L017",
            f"`{leaf}(...)` name built dynamically (f-string/`+` "
            f"concatenation) — span/event names key the flight-recorder "
            f"ring, goodput rollups, and Prometheus series, so an "
            f"interpolated name mints unbounded cardinality; use a "
            f"literal name and carry the variable part as an attribute "
            f"(or add a justified bounded prefix to "
            f"_L017_ALLOW_PREFIXES)"))
    return findings


# -- L018: per-row python on the serving hot path ----------------------------- #

# hot-path function-name markers within serving/ modules
_L018_HOT_NAMES = ("score", "assemble", "demux", "parse")
# rows-shaped iterable leaf names a hot-path For must not iterate
_L018_ROWS_NAMES = ("rows",)
# the codec module IS the sanctioned per-row implementation; smoke and
# chaos drivers are load generators, not the serving data plane
_L018_ALLOW_FILES = ("rowcodec.py",)


def _l018_rows_iter(node: ast.AST) -> bool:
    """True when a For's iterable is rows-shaped: the name ``rows`` (or
    ``*_rows``), possibly behind an attribute (``self.rows``), a
    subscript/slice (``rows[1:]``), or an ``enumerate(...)``."""
    if isinstance(node, ast.Call):
        fn = _dotted(node.func)
        if fn in ("enumerate", "reversed") and node.args:
            return _l018_rows_iter(node.args[0])
        return False
    if isinstance(node, ast.Subscript):
        return _l018_rows_iter(node.value)
    name = _dotted(node)
    if name is None:
        return False
    leaf = name.split(".")[-1]
    return leaf in _L018_ROWS_NAMES or leaf.endswith("_rows")


def _check_per_row_serving_loops(tree: ast.AST,
                                 path: str) -> List[LintFinding]:
    """Flag per-row ``for r in rows:`` loops inside serving hot-path
    functions — the host cost the compiled row codec exists to
    eliminate."""
    parts = os.path.normpath(path).split(os.sep)
    if "serving" not in parts or any(
            d in parts for d in ("testkit", "tests")):
        return []
    base = parts[-1]
    if base in _L018_ALLOW_FILES or base.endswith("_smoke.py") \
            or base in ("chaos.py", "smoke.py"):
        return []
    findings: List[LintFinding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        lname = fn.name.lower()
        if not any(m in lname for m in _L018_HOT_NAMES):
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.For) and _l018_rows_iter(node.iter):
                findings.append(LintFinding(
                    path, getattr(node, "lineno", 0), "L018",
                    f"per-row loop over rows in serving hot path "
                    f"`{fn.name}` — the request parse cost the "
                    f"compiled row codec removed; route rows through "
                    f"data/rowcodec.encode_rows (or operate "
                    f"columnar) instead of iterating request dicts"))
    return findings


# -- L019: blocking work inside a lock's critical section -------------------- #

# calls by dotted name that block on the clock or the disk
_L019_BLOCKING_DOTTED = {
    "time.sleep", "open", "io.open",
    "os.makedirs", "os.replace", "os.fsync", "os.remove", "os.rename",
    "json.dump", "json.load", "pickle.dump", "pickle.load",
    "shutil.copy", "shutil.copyfile", "shutil.move", "shutil.rmtree",
}
# method leaves that are file I/O regardless of receiver (pathlib)
_L019_BLOCKING_LEAVES = {"write_text", "read_text", "write_bytes",
                         "read_bytes"}
# same spelling the whole-program auditor (analysis/concurrency.py)
# accepts — one `# conc-ok: C003` annotation silences both tools, since
# both flag the same pattern (lint sees the lexical site, the auditor
# also sees lock-holding callers)
_L019_CONC_OK_RE = re.compile(r"#\s*conc-ok(?::\s*([A-Z0-9,\s]+))?")


def _l019_lockish(node: ast.AST) -> Optional[str]:
    """The dotted name of a with-item that names a lock (leaf contains
    'lock', or is 'cond'/'mutex'), else None. Name-based on purpose:
    the linter is single-file and cannot resolve types; the auditor
    does the type-resolved pass."""
    name = _dotted(node)
    if name is None:
        return None
    leaf = name.split(".")[-1].lower()
    if "lock" in leaf or leaf in ("cond", "mutex"):
        return name
    return None


def _l019_suppressed(lines: Sequence[str], lineno: int) -> bool:
    """True when the finding line (or the line above it) carries a
    ``# conc-ok`` annotation naming L019 or C003 (or bare)."""
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = _L019_CONC_OK_RE.search(lines[ln - 1])
            if m:
                rules = m.group(1)
                if rules is None:
                    return True
                named = {r.strip() for r in rules.split(",")}
                if named & {"L019", "C003"}:
                    return True
    return False


def _check_blocking_under_lock(tree: ast.AST, path: str,
                               lines: Sequence[str]) -> List[LintFinding]:
    """Flag sleep/file-I/O calls lexically inside ``with <lock>:``."""
    parts = os.path.normpath(path).split(os.sep)
    base = parts[-1]
    if base.endswith("_smoke.py") or base in ("smoke.py", "chaos.py") \
            or "tests" in parts or "testkit" in parts:
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.With):
            continue
        lock_name = None
        for item in node.items:
            lock_name = _l019_lockish(item.context_expr)
            if lock_name is not None:
                break
        if lock_name is None:
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = _dotted(sub.func)
            if fn is None:
                continue
            blocked = fn if fn in _L019_BLOCKING_DOTTED else None
            if blocked is None and "." in fn \
                    and fn.split(".")[-1] in _L019_BLOCKING_LEAVES:
                blocked = fn
            if blocked is None:
                continue
            lineno = getattr(sub, "lineno", 0)
            findings.append(LintFinding(
                path, lineno, "L019",
                f"blocking call `{blocked}` inside `with {lock_name}:` — "
                f"every thread contending {lock_name} stalls behind this "
                f"sleep/disk operation; stage data under the lock and do "
                f"the blocking work after release, or annotate a "
                f"deliberately-serialized writer with `# conc-ok: C003`",
                suppression=("annotation"
                             if _l019_suppressed(lines, lineno) else None)))
    return findings


# -- L020: direct writes into artifact-store namespaces ---------------------- #

# calls that RESOLVE a store/cache location: any path expression built
# on top of one of these is inside a manifest-verified namespace
_L020_DIR_FUNCS = {"path_of", "default_cache_dir", "cache_root",
                   "resolve_dir", "resolved_dir", "resolved_corpus_dir"}
# variable spellings that name a store/cache directory
_L020_DIR_NAME_RE = re.compile(
    r"^(cache|store|artifact)_?dir$|^(feature_cache|artifact_store)_dir$")
_L020_WRITE_MODES = re.compile(r"[wax+]")
_L020_NP_WRITERS = {"save", "savez", "savez_compressed", "savetxt"}
_L020_PATH_LEAVES = {"write_text", "write_bytes"}
_L020_STORE_OK_RE = re.compile(r"#\s*store-ok\b")


def _l020_storeish(expr: ast.AST) -> Optional[str]:
    """The dotted name of the store-location source inside a path
    expression, else None. Walks the whole expression so
    ``os.path.join(cache.path_of(k), "x")`` and f-strings match."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name and name.split(".")[-1] in _L020_DIR_FUNCS:
                return name
        elif isinstance(node, (ast.Name, ast.Attribute)):
            name = _dotted(node)
            if name and _L020_DIR_NAME_RE.match(name.split(".")[-1]):
                return name
    return None


def _l020_suppressed(lines: Sequence[str], lineno: int) -> bool:
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines) and _L020_STORE_OK_RE.search(lines[ln - 1]):
            return True
    return False


def _check_store_bypass_writes(tree: ast.AST, path: str,
                               lines: Sequence[str]) -> List[LintFinding]:
    """Flag writes whose destination path derives from an artifact-store
    location without going through ``ArtifactStore.put``."""
    parts = os.path.normpath(path).split(os.sep)
    base = parts[-1]
    if base.endswith("_smoke.py") or base in ("smoke.py", "chaos.py",
                                              "artifact.py") \
            or "tests" in parts or "testkit" in parts \
            or ("store" in parts and base == "state.py"):
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = _dotted(node.func)
        if fn is None or not node.args:
            continue
        leaf = fn.split(".")[-1]
        target: Optional[ast.AST] = None
        if fn in ("open", "io.open"):
            mode = ""
            if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
                mode = str(node.args[1].value)
            for kw in node.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    mode = str(kw.value.value)
            if _L020_WRITE_MODES.search(mode):
                target = node.args[0]
        elif "." in fn and leaf in _L020_NP_WRITERS:
            target = node.args[0]
        elif "." in fn and leaf in _L020_PATH_LEAVES:
            target = node.func.value  # receiver path expression
        if target is None:
            continue
        src_name = _l020_storeish(target)
        if src_name is None:
            continue
        lineno = getattr(node, "lineno", 0)
        findings.append(LintFinding(
            path, lineno, "L020",
            f"direct write via `{fn}` into an artifact-store namespace "
            f"(path built from `{src_name}`) — files in manifest-"
            f"verified directories must land through "
            f"`store.ArtifactStore.put`/`seal_and_commit` (the manifest "
            f"goes in LAST, so readers never see this file as part of a "
            f"verified artifact, or reject the artifact it mutated); "
            f"stage + commit through the store, or annotate a "
            f"deliberate sidecar with `# store-ok: <why>`",
            suppression=("annotation"
                         if _l020_suppressed(lines, lineno) else None)))
    return findings


# -- L021: constant-delay polling loops -------------------------------------- #

def _l021_suppressed(lines: Sequence[str], lineno: int) -> bool:
    """Same ``# conc-ok`` spelling as L019; accepts L021 (or bare)."""
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = _L019_CONC_OK_RE.search(lines[ln - 1])
            if m:
                rules = m.group(1)
                if rules is None:
                    return True
                if {r.strip() for r in rules.split(",")} & {"L021"}:
                    return True
    return False


def _check_blind_poll_loops(tree: ast.AST, path: str,
                            lines: Sequence[str]) -> List[LintFinding]:
    """Flag ``time.sleep(<literal>)`` lexically inside a ``while`` loop:
    coordination waits must be TTL/backoff-derived or Event-woken (see
    module docstring). Only constant arguments flag — a computed delay
    is evidence the loop already derives its cadence from something."""
    parts = os.path.normpath(path).split(os.sep)
    base = parts[-1]
    if base.endswith("_smoke.py") or base in ("smoke.py", "chaos.py") \
            or "tests" in parts or "testkit" in parts:
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.While):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call) \
                    or _dotted(sub.func) != "time.sleep":
                continue
            if not sub.args or not isinstance(sub.args[0], ast.Constant):
                continue
            lineno = getattr(sub, "lineno", 0)
            findings.append(LintFinding(
                path, lineno, "L021",
                f"constant-delay `time.sleep({sub.args[0].value!r})` "
                f"inside a `while` polling loop — a fixed cadence either "
                f"hammers shared state (K replicas polling one "
                f"store/state cell scale the CAS load with fleet size) "
                f"or eats the whole period as idle wall on a cross-host "
                f"handoff; derive the delay from the wait (TTL/deadline, "
                f"capped exponential backoff) or block on "
                f"`Event.wait(timeout=...)` so a writer can wake the "
                f"loop early; annotate a deliberate fixed cadence with "
                f"`# conc-ok: L021`",
                suppression=("annotation"
                             if _l021_suppressed(lines, lineno) else None)))
    return findings


# -- L022: actuation-path calls without a flight-recorder event -------------- #

_L022_ACTUATORS = {"rebucket", "rearm_auto_rebucket", "set_pressure",
                   "set_fidelity_route", "set_route_override"}
_L022_EMITTERS = {"record_event", "request_dump"}
_L022_OK_RE = re.compile(r"#\s*autopilot-ok\b")


def _l022_suppressed(lines: Sequence[str], lineno: int) -> bool:
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines) and _L022_OK_RE.search(lines[ln - 1]):
            return True
    return False


def _check_unlogged_actuations(tree: ast.AST, path: str,
                               lines: Sequence[str]) -> List[LintFinding]:
    """Flag actuation-API calls outside the controller whose enclosing
    function never emits a flight-recorder event — see module
    docstring (L022)."""
    parts = os.path.normpath(path).split(os.sep)
    base = parts[-1]
    if base in ("autopilot.py", "smoke.py", "chaos.py") \
            or base.endswith("_smoke.py") \
            or "tests" in parts or "testkit" in parts:
        return []
    findings: List[LintFinding] = []
    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    # innermost-enclosing-function map: nested defs are visited too, so
    # sort outer-first and let inner functions overwrite their ranges
    for fn in funcs:
        emits = any(isinstance(sub, ast.Call)
                    and (_dotted(sub.func) or "").rsplit(".", 1)[-1]
                    in _L022_EMITTERS
                    for sub in ast.walk(fn))
        if emits:
            continue
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            name = _dotted(sub.func) or ""
            leaf = name.rsplit(".", 1)[-1]
            if leaf not in _L022_ACTUATORS:
                continue
            if fn.name == leaf:
                continue  # the definition module's own wrapper
            lineno = getattr(sub, "lineno", 0)
            findings.append(LintFinding(
                path, lineno, "L022",
                f"actuation call `{name}` outside the autopilot "
                f"controller with no flight-recorder event in "
                f"`{fn.name}` — route flips, admission-threshold "
                f"writes, and ladder re-derivations must record the "
                f"burn window/prediction (or operator action) that "
                f"justified them, or a post-incident flight dump "
                f"cannot explain why traffic moved; emit "
                f"`record_event(...)` beside the call or annotate "
                f"`# autopilot-ok: <why>`",
                suppression=("annotation"
                             if _l022_suppressed(lines, lineno)
                             else None)))
    return findings


# -- L023: manual trace ids that sever the ambient trace context ------------ #

_L023_OPENERS = {"span", "Span", "RequestTrace", "record_event",
                 "emit_event", "add_event"}
_L023_GENERATORS = {"new_run_id", "new_trace_id", "uuid1", "uuid3",
                    "uuid4", "uuid5", "hex", "token_hex"}
_L023_DIRS = {"serving", "parallel", "continual"}
_L023_OK_RE = re.compile(r"#\s*trace-ok\b")


def _l023_suppressed(lines: Sequence[str], lineno: int) -> bool:
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines) and _L023_OK_RE.search(lines[ln - 1]):
            return True
    return False


def _l023_manual_id(node: ast.AST) -> bool:
    """A trace-id VALUE that was hand-built rather than derived from
    live context: string literals/templates/concats and fresh
    id-generator calls flag; attribute reads (``rt.trace_id``,
    ``ctx.trace_id``) and plain names pass — they carry an id that
    already exists somewhere upstream."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, str)
    if isinstance(node, (ast.JoinedStr, ast.BinOp)):
        return True
    if isinstance(node, ast.Call):
        leaf = (_dotted(node.func) or "").rsplit(".", 1)[-1]
        return leaf in _L023_GENERATORS
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Call):
        # `uuid.uuid4().hex`: a fresh-id call dressed as an attribute
        # read — still hand-built, unlike `rt.trace_id` (Name-rooted)
        return _l023_manual_id(node.value)
    return False


def _check_dropped_trace_context(tree: ast.AST, path: str,
                                 lines: Sequence[str]
                                 ) -> List[LintFinding]:
    """Flag span/event calls that pass a manual trace-id string instead
    of the ambient contextvar parent — see module docstring (L023)."""
    parts = os.path.normpath(path).split(os.sep)
    base = parts[-1]
    if not _L023_DIRS.intersection(parts[:-1]):
        return []
    if base in ("smoke.py", "chaos.py") or base.endswith("_smoke.py") \
            or "tests" in parts or "testkit" in parts:
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        leaf = (_dotted(node.func) or "").rsplit(".", 1)[-1]
        if leaf not in _L023_OPENERS:
            continue
        for kw in node.keywords:
            if kw.arg != "trace_id" or not _l023_manual_id(kw.value):
                continue
            lineno = getattr(node, "lineno", 0)
            findings.append(LintFinding(
                path, lineno, "L023",
                f"`{leaf}(...)` passes a manual trace id instead of "
                f"the ambient trace context — a hand-built id severs "
                f"the cross-process stitch: the span lands in the "
                f"trace shard under an id merge_fleet_trace will "
                f"never be asked for, and the request's remote leg "
                f"goes missing from the merged timeline; join the "
                f"current trace (omit trace_id, pass a TraceContext/"
                f"parent span, or root deliberately with "
                f"new_trace=True) or annotate `# trace-ok: <why>`",
                suppression=("annotation"
                             if _l023_suppressed(lines, lineno)
                             else None)))
    return findings


# -- driver ----------------------------------------------------------------- #

def lint_source(src: str, path: str = "<string>") -> List[LintFinding]:
    """Lint one source string (unit-test entry point)."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        # a file the linter cannot parse is surfaced, but must not fail
        # a CI gate the way a real finding does — warning severity
        return [LintFinding(path, e.lineno or 0, "L000",
                            f"syntax error: {e.msg}", severity="warning")]
    classes = {n.name: n for n in ast.walk(tree)
               if isinstance(n, ast.ClassDef)}
    linter = _FileLinter(path, classes)
    linter.visit(tree)
    linter.findings.extend(_check_spmd_callbacks(tree, path))
    linter.findings.extend(_check_legacy_np_random(tree, path))
    linter.findings.extend(_check_magic_knobs(tree, path))
    linter.findings.extend(_check_service_construction(tree, path))
    linter.findings.extend(_check_unnamed_threads(tree, path))
    linter.findings.extend(_check_closure_constants(tree, path))
    linter.findings.extend(_check_event_name_cardinality(tree, path))
    linter.findings.extend(_check_per_row_serving_loops(tree, path))
    linter.findings.extend(_check_blocking_under_lock(
        tree, path, src.splitlines()))
    linter.findings.extend(_check_store_bypass_writes(
        tree, path, src.splitlines()))
    linter.findings.extend(_check_blind_poll_loops(
        tree, path, src.splitlines()))
    linter.findings.extend(_check_unlogged_actuations(
        tree, path, src.splitlines()))
    linter.findings.extend(_check_dropped_trace_context(
        tree, path, src.splitlines()))
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.code))


def lint_file(path: str) -> List[LintFinding]:
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path)


def iter_py_files(paths: Sequence[str]):
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)


def lint_paths(paths: Sequence[str]) -> List[LintFinding]:
    findings: List[LintFinding] = []
    for path in iter_py_files(paths):
        findings.extend(lint_file(path))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m transmogrifai_tpu.lint",
        description="JAX-pitfall lint over stage/kernel source")
    parser.add_argument("paths", nargs="+",
                        help=".py files or directories to lint")
    parser.add_argument("--json", action="store_true",
                        help="emit the shared analysis JSON envelope "
                             "(same shape as analysis.concurrency)")
    args = parser.parse_args(argv)
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        # a typo'd path must not pass a CI gate as "0 findings"
        for p in missing:
            print(f"lint: path does not exist: {p}", file=sys.stderr)
        return 2
    findings: List[LintFinding] = []
    n_files = 0
    for path in iter_py_files(args.paths):
        n_files += 1
        findings.extend(lint_file(path))
    gating = [f for f in findings if f.gating]
    if args.json:
        from transmogrifai_tpu.analysis import report
        print(report.render_json("lint", [
            report.Finding(path=f.path, line=f.line, rule=f.code,
                           message=f.message, severity=f.severity,
                           suppression=f.suppression)
            for f in findings], {"files": n_files}))
    else:
        for f in findings:
            print(f)
        print(f"lint: {len(gating)} gating finding(s) "
              f"({len(findings) - len(gating)} warning/suppressed) "
              f"in {n_files} file(s)")
    # parse-skipped files (L000, warning severity) and annotated
    # escape-hatch findings are reported but never gate the exit code
    return 1 if gating else 0


if __name__ == "__main__":
    sys.exit(main())
