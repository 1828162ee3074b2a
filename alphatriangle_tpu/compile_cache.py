"""AOT executable cache: serialize compiled XLA programs across processes.

The first rollout-chunk compile of the flagship costs the better part
of a minute, and every process pays it before its first metric lands.
The XLA persistent compilation cache
(utils/helpers.py:enable_persistent_compilation_cache) already removes
*re*-compiles on accelerator backends, but (a) it is disabled on CPU
(AOT reload SIGILL risk at the XLA layer), (b) it still pays tracing +
lowering + cache lookup inside the measurement window, and (c) nothing
fills it ahead of a run. This module closes all three gaps,
Podracer-style (arXiv:2104.06272 treats program build/launch latency as
a first-class amortized cost):

- `CompileCache.wrap(name, jit_fn)` returns a `CachedProgram` that, on
  first dispatch of each distinct input signature, either DESERIALIZES
  a previously saved executable (hit: milliseconds instead of a full
  compile) or compiles fresh and serializes the result for the next
  process (miss). Executables ride `jax.experimental.
  serialize_executable` and live beside the XLA persistent cache.
- Keys are (jax version, backend, device kinds + topology, a source
  digest of this package, program name + config digest, input
  avals/shardings) — see `docs/COMPILE_CACHE.md` for the invalidation
  rules. A key mismatch is never an error: it just falls back to a
  fresh `lower().compile()`.
- `warm.py` + `cli warm` enumerate the hot bench/training programs for
  a preset and push them through this cache ahead of time, so a later
  run starts measuring in seconds.

Every load/compile/serialize is recorded as a `compile/<name>` span on
the attached `SpanTracer` (telemetry/tracer.py), so compile cost shows
up in trace.json next to rollout/learner spans; `stats()` feeds the
bench JSON's `compile_cache: {hits, misses}` block.

Degradation contract: any failure (unpicklable executable, corrupt
file, host feature mismatch on reload, an exotic backend without
serialization support) logs once, counts in `stats()`
(`deserialize_errors`, `serialize_errors`, `exec_errors`) and falls
back to a fresh compile or the plain jitted call: a stale artifact
must not stop a run. It must not pass unseen on an accelerator either,
where a hot program that quietly recompiles is the set-up cost this
module exists to remove: `chip_smoke.py` fails on any non-zero
`deserialize_errors` or `exec_errors`.
"""

import hashlib
import logging
import os
import pickle
import threading
import time
import weakref
from contextlib import nullcontext
from pathlib import Path

import jax

from .utils.helpers import compilation_cache_root

logger = logging.getLogger(__name__)


def _exc_brief(exc: BaseException, limit: int = 160) -> str:
    """Exception text bounded for logs (XLA reload errors embed the
    full missing-symbol list — thousands of characters of noise)."""
    text = f"{type(exc).__name__}: {exc}"
    return text if len(text) <= limit else text[: limit - 3] + "..."


def _execution_device_ids(compiled) -> list[int]:
    """Ids of the devices `compiled` was built for, in assignment order
    (the same list jit handed the compiler). The loaded executable
    expects one input shard per device of this list, so a reload must
    name exactly these devices: `deserialize_and_load` defaults to
    EVERY device of the backend, which turns a one-device program on an
    N-device host into one that demands N shards."""
    return [
        d.id for d in compiled._executable._unloaded_executable.device_list
    ]


def _reload(record: dict):
    """The `jax.stages.Compiled` of one artifact record, loaded for the
    devices it was compiled for."""
    from jax.experimental.serialize_executable import deserialize_and_load

    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        record["payload"],
        record["in_tree"],
        record["out_tree"],
        execution_devices=[by_id[i] for i in record["device_ids"]],
    )


# Sentinel stored per signature when AOT execution is not viable for
# those inputs; the program permanently delegates to the jitted fall
# back for that signature (never retries a failing executable).
_FALLBACK = object()


def default_cache_dir() -> str:
    """AOT executables live in an `aot/` subdir of the XLA persistent
    cache's directory, so the one knob (JAX_COMPILATION_CACHE_DIR)
    places both. ALPHATRIANGLE_AOT_CACHE_DIR is the test suite's
    override (tests/conftest.py) and is unset everywhere else."""
    root = (
        os.environ.get("ALPHATRIANGLE_AOT_CACHE_DIR")
        or compilation_cache_root()
    )
    return os.path.join(root, "aot")


def _package_source_digest() -> str:
    """Digest of every .py file in this package: executables are only
    reused by the exact code that produced them. The shape signature
    alone cannot see a changed scan body or loss function — reusing a
    stale executable would silently compute the wrong thing, the one
    failure mode a cache must not have."""
    pkg = Path(__file__).parent
    h = hashlib.sha256()
    for path in sorted(pkg.rglob("*.py")):
        h.update(str(path.relative_to(pkg)).encode())
        try:
            h.update(path.read_bytes())
        except OSError:
            h.update(b"?")
    return h.hexdigest()[:16]


_source_digest_cache: str | None = None


def _source_digest() -> str:
    global _source_digest_cache
    if _source_digest_cache is None:
        _source_digest_cache = _package_source_digest()
    return _source_digest_cache


def config_digest(*configs) -> str:
    """Fingerprint config objects that shape a program but are invisible
    in its input avals (MCTS sim counts, loss weights, optimizer type).
    Pydantic models dump to canonical JSON; anything else reprs.
    RUN_NAME is excluded — it can never affect a compiled program, and
    keeping it would make every differently-named run a cache miss."""
    h = hashlib.sha256()
    for cfg in configs:
        if cfg is None:
            h.update(b"none")
            continue
        dump = getattr(cfg, "model_dump", None)
        if callable(dump):
            d = dump()
            d.pop("RUN_NAME", None)
            h.update(repr(sorted(d.items())).encode())
        else:
            h.update(repr(cfg).encode())
    return h.hexdigest()[:12]


def _describe_leaf(x) -> str:
    """Stable aval + sharding description of one input leaf.

    Mesh (Named) shardings genuinely change the lowered program (GSPMD
    partitioning) and are part of the key; single-device placement vs
    an uncommitted host array does not (both lower to the same
    default-device program), so everything else canonicalizes to "-"
    — this is what lets `cli warm`'s lowering match the bench process's
    real dispatch arguments. A mesh of ONE device partitions nothing
    either, and an executable accepts either spelling of that
    placement: it canonicalizes too. (Otherwise the rollout program
    compiles a second time after the first weight sync of every
    one-chip run, when the net's fresh-init weights give way to copies
    of the trainer's (1, 1, 1)-mesh params — a minute and a half on a
    v5e.)
    """
    shape = tuple(getattr(x, "shape", ()))
    dtype = getattr(getattr(x, "dtype", None), "name", str(getattr(x, "dtype", type(x).__name__)))
    sh = getattr(x, "sharding", None)
    if (
        sh is not None
        and type(sh).__name__ == "NamedSharding"
        and sh.mesh.size > 1
    ):
        mesh_desc = tuple((str(k), int(v)) for k, v in sh.mesh.shape.items())
        sh_desc = f"NS{mesh_desc}{sh.spec}"
    else:
        sh_desc = "-"
    return f"{dtype}{list(shape)}@{sh_desc}"


class CompileCache:
    """Process-wide registry of AOT-cached programs (see module doc)."""

    def __init__(
        self, cache_dir: str | None = None, enabled: bool | None = None
    ) -> None:
        if enabled is None:
            enabled = os.environ.get("ALPHATRIANGLE_NO_COMPILE_CACHE") != "1"
        self.cache_dir = Path(cache_dir or default_cache_dir())
        self.enabled = enabled
        self.tracer = None  # optional telemetry SpanTracer
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.deserialize_errors = 0
        self.serialize_errors = 0
        self.exec_errors = 0
        # name -> {"event": hit|miss|..., "seconds": float}
        self.events: list[dict] = []
        # "name:key" -> memory record (telemetry/memory.py): every
        # program's AOT memory_analysis, captured at compile/reload
        # time and persisted beside the executable artifact. Runs pull
        # from this registry (RunTelemetry keeps its own seen-set, so
        # several runs in one process each ledger every record once).
        self.memory_records: dict[str, dict] = {}
        # "name:key" -> cost record (telemetry/roofline.py): the same
        # flow for `cost_analysis()` — compiler-reported FLOPs / bytes
        # accessed / transcendentals, persisted as `.cost.json`
        # sidecars and drained into run ledgers for `cli roofline`.
        self.cost_records: dict[str, dict] = {}
        # The wrapped programs that are alive: `executables()` reads
        # their compiled objects (profiling.py's phase table).
        self._programs: "weakref.WeakSet[CachedProgram]" = weakref.WeakSet()

    # --- wiring -----------------------------------------------------------

    def set_tracer(self, tracer) -> None:
        """Attach a telemetry SpanTracer: every load/compile/serialize
        becomes a `compile/<program>` span in the run's trace.json."""
        self.tracer = tracer

    def _span(self, name: str, **args):
        if self.tracer is None:
            return nullcontext()
        return self.tracer.span(name, **args)

    def wrap(
        self,
        name: str,
        jit_fn,
        extra: str = "",
        cpu_aot: bool = True,
        serialize: bool = True,
    ) -> "CachedProgram":
        """Wrap a jitted function in an AOT-caching dispatcher.

        `extra` carries a digest of everything that shapes the program
        but is invisible in its input avals (use `config_digest`).
        `cpu_aot=False` bypasses the AOT path entirely on the CPU
        backend (plain jit, no artifacts read or written): XLA:CPU
        deserialization of the learner-step program family is broken in
        this image — the reloaded executable runs without error and
        returns the donated train state UNCHANGED (params silently stop
        updating; reproduced deterministically, see rl/trainer.py).
        Accelerator backends are unaffected by the flag.
        `serialize=False` keeps the in-memory AOT path (lower+compile
        once per signature) but never reads or writes executable
        artifacts on ANY backend — for programs whose executables are
        not round-trippable, e.g. beacon-armed programs embedding
        `jax.debug.callback` closures (telemetry/device_stats.py)."""
        program = CachedProgram(
            self, name, jit_fn, extra=extra, cpu_aot=cpu_aot,
            serialize=serialize,
        )
        self._programs.add(program)
        return program

    def executables(self) -> list[tuple[str, object]]:
        """(program name, `jax.stages.Compiled`) of every AOT executable
        a live wrapped program holds. The compiled text is where an
        operation's `op_name` (and with it its phase) is kept; a TPU
        trace names the instruction only."""
        out = []
        for program in list(self._programs):
            for exe in list(program._execs.values()):
                if exe is not _FALLBACK:
                    out.append((program.name, exe))
        return out

    # --- keying -----------------------------------------------------------

    def signature(self, name: str, args: tuple, extra: str = "") -> str:
        """Cross-process cache key for one (program, inputs) pair."""
        backend = jax.default_backend()
        devices = jax.devices()
        parts = [
            jax.__version__,
            backend,
            ",".join(
                sorted({str(getattr(d, "device_kind", d.platform)) for d in devices})
            ),
            f"d{len(devices)}p{jax.process_count()}",
            _source_digest(),
            name,
            extra,
            str(jax.tree_util.tree_structure(args)),
        ]
        leaves = jax.tree_util.tree_leaves(args)
        # An executable is built for specific devices. Inputs committed
        # to anything but the default device (a second replica's chip,
        # a sub-mesh) key apart, so a reload never lands on devices its
        # arguments do not live on.
        placed = sorted(
            {
                d.id
                for leaf in leaves
                if getattr(leaf, "sharding", None) is not None
                and getattr(leaf, "committed", True)
                for d in leaf.sharding.device_set
            }
        )
        if placed not in ([], [devices[0].id]):
            parts.append(f"on{placed}")
        parts.extend(_describe_leaf(leaf) for leaf in leaves)
        return hashlib.sha256("|".join(parts).encode()).hexdigest()[:20]

    def _path(self, name: str, key: str) -> Path:
        safe = name.replace("/", "_").replace(" ", "_")
        return self.cache_dir / f"{safe}-{key}.jaxexe"

    # --- memory attribution (telemetry/memory.py; docs/OBSERVABILITY.md) --

    def memory_record_for(self, name: str, key: str) -> "dict | None":
        with self._lock:
            return self.memory_records.get(f"{name}:{key}")

    def _register_memory(self, name: str, key: str, record: dict) -> None:
        with self._lock:
            self.memory_records.setdefault(f"{name}:{key}", record)

    def capture_memory(
        self, name: str, key: str, compiled, persist: bool = True
    ) -> "dict | None":
        """Record `compiled.memory_analysis()` for one program and (by
        default) persist it as a `.mem.json` sidecar beside the
        executable artifact, so `cli mem` can attribute a run's HBM
        without recompiling anything. Never raises — attribution can
        only ever add visibility, never break a compile."""
        existing = self.memory_record_for(name, key)
        if existing is not None:
            return existing
        try:
            from .telemetry.memory import program_memory_record

            record = program_memory_record(
                name,
                compiled,
                backend=jax.default_backend(),
                key=key,
            )
        except Exception:
            return None
        if record is None:
            return None
        self._register_memory(name, key, record)
        if persist:
            try:
                import json

                sidecar = self._path(name, key).with_suffix(".mem.json")
                sidecar.parent.mkdir(parents=True, exist_ok=True)
                tmp = sidecar.with_suffix(f".tmp{os.getpid()}")
                tmp.write_text(json.dumps(record))
                tmp.replace(sidecar)
            except OSError:
                logger.debug(
                    "compile_cache: %s memory sidecar write failed", name
                )
        return record

    def _load_memory_sidecar(self, name: str, key: str) -> "dict | None":
        """Reload a previously persisted memory record on an AOT hit
        (the analysis also works on deserialized executables — the
        sidecar just makes the record survive artifact sharing)."""
        try:
            import json

            sidecar = self._path(name, key).with_suffix(".mem.json")
            record = json.loads(sidecar.read_text())
        except (OSError, ValueError):
            return None
        if not isinstance(record, dict) or record.get("kind") != "memory":
            return None
        record["origin"] = "sidecar"
        self._register_memory(name, key, record)
        return record

    def memory_summary(self) -> list[dict]:
        """Every program memory record this process captured (the bench
        JSON's `extra.memory.programs` block)."""
        with self._lock:
            return list(self.memory_records.values())

    # --- cost attribution (telemetry/roofline.py) -------------------------

    def cost_record_for(self, name: str, key: str) -> "dict | None":
        with self._lock:
            return self.cost_records.get(f"{name}:{key}")

    def _register_cost(self, name: str, key: str, record: dict) -> None:
        with self._lock:
            self.cost_records.setdefault(f"{name}:{key}", record)

    def capture_cost(
        self, name: str, key: str, compiled, persist: bool = True
    ) -> "dict | None":
        """Record `compiled.cost_analysis()` for one program and (by
        default) persist it as a `.cost.json` sidecar beside the
        executable artifact — the exact twin of `capture_memory`, so
        `cli roofline` can attribute a run without recompiling
        anything. Never raises."""
        existing = self.cost_record_for(name, key)
        if existing is not None:
            return existing
        try:
            from .telemetry.roofline import program_cost_record

            record = program_cost_record(
                name,
                compiled,
                backend=jax.default_backend(),
                key=key,
            )
        except Exception:
            return None
        if record is None:
            return None
        self._register_cost(name, key, record)
        if persist:
            try:
                import json

                sidecar = self._path(name, key).with_suffix(".cost.json")
                sidecar.parent.mkdir(parents=True, exist_ok=True)
                tmp = sidecar.with_suffix(f".tmp{os.getpid()}")
                tmp.write_text(json.dumps(record))
                tmp.replace(sidecar)
            except OSError:
                logger.debug(
                    "compile_cache: %s cost sidecar write failed", name
                )
        return record

    def _load_cost_sidecar(self, name: str, key: str) -> "dict | None":
        """Reload a previously persisted cost record on an AOT hit.
        Missing, corrupt or wrong-kind sidecars return None (the caller
        re-analyzes the reloaded executable) — torn files degrade,
        never raise."""
        try:
            import json

            sidecar = self._path(name, key).with_suffix(".cost.json")
            record = json.loads(sidecar.read_text())
        except (OSError, ValueError):
            return None
        if not isinstance(record, dict) or record.get("kind") != "cost":
            return None
        record["origin"] = "sidecar"
        self._register_cost(name, key, record)
        return record

    def cost_summary(self) -> list[dict]:
        """Every program cost record this process captured (the bench
        JSON's `extra.roofline.programs` block)."""
        with self._lock:
            return list(self.cost_records.values())

    # --- load / compile / serialize ---------------------------------------

    def load_or_compile(
        self, name: str, key: str, jit_fn, args, serialize: bool = True
    ):
        """Deserialize a cached executable for `key`, or compile fresh
        (serializing the result). Returns a `jax.stages.Compiled`, or
        `_FALLBACK` when neither path is viable. `serialize=False`
        skips BOTH artifact directions (no deserialize, no serialize):
        the executable lives only in this process."""
        path = self._path(name, key)
        if serialize and path.exists():
            t0 = time.time()
            try:
                with self._span(f"compile/{name}", event="deserialize"):
                    with path.open("rb") as fh:
                        compiled = _reload(pickle.load(fh))
                dt = time.time() - t0
                self._note("hit", name, dt)
                # Attribution rides the hit too: prefer the persisted
                # sidecars, fall back to analyzing the reloaded program.
                if self._load_memory_sidecar(name, key) is None:
                    self.capture_memory(name, key, compiled)
                if self._load_cost_sidecar(name, key) is None:
                    self.capture_cost(name, key, compiled)
                logger.info(
                    "compile_cache: %s HIT (%s, deserialized in %.2fs)",
                    name,
                    path.name,
                    dt,
                )
                return compiled
            except Exception as exc:
                # Corrupt file, jaxlib mismatch, host feature check
                # failure on reload — treat as a miss and recompile.
                self.deserialize_errors += 1
                logger.warning(
                    "compile_cache: %s deserialize failed (%s); "
                    "recompiling fresh.",
                    name,
                    _exc_brief(exc),
                )
        t0 = time.time()
        try:
            with self._span(f"compile/{name}", event="compile"):
                compiled = jit_fn.lower(*args).compile()
        except Exception as exc:
            # e.g. a transform jit cannot lower for these args; the
            # plain call path may still work — let it own the error.
            logger.warning(
                "compile_cache: %s AOT lower/compile failed (%s); "
                "falling back to the jitted call.",
                name,
                _exc_brief(exc),
            )
            self.exec_errors += 1
            return _FALLBACK
        dt = time.time() - t0
        self._note("miss", name, dt)
        logger.info("compile_cache: %s MISS (compiled in %.2fs)", name, dt)
        self.capture_memory(name, key, compiled)
        self.capture_cost(name, key, compiled)
        if serialize:
            self._serialize(name, path, compiled)
        return compiled

    def _serialize(self, name: str, path: Path, compiled) -> None:
        tmp = path.with_suffix(f".tmp{os.getpid()}")
        try:
            with self._span(f"compile/{name}", event="serialize"):
                from jax.experimental.serialize_executable import serialize

                payload, in_tree, out_tree = serialize(compiled)
                record = {
                    "payload": payload,
                    "in_tree": in_tree,
                    "out_tree": out_tree,
                    "device_ids": _execution_device_ids(compiled),
                    "meta": {
                        "name": name,
                        "jax": jax.__version__,
                        "backend": jax.default_backend(),
                        "created": time.time(),
                    },
                }
                path.parent.mkdir(parents=True, exist_ok=True)
                with tmp.open("wb") as fh:
                    pickle.dump(record, fh)
                # VALIDATE before publishing: an executable that
                # compile() itself loaded from the XLA persistent cache
                # serializes to a truncated payload on XLA:CPU (the
                # object code is absent; reload dies with "Symbols not
                # found"). A broken artifact would turn every future
                # warm start into a deserialize-error + recompile — so
                # prove the round trip here, where the cost is off any
                # measurement window, and publish only what reloads.
                with tmp.open("rb") as fh:
                    _reload(pickle.load(fh))
                tmp.replace(path)  # atomic: readers never see a torn file
        except Exception as exc:
            self.serialize_errors += 1
            try:
                tmp.unlink(missing_ok=True)
            except OSError:
                pass
            logger.warning(
                "compile_cache: %s not serialized (%s) — this process "
                "keeps its in-memory executable; the next cold process "
                "recompiles (or reuses the XLA persistent cache).",
                name,
                _exc_brief(exc),
            )

    def _note(self, event: str, name: str, seconds: float) -> None:
        with self._lock:
            if event == "hit":
                self.hits += 1
            else:
                self.misses += 1
            self.events.append(
                {"event": event, "program": name, "seconds": round(seconds, 3)}
            )

    # --- reporting --------------------------------------------------------

    def stats(self) -> dict:
        """The bench JSON `compile_cache` block."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "dir": str(self.cache_dir),
                "hits": self.hits,
                "misses": self.misses,
                "deserialize_errors": self.deserialize_errors,
                "serialize_errors": self.serialize_errors,
                "exec_errors": self.exec_errors,
                "events": list(self.events),
            }


class CachedProgram:
    """Callable wrapper over one jitted function: per-input-signature
    AOT executables with a jitted fallback.

    Drop-in for the jitted function it wraps (bit-identical outputs —
    it runs the same lowered program), plus:
    - `warm(*args)`: populate (deserialize or compile+serialize) the
      executable for these argument avals WITHOUT executing — the AOT
      precompilation entry point (`cli warm`).
    - multi-signature: a program called with several distinct shapes
      (e.g. the trainer's fused-from program across K values) caches an
      executable per signature, exactly like jit's own cache.
    """

    def __init__(
        self,
        cache: CompileCache,
        name: str,
        jit_fn,
        extra: str = "",
        cpu_aot: bool = True,
        serialize: bool = True,
    ) -> None:
        self._cache = cache
        self.name = name
        self._jit_fn = jit_fn
        self._extra = extra
        self._cpu_aot = cpu_aot
        self._serialize_artifacts = serialize
        self._execs: dict[str, object] = {}
        self._lock = threading.Lock()

    @property
    def aot_active(self) -> bool:
        """Whether this program uses the AOT artifact path here: the
        cache is enabled AND the program is not CPU-bypassed (see
        CompileCache.wrap's cpu_aot)."""
        return self._cache.enabled and (
            self._cpu_aot or jax.default_backend() != "cpu"
        )

    def _executable_for(self, args):
        key = self._cache.signature(self.name, args, self._extra)
        exe = self._execs.get(key)
        if exe is None:
            with self._lock:
                exe = self._execs.get(key)
                if exe is None:
                    exe = self._cache.load_or_compile(
                        self.name,
                        key,
                        self._jit_fn,
                        args,
                        serialize=self._serialize_artifacts,
                    )
                    self._execs[key] = exe
        return key, exe

    def warm(self, *args) -> bool:
        """Ensure an executable exists for these argument avals (no
        execution, no donation). True when an AOT executable is ready,
        False when this program fell back to plain jit (or is
        CPU-bypassed)."""
        if not self.aot_active:
            return False
        _, exe = self._executable_for(args)
        return exe is not _FALLBACK

    def analyze(self, *args, persist: bool = False) -> "dict | None":
        """Memory record for this program at these argument avals
        (telemetry/memory.py), compiling AOT if needed — WITHOUT
        executing anything. Works even for CPU-bypassed programs
        (cpu_aot=False guards *deserialization*; a fresh lower+compile
        purely for `memory_analysis()` is safe and is not serialized).
        `persist=True` additionally writes the `.mem.json` sidecar on
        the fresh-compile path (the megastep uses this so its record
        survives into the cache dir even where the executable itself
        is CPU-bypassed); the default keeps analysis artifact-free.
        None when the program can't lower or the backend reports no
        analysis. This is `cli fit`'s estimator entry point.

        The cost leg (telemetry/roofline.py) rides every branch: each
        compiled object analyzed here also captures its
        `cost_analysis()` record, so `cli roofline` covers programs
        whose executables never touch the AOT artifact path
        (cpu_aot=False families included). Cost sidecars persist
        unconditionally — a `.cost.json` is a few hundred bytes of
        compiler ground truth (autotune's `--calibrate` cost_flops
        source reads them across processes), unlike the executable
        artifact whose serialization `persist` actually guards."""
        key = self._cache.signature(self.name, args, self._extra)
        record = self._cache.memory_record_for(self.name, key)
        if record is not None and (
            self._cache.cost_record_for(self.name, key) is not None
        ):
            return record
        if self.aot_active:
            _, exe = self._executable_for(args)
            if exe is not _FALLBACK:
                # Cost rides every analysis leg (telemetry/roofline.py):
                # the same compiled object answers both questions.
                self._cache.capture_cost(self.name, key, exe)
                record = self._cache.memory_record_for(self.name, key)
                if record is not None:
                    return record
                return self._cache.capture_memory(self.name, key, exe)
        try:
            compiled = self._jit_fn.lower(*args).compile()
        except Exception as exc:
            logger.warning(
                "compile_cache: %s memory analysis lower/compile failed "
                "(%s)",
                self.name,
                _exc_brief(exc),
            )
            return record
        self._cache.capture_cost(self.name, key, compiled)
        mem = self._cache.capture_memory(
            self.name, key, compiled, persist=persist
        )
        return mem if mem is not None else record

    def __call__(self, *args):
        if not self.aot_active:
            return self._jit_fn(*args)
        key, exe = self._executable_for(args)
        if exe is _FALLBACK:
            return self._jit_fn(*args)
        try:
            return exe(*args)
        except (TypeError, ValueError) as exc:
            # Input validation rejected the call BEFORE execution (so
            # no buffer was donated): e.g. a weak-typed scalar the jit
            # path would have accepted. Never retry this signature.
            self._cache.exec_errors += 1
            logger.warning(
                "compile_cache: %s AOT call rejected (%s: %s); using "
                "the jitted path for this signature.",
                self.name,
                type(exc).__name__,
                exc,
            )
            self._execs[key] = _FALLBACK
            return self._jit_fn(*args)


# --- process-wide cache ----------------------------------------------------

_global_cache: CompileCache | None = None
_global_lock = threading.Lock()


def get_compile_cache() -> CompileCache:
    """The process-wide cache every engine/trainer wraps through.

    Multi-process runs disable AOT caching (deserializing an executable
    that spans non-addressable devices is not supported); the XLA
    persistent cache still covers those.
    """
    global _global_cache
    with _global_lock:
        if _global_cache is None:
            cache = CompileCache()
            if jax.process_count() > 1:
                cache.enabled = False
            _global_cache = cache
        return _global_cache


def reset_compile_cache(
    cache_dir: str | None = None, enabled: bool | None = None
) -> CompileCache:
    """Replace the process-wide cache (tests; fresh stats windows)."""
    global _global_cache
    with _global_lock:
        _global_cache = CompileCache(cache_dir=cache_dir, enabled=enabled)
        return _global_cache
