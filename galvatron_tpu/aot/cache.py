"""Plan-keyed persistent compile-artifact cache.

Galvatron's premise is that the plan is known *before* the run — so the
compiled programs a (plan × model shape × mesh) run needs are a pure
function of inputs that exist with no data and no devices warmed up.  This
module makes compilation a first-class, keyed artifact:

- :func:`enable_persistent_cache` — the ONE shared wiring of JAX's
  persistent compilation cache (previously hand-wired three divergent ways:
  tests/conftest.py, a CI env block, and nothing at all for the trainer).
  Idempotent; ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside
  and wins over every flag, and without it the cache lives at the fixed
  ``<repo>/.jax_cache``.
- :func:`program_key` — the content key of one compiled program:
  ``(program name, plan_hash, effective model-shape dict, topology
  fingerprint, jax/jaxlib version, relevant XLA flags, donate/sharding
  signature of the abstract inputs)``.  Any semantic change to any term
  forces a miss; identical inputs hash identically across processes and
  hosts (the same property ``core/strategy.plan_hash`` gives plans).
- :class:`ArtifactStore` — a managed manifest over the JAX cache dir:
  atomically-committed JSON (tmp+fsync+rename, ``core/retry.py`` — the
  checkpoint/shard-manifest idioms) recording per-key compile_ms,
  hit/miss/invalidation accounting, and optionally the ``serialize``\\d AOT
  executable itself where the backend supports it.  The manifest is what
  lets a *restart* know its programs are warm before compiling anything —
  the watchdog's first-step grace and the elastic prewarm both key on it.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import time
from typing import Any, Dict, Iterable, Optional

AOT_SCHEMA = "galvatron-aot-v1"
MANIFEST_NAME = "galvatron_aot_manifest.json"

#: environment variables whose value changes the compiled artifact
RELEVANT_XLA_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")

_DISABLED_VALUES = ("0", "off", "none", "disabled")


# ---------------------------------------------------------------------------
# shared persistent-cache wiring
# ---------------------------------------------------------------------------


#: where JAX's persistent cache lives when nothing outside places it: one
#: fixed path inside the checkout (the path is part of the cache key, so a
#: directory that moves never hits)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache(
    cache_dir: Optional[str],
    *,
    min_entry_bytes: Optional[int] = None,
    min_compile_time_s: Optional[float] = None,
) -> Optional[str]:
    """Point JAX's persistent compilation cache at ``cache_dir`` (a
    :func:`resolve_compile_cache_dir` result) and return the EFFECTIVE dir.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: where it is
    set, that directory is kept whatever ``cache_dir`` says — nothing in
    code redirects a cache an operator placed.  ``None`` wires nothing and
    returns what is configured.

    Thresholds: a redirect wires ``min_entry_bytes``/``min_compile_time_s``
    (0 / 0.0 when unspecified, so every compile persists); when the dir is
    already the live one, only EXPLICITLY passed thresholds land — a bare
    re-enable (trainer start, elastic prewarm) must not silently drop a
    conftest's write-churn floor mid-suite."""
    import jax

    current = getattr(jax.config, "jax_compilation_cache_dir", None)

    def _apply_thresholds(entry_default=None, time_default=None):
        eb = min_entry_bytes if min_entry_bytes is not None else entry_default
        ct = min_compile_time_s if min_compile_time_s is not None else time_default
        if eb is not None:
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", int(eb))
        if ct is not None:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", float(ct)
            )

    if cache_dir is None:
        return current
    cache_dir = os.path.abspath(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
    )
    if current and os.path.abspath(current) == cache_dir:
        _apply_thresholds()
        return current
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    _apply_thresholds(0, 0.0)
    # jax latches its cache state (including "no cache configured") on the
    # FIRST compile of the process; a config update after that is ignored
    # until the module handle is dropped
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()
    return cache_dir


@contextlib.contextmanager
def persistent_cache_off():
    """Compile with JAX's persistent cache out of the way, then put it back.
    For compiles whose executable must be the compiler's own: one for a
    described (not attached) device is written to the cache but cannot be
    read back, and one read back from the cache need not carry its text."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        _cc.reset_cache()


def resolve_compile_cache_dir(ns=None) -> Optional[str]:
    """Where this run's compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when set (whatever the flags say) > an explicit ``--compile_cache_dir``
    > ``<repo>/.jax_cache``.  ``--compile_cache_dir 0``/``off``/``none``
    returns None: this run wires no cache and runs no AOT consult."""
    v = getattr(ns, "compile_cache_dir", None)
    if v and str(v).lower() in _DISABLED_VALUES:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return os.path.abspath(env)
    return os.path.abspath(v) if v else REPO_CACHE_DIR


# ---------------------------------------------------------------------------
# program keys
# ---------------------------------------------------------------------------


def topology_fingerprint(devices=None) -> Dict[str, Any]:
    """Compile-relevant topology identity: platform, device kind, device and
    process counts.  (The mesh SHAPE rides the sharding signature — two
    plans on the same chips with different meshes already key apart.)"""
    import jax

    devices = list(devices) if devices is not None else jax.devices()
    d0 = devices[0]
    return {
        "platform": str(getattr(d0, "platform", "unknown")),
        "device_kind": str(getattr(d0, "device_kind", "unknown")),
        "device_count": len(devices),
        "process_count": int(jax.process_count()),
    }


def xla_flag_signature(env: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """The XLA-relevant env flags, token-sorted and de-duplicated so neither
    reordering a flag string nor stating the same token twice (a launcher's
    XLA_FLAGS + `--force_world`'s append of the identical flag) masquerades
    as a different compiler configuration."""
    env = os.environ if env is None else env
    out: Dict[str, Any] = {}
    for var in RELEVANT_XLA_ENV:
        v = env.get(var)
        out[var] = sorted(set(v.split())) if v else None
    return out


def jax_version_string() -> str:
    import jax

    try:
        import jaxlib

        return f"{jax.__version__}/{jaxlib.__version__}"
    except Exception:  # pragma: no cover
        return str(jax.__version__)


def abstract_signature(args: Any, kwargs: Optional[Dict[str, Any]] = None) -> str:
    """Digest of the flattened abstract inputs: shape, dtype and sharding of
    every leaf (non-array leaves — static configs — by repr).  Shardings are
    part of the compiled artifact's identity: the same shapes under a
    different partitioning are a different program."""
    import jax

    h = hashlib.sha256()
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    h.update(str(treedef).encode())
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            sharding = getattr(leaf, "sharding", None)
            h.update(
                f"{tuple(shape)}|{getattr(leaf, 'dtype', None)}|{sharding}".encode()
            )
        else:
            h.update(repr(leaf).encode())
    return h.hexdigest()


def program_key(
    name: str,
    *,
    plan: Any = None,
    model_cfg: Any = None,
    abstract_args: Any = (),
    abstract_kwargs: Optional[Dict[str, Any]] = None,
    donate: Any = None,
    topology: Optional[Dict[str, Any]] = None,
    xla_flags: Optional[Dict[str, Any]] = None,
    jax_version: Optional[str] = None,
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """Stable content key of one compiled program (see module docstring for
    the term list).  ``plan`` is a HybridParallelConfig / strategy JSON dict
    (hashed through :func:`core.strategy.plan_hash`, so provenance keys and
    ordering never matter) or ``None`` for plan-free programs (serving,
    generate)."""
    payload: Dict[str, Any] = {
        "schema": AOT_SCHEMA,
        "program": str(name),
        "plan_hash": None,
        "model_shape": None,
        "topology": topology if topology is not None else topology_fingerprint(),
        "jax": jax_version if jax_version is not None else jax_version_string(),
        "xla_flags": xla_flags if xla_flags is not None else xla_flag_signature(),
        "args_sig": abstract_signature(abstract_args, abstract_kwargs),
        "donate": repr(donate) if donate is not None else None,
        "extra": extra or None,
    }
    if plan is not None:
        from galvatron_tpu.core.strategy import plan_hash

        payload["plan_hash"] = plan if isinstance(plan, str) else plan_hash(plan)
    if model_cfg is not None:
        from galvatron_tpu.analysis.plan_check import model_shape_dict

        payload["model_shape"] = model_shape_dict(model_cfg)
        # executed-config terms the shape dict cannot see: a different
        # kernel, compute dtype, or packing contract is a different program
        payload["model_exec"] = {
            k: str(getattr(model_cfg, k, None))
            for k in ("attn_impl", "dtype", "pack_sequences", "mlp_recompute")
        }
    digest = hashlib.sha256(
        json.dumps(payload, sort_keys=True, default=repr).encode()
    ).hexdigest()
    return f"aot:{digest}"


# ---------------------------------------------------------------------------
# manifest store
# ---------------------------------------------------------------------------


class ArtifactStore:
    """Managed manifest over a persistent-compile-cache directory.

    One JSON file (``galvatron_aot_manifest.json``) maps program keys to
    accounting records; commits are atomic (tmp + fsync + rename + dir
    fsync) and retried through ``core/retry.py`` — the same idioms as the
    checkpoint and shard manifests, because the same partial-write failure
    modes apply.  The store is advisory: losing it costs accounting, never
    correctness (JAX's own cache still serves the executables)."""

    def __init__(self, cache_dir: str):
        self.dir = os.path.abspath(cache_dir)
        os.makedirs(self.dir, exist_ok=True)
        self.manifest_path = os.path.join(self.dir, MANIFEST_NAME)
        # per-store-instance session accounting (the manifest carries the
        # cross-process totals)
        self.hits = 0
        self.misses = 0
        # the parsed manifest, read once per store instance: a warmup sweep
        # of P programs against a long-lived cache dir must not pay P full
        # JSON parses of an ever-growing file. Writes go through the cached
        # doc, so the last writer wins the whole file — the same window the
        # per-call read-modify-write had, and the store is advisory by
        # contract (losing accounting never loses executables).
        self._doc: Optional[Dict[str, Any]] = None

    # -- manifest I/O --------------------------------------------------------

    def _load(self) -> Dict[str, Any]:
        if self._doc is None:
            self._doc = self._read()
        return self._doc

    def _read(self) -> Dict[str, Any]:
        from galvatron_tpu.core.retry import with_retries

        if not os.path.exists(self.manifest_path):
            return {"schema": AOT_SCHEMA, "programs": {}, "invalidations": 0}

        def read():
            with open(self.manifest_path) as f:
                return json.load(f)

        try:
            doc = with_retries(read, describe=f"read {self.manifest_path}")
        except (OSError, ValueError) as e:
            # a torn manifest must not take the run down — accounting
            # restarts; the executables in jax's cache are untouched
            print(f"aot: unreadable manifest {self.manifest_path} ({e!r}); resetting")
            return {"schema": AOT_SCHEMA, "programs": {}, "invalidations": 0}
        if not isinstance(doc, dict) or not isinstance(doc.get("programs"), dict):
            return {"schema": AOT_SCHEMA, "programs": {}, "invalidations": 0}
        doc.setdefault("invalidations", 0)
        return doc

    def _write(self, doc: Dict[str, Any]) -> None:
        from galvatron_tpu.core.retry import with_retries

        tmp = self.manifest_path + f".tmp.{os.getpid()}"

        def commit():
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.manifest_path)
            try:
                fd = os.open(self.dir, os.O_RDONLY)
            except OSError:
                return  # not all filesystems expose dir fds
            try:
                os.fsync(fd)
            finally:
                os.close(fd)

        try:
            with_retries(commit, describe=f"commit {self.manifest_path}")
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    # -- accounting ----------------------------------------------------------

    def entries(self) -> Dict[str, Any]:
        return self._load()["programs"]

    def lookup(self, key: str) -> Optional[Dict[str, Any]]:
        return self._load()["programs"].get(key)

    def record_compile(
        self,
        key: str,
        *,
        program: str,
        compile_ms: float,
        hit: bool,
        meta: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Account one compile of ``key``: a key already present is a HIT
        (the persistent cache served it), a new key is a MISS (real XLA
        compile, now cached for every later process)."""
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        doc = self._load()
        entry = doc["programs"].setdefault(
            key,
            {
                "program": program,
                "first_compiled_at": time.time(),
                "compiles": 0,
                "hits": 0,
                "first_compile_ms": round(float(compile_ms), 3),
            },
        )
        entry["program"] = program
        entry["compiles"] = int(entry.get("compiles", 0)) + 1
        if hit:
            entry["hits"] = int(entry.get("hits", 0)) + 1
        entry["last_compile_ms"] = round(float(compile_ms), 3)
        entry["last_compiled_at"] = time.time()
        if meta:
            entry.setdefault("meta", {}).update(meta)
        self._write(doc)
        return entry

    def invalidate(self, keys: Optional[Iterable[str]] = None) -> int:
        """Drop manifest entries (all of them by default) and count the
        invalidation — an operator clearing a poisoned cache, or a test
        forcing misses.  Serialized executables for dropped keys are removed
        too; JAX's own cache files are left alone (they are content-addressed
        and harmless)."""
        doc = self._load()
        dropped = list(doc["programs"]) if keys is None else [
            k for k in keys if k in doc["programs"]
        ]
        for k in dropped:
            del doc["programs"][k]
            exe = self._exec_path(k)
            if os.path.exists(exe):
                try:
                    os.remove(exe)
                except OSError:
                    pass
        doc["invalidations"] = int(doc.get("invalidations", 0)) + len(dropped)
        self._write(doc)
        return len(dropped)

    def stats(self) -> Dict[str, Any]:
        doc = self._load()
        progs = doc["programs"]
        return {
            "entries": len(progs),
            "session_hits": self.hits,
            "session_misses": self.misses,
            "total_compiles": sum(int(e.get("compiles", 0)) for e in progs.values()),
            "total_hits": sum(int(e.get("hits", 0)) for e in progs.values()),
            "invalidations": int(doc.get("invalidations", 0)),
        }

    # -- serialized executables ---------------------------------------------

    def _exec_path(self, key: str) -> str:
        return os.path.join(self.dir, key.replace(":", "_") + ".exec")

    def save_executable(self, key: str, compiled) -> bool:
        """Persist the AOT executable itself (``jax.experimental.
        serialize_executable``) where the backend supports it.  Best-effort
        by contract: any failure returns False and costs nothing — the
        persistent compile cache remains the durable layer.  The blob is
        VERIFIED reloadable before it is recorded: some backends serialize
        happily but cannot reload the result (e.g. CPU executables that
        were themselves deserialized from the compile cache reference
        jit'd symbols) — ``serialized: true`` must mean loadable."""
        import pickle

        try:
            from jax.experimental import serialize_executable as se

            blob = pickle.dumps(se.serialize(compiled))
        except Exception:  # noqa: BLE001 — backend/tree not serializable here
            return False
        path = self._exec_path(key)
        tmp = path + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        if self.load_executable(key) is None:
            try:
                os.remove(path)
            except OSError:
                pass
            return False
        doc = self._load()
        if key in doc["programs"]:
            doc["programs"][key]["serialized"] = True
            doc["programs"][key]["serialized_bytes"] = len(blob)
            self._write(doc)
        return True

    def load_executable(self, key: str):
        """Deserialize a previously saved executable, or None.  The caller
        owns validity: the key already encodes everything that could make a
        stale executable unsafe (topology, versions, flags, shardings)."""
        import pickle

        path = self._exec_path(key)
        if not os.path.exists(path):
            return None
        try:
            from jax.experimental import serialize_executable as se

            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.loads(f.read())
            return se.deserialize_and_load(payload, in_tree, out_tree)
        except Exception:  # noqa: BLE001 — a corrupt blob degrades to recompile
            return None
