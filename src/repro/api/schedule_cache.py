"""Persistent schedule cache — measured-tuning winners, paid for once.

The measured autotuner (``repro.api.tuner``) is the expensive half of
``plan(..., RunConfig(autotune="measure"))``: it compiles and times several
candidate schedules on the real backend.  A production process (the ROADMAP's
serving north-star) cannot afford that on every boot, so winners are
persisted to a small JSON file keyed by everything that determines the
optimum:

    (stencil, shape, dtype, boundary condition, cell_bytes, backend,
     execution platform, device, n_chips / chip_grid,
     pinned par_time/bsize, code-version salt)

The *code-version salt* is a content hash of the stencil/kernel/engine/
blocking sources: editing any of them silently invalidates every cached
schedule
(stale winners are never served), with no manual version bump to forget.

Cache resolution (see ``RunConfig.cache``): ``None``/``True`` -> the
``REPRO_SCHEDULE_CACHE`` env var, else ``~/.cache/repro/schedules.json``
(honoring ``XDG_CACHE_HOME``); a path string -> that file; ``False`` ->
caching disabled.  The file is human-readable JSON; deleting it (or any
entry) is always safe — the only cost is re-tuning on the next miss.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import tempfile
import time
import warnings
from pathlib import Path
from typing import Optional, Union

from repro.resilience.faults import fault_point, register_point

try:
    import fcntl
except ImportError:          # non-POSIX: writes fall back to merge-no-lock
    fcntl = None

#: inside ``_load``'s degradation envelope: an injected ``OSError`` here
#: behaves exactly like a flaky filesystem — the cache treats it as a miss
#: (re-tune), never a crash
FP_LOAD = register_point(
    "schedule_cache.get", "on every schedule-cache file read (inject "
    "exc=OSError to model a real filesystem failure)")
FP_PUT = register_point(
    "schedule_cache.put", "before a measured winner is persisted")

#: Bump when the on-disk entry layout changes (not for code changes — those
#: are covered by the content salt).
CACHE_FORMAT_VERSION = 1

_salt_cache: Optional[str] = None


def code_version_salt() -> str:
    """Content hash of the sources that determine a schedule's performance."""
    global _salt_cache
    if _salt_cache is None:
        from repro import programs
        from repro.core import blocking, engine, stencils
        from repro.kernels import builder, ops
        h = hashlib.sha1()
        for mod in (blocking, engine, stencils, ops, builder, programs):
            with open(mod.__file__, "rb") as f:
                h.update(f.read())
        _salt_cache = h.hexdigest()[:12]
    return _salt_cache


def default_cache_path() -> Path:
    env = os.environ.get("REPRO_SCHEDULE_CACHE")
    if env:
        return Path(env)
    base = os.environ.get("XDG_CACHE_HOME") or (Path.home() / ".cache")
    return Path(base) / "repro" / "schedules.json"


def stencil_fingerprint(st) -> str:
    """Hash of what makes a stencil *itself*: name alone is not identity for
    user-defined stencils, whose ``apply`` can change under the same name.

    Shared by the persistent schedule cache (this module) and the
    process-level executable cache (``repro.api.backends``).

    A multi-stage :class:`~repro.programs.StencilProgram` fingerprints as
    the ordered chain of its stages — each stage's stencil fingerprint plus
    its static coefficient overrides and per-stage BC — so two programs
    collide only when they compute the same thing.  DAG wiring (explicit
    ``inputs=``, extra ``fields=``, ``updates=``) folds in only when
    present, so every pre-DAG linear program keeps its exact historical
    fingerprint (cached schedules stay valid)."""
    if hasattr(st, "stages"):    # StencilProgram
        h = hashlib.sha1()
        for s in st.stages:
            btok = (s.boundary.token() if hasattr(s.boundary, "token")
                    else repr(s.boundary))
            h.update(stencil_fingerprint(s.stencil).encode())
            h.update(repr((s.name, s.coeffs, btok)).encode())
            if s.inputs is not None:
                h.update(repr(("inputs", s.inputs)).encode())
        if st.fields != ("u",) or st.updates is not None:
            h.update(repr(("state", st.fields, st.updates)).encode())
        return h.hexdigest()[:8]
    h = hashlib.sha1()
    h.update(repr((st.ndim, st.radius, st.flop_pcu, st.num_read,
                   st.num_write, st.has_aux, st.coeff_names,
                   st.offsets)).encode())
    if getattr(st, "arity", 1) != 1:
        h.update(repr(("arity", st.arity)).encode())
    code = getattr(st.apply, "__code__", None)
    if code is not None:
        h.update(code.co_code)
        # nested code objects repr with process-dependent addresses: skip
        h.update(repr([c for c in code.co_consts
                       if not hasattr(c, "co_code")]).encode())
    return h.hexdigest()[:8]


def schedule_key(problem, config, device, n_chips: int, chip_grid,
                 salt: Optional[str] = None) -> str:
    """Stable, human-readable cache key for one tuning context.

    ``iters_hint`` is deliberately excluded: winners are ranked by amortized
    per-iteration time (see ``repro.api.tuner``), a steady-state metric that
    does not depend on how many super-steps a run chains.
    Everything that constrains the swept candidate set *is* included —
    pinned ``par_time``/``bsize``, ``par_time_max`` and ``tune_top_k`` — so
    a winner found under a tight constraint never shadows (or violates) a
    search run under a looser one.
    """
    import jax
    shape = "x".join(str(d) for d in problem.shape)
    grid = "x".join(str(c) for c in chip_grid) if chip_grid else "-"
    pin_bs = config.normalized_bsize(problem.ndim)
    pin = (f"{config.par_time if config.par_time is not None else '-'}"
           f",{'x'.join(str(b) for b in pin_bs) if pin_bs else '-'}"
           f",{config.par_vec if config.par_vec is not None else '-'}")
    return "|".join([
        problem.stencil.name, f"st={stencil_fingerprint(problem.stencil)}",
        f"shape={shape}", f"dtype={problem.dtype}",
        # the BC shapes the compiled program and its traffic (periodic adds
        # a stream extension): a winner tuned under clamp must never be
        # served to a periodic plan
        f"bc={problem.bc.token()}",
        f"cb={config.resolved_cell_bytes(problem.dtype)}",
        # interpret-mode timings (backend=pallas_interpret) have no relation
        # to compiled ordering: the backend keeps them apart
        f"backend={config.backend}",
        # config.device is only the perf-model's label; the stopwatch ran on
        # the actual jax platform — a shared cache file must not let a
        # CPU-timed winner serve a TPU process (or vice versa)
        f"host={jax.default_backend()}",
        f"device={device.name}", f"chips={n_chips}", f"grid={grid}",
        f"pin={pin}",
        f"lim={config.par_time_max}/{config.tune_top_k}",
        f"salt={salt or code_version_salt()}",
    ])


class ScheduleCache:
    """A JSON file of measured-tuning winners, safe to share and to delete.

    Writes are atomic (tempfile + ``os.replace``) and re-read the file first,
    so concurrent tuners lose at worst one entry, never the file.
    """

    def __init__(self, path: Union[str, Path]):
        self.path = Path(path)

    @classmethod
    def resolve(cls, cache: Union[None, bool, str, Path]
                ) -> Optional["ScheduleCache"]:
        """``RunConfig.cache`` -> a cache instance, or None when disabled."""
        if cache is False:
            return None
        if cache is None or cache is True:
            return cls(default_cache_path())
        return cls(cache)

    def _load(self) -> dict:
        try:
            fault_point(FP_LOAD, {"path": str(self.path)})
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return {}
        if (not isinstance(data, dict)
                or data.get("version") != CACHE_FORMAT_VERSION
                or not isinstance(data.get("entries"), dict)):
            return {}    # unknown layout: treat as empty, overwrite on put
        return data["entries"]

    def get(self, key: str) -> Optional[dict]:
        entry = self._load().get(key)
        return dict(entry) if isinstance(entry, dict) else None

    @contextlib.contextmanager
    def _write_lock(self):
        """Exclusive advisory lock over the cache file's writers.

        Without it, two concurrent ``plan()`` processes race the
        read-modify-write in :meth:`put`: both load, both write, and the
        ``os.replace`` that lands second silently drops the other's freshly
        measured entry.  ``flock`` on a sidecar ``.lock`` file serializes
        the load→merge→replace critical section (the sidecar, not the cache
        file itself, because ``os.replace`` swaps the cache inode out from
        under any lock held on it).  Non-POSIX hosts (no ``fcntl``) fall
        back to merging immediately before the replace — a much smaller
        window than the old load-at-entry, not a guarantee."""
        if fcntl is None:
            yield
            return
        lock_path = self.path.with_name(self.path.name + ".lock")
        with open(lock_path, "a") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(lockf, fcntl.LOCK_UN)

    def put(self, key: str, entry: dict) -> None:
        """Persist ``entry``; an unwritable path degrades to a warning — the
        cache is an optimization, and a write failure must not discard the
        freshly measured winner by crashing ``plan()``.

        Concurrent-writer safe: the on-disk state is (re)loaded and merged
        with this entry *inside* the write lock, immediately before the
        atomic ``os.replace`` — two processes tuning different problems
        both keep their winners (regression-tested with real concurrent
        processes in tests/test_resilience.py)."""
        tmp = None
        try:
            fault_point(FP_PUT, {"path": str(self.path), "key": key})
            self.path.parent.mkdir(parents=True, exist_ok=True)
            with self._write_lock():
                entries = self._load()      # fresh read, under the lock
                entries[key] = dict(entry, saved_at=time.time())
                fd, tmp = tempfile.mkstemp(dir=self.path.parent,
                                           prefix=self.path.name,
                                           suffix=".tmp")
                with os.fdopen(fd, "w") as f:
                    json.dump({"version": CACHE_FORMAT_VERSION,
                               "entries": entries}, f, indent=1,
                              sort_keys=True)
                os.replace(tmp, self.path)
        except OSError as e:
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            warnings.warn(f"schedule cache not persisted to {self.path}: {e}",
                          RuntimeWarning, stacklevel=2)

    def __len__(self) -> int:
        return len(self._load())
