"""Persistent XLA compilation cache for the jax candidate-axis engine.

The jax engine's cold-start cost is dominated by XLA compilation of the
scan runner (~seconds per shape signature) — paid once per *process* under
plain ``jax.jit``, which is exactly the cost profile the order library
already solved for dispatch orders.  This module applies the same recipe
to compiled executables: ahead-of-time compile once
(``jax.jit(fn).lower(*args).compile()``), serialize the executable with
:mod:`jax.experimental.serialize_executable`, and persist the payload in
the sweep's :class:`~repro.core.diskcache.DiskCache` under the ``xla``
entry namespace — so a compile is paid once per shape *ever*, and every
later process deserializes in milliseconds instead.

Safety properties, mirroring the order library's:

* **Environment-keyed.**  Cache keys embed the jax/jaxlib versions, the
  backend platform and the x64 mode alongside the caller's shape/static
  signature; an upgraded jaxlib or a different backend can never be served
  a stale executable — it just misses and recompiles.
* **Corruption-checked.**  Disk entries ride the DiskCache content-hash
  integrity check; payloads that additionally fail
  ``deserialize_and_load`` (e.g. a same-version-string but incompatible
  build) are swallowed and counted (``failures``), degrading to a fresh
  compile, never to a crash or a wrong executable.
* **Two-tier.**  An in-memory map serves repeat lookups in-process (the
  role ``functools.lru_cache`` used to play); the disk tier serves future
  processes.  ``disk=None`` keeps the in-memory tier only.

Beside that user-named store, :func:`enable_persistent_cache` turns on
JAX's own persistent compilation cache for a whole process.  Entry points
(``python -m repro.explore``, ``chip_smoke.py``, the benchmark scripts)
call it; importing the library never does.

The module deliberately imports jax lazily (inside functions), so
importing it — e.g. via :mod:`repro.core.explore` — stays cheap and
jax-free.
"""
from __future__ import annotations

import collections
import json
import os
import threading
from pathlib import Path
from typing import Any, Callable, Dict, Optional

from .diskcache import DiskCache
from ..testing import faults

#: In-memory executables kept per cache (LRU).  Executables are a few MB
#: at most and a sweep touches a handful of shapes, so this is a backstop
#: against pathological shape churn, not a working-set tuning knob.
MEM_CAP = 64

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed directory inside the checkout.  The path is part of what
#: lets a later process find an entry again, so it is never built from a
#: temporary name, a pid or the time.
DEFAULT_JAX_CACHE_DIR = str(Path(__file__).resolve().parents[3]
                            / ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else
    :data:`DEFAULT_JAX_CACHE_DIR`.  Call once, before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        DEFAULT_JAX_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # keep every compile: the scan's small shapes compile in under the
    # default one-second floor, and a cold chip run pays for each of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCache:
    """Two-tier (memory + :class:`DiskCache`) store of XLA executables.

    ``get``/``put`` speak *loaded executables* (the object returned by
    ``Lowered.compile()`` and ``deserialize_and_load``); serialization is
    internal.  Counters: ``mem_hits`` / ``disk_hits`` (where lookups were
    served), ``compiles`` (misses that had to compile — the number a warm
    store drives to zero), ``failures`` (disk payloads rejected by
    deserialization; each one degrades to a compile).
    """

    def __init__(self, disk: Optional[DiskCache] = None):
        self.disk = disk
        self._mem: "collections.OrderedDict[str, Any]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.mem_hits = 0
        self.disk_hits = 0
        self.compiles = 0
        self.failures = 0

    # ------------------------------------------------------------------
    @staticmethod
    def _env() -> list:
        """Everything a serialized executable is only valid for."""
        import jax
        import jaxlib
        return [jax.__version__, getattr(jaxlib, "__version__", "?"),
                jax.default_backend(), bool(jax.config.jax_enable_x64)]

    def _key_text(self, signature: Any) -> str:
        """The ``xla`` DiskCache namespace key (see diskcache docstring)."""
        return json.dumps(["xla", 1, *self._env(), repr(signature)])

    def as_dict(self) -> Dict[str, int]:
        with self._lock:
            return {"mem_hits": self.mem_hits, "disk_hits": self.disk_hits,
                    "compiles": self.compiles, "failures": self.failures}

    # ------------------------------------------------------------------
    def get(self, signature: Any) -> Optional[Any]:
        """The loaded executable for ``signature``, or ``None`` on miss."""
        text = self._key_text(signature)
        with self._lock:
            exe = self._mem.get(text)
            if exe is not None:
                self._mem.move_to_end(text)
                self.mem_hits += 1
                return exe
        if self.disk is None:
            return None
        got = self.disk.get(text)
        if not (isinstance(got, tuple) and len(got) == 5
                and got[0] == "xla-exec" and got[1] == 1):
            return None
        try:
            from jax.experimental import serialize_executable as se
            exe = se.deserialize_and_load(got[2], got[3], got[4])
        except Exception:       # noqa: BLE001 — any rejection -> recompile
            with self._lock:
                self.failures += 1
            return None
        with self._lock:
            self.disk_hits += 1
            self._remember(text, exe)
        return exe

    def put(self, signature: Any, executable: Any) -> None:
        """Store a freshly compiled executable in both tiers."""
        text = self._key_text(signature)
        with self._lock:
            self.compiles += 1
            self._remember(text, executable)
        if self.disk is None:
            return
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(executable)
        except Exception:       # noqa: BLE001 — unserializable backends
            return              # stay useful as an in-memory cache
        self.disk.put(text, ("xla-exec", 1, payload, in_tree, out_tree))

    def load_or_compile(self, signature: Any,
                        lower: Callable[[], Any]) -> Any:
        """``get`` or else ``lower().compile()`` + ``put`` — the one-call
        form the scan driver uses.  ``lower`` returns a ``jax.stages.
        Lowered`` (i.e. ``jax.jit(fn).lower(*args)``)."""
        if faults.fire("fail_compile"):
            # ahead of the mem-tier check so a warm cache can't mask the
            # injected failure; callers demote the engine on any raise here
            raise RuntimeError("injected fault: fail_compile")
        exe = self.get(signature)
        if exe is None:
            exe = lower().compile()
            self.put(signature, exe)
        return exe

    def _remember(self, text: str, exe: Any) -> None:
        # caller holds the lock
        self._mem[text] = exe
        self._mem.move_to_end(text)
        while len(self._mem) > MEM_CAP:
            self._mem.popitem(last=False)
