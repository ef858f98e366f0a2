"""Where the persistent XLA compilation cache lives.

A cold GPT-2-1.5B train step compiles for minutes and a server builds
dozens of small executables; a second process should find all of them
again.  The cache directory is part of the deployment, not of the
program: when ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
and this module sets nothing.  Otherwise the cache goes to
``<checkout>/.jax_cache`` — a path derived from this file's location, so
every process started from the same checkout agrees on it (a cache whose
directory moves never hits).

**A run does not evict its own executables** (:func:`_leave_room`).  Where
the deployment caps the cache (``JAX_COMPILATION_CACHE_MAX_SIZE``; the chip
machines set 192 MiB), JAX makes room for a new entry by evicting the least
recently used ones, and refuses only an entry larger than the whole cap.
The entries a process has read or written are the newest in the directory,
so they are evicted exactly when the new entry does not fit beside them -
and they are what the next process of the same program will ask for: it
finds them gone, compiles them again, and writes them over the entry that
displaced them.  Neither side ever hits (PR 60: the eleventh cell's
comparison program serialises to 198.6 MB under a cap of 201.3 and took the
step's 61.9 MB and everything else with it, run after run - set-up read
215-227 s in a second process where the parent, whose same program is
204.8 MB and was refused by JAX's own rule, read 110; my chip runs, PR 60).
So of two parts of one run that the cap cannot hold together the larger is
left out, whichever came first: an entry that does not fit beside what this
process has already used of the cache, and is larger than all of that, is
not written; a smaller one is, and JAX evicts for it as before.  A run whose
executables fit under the cap together never meets the rule.  What it does
not see is an entry written while the process has used little (the eighth
cell's comparison program, 159 MB, is put beside ~5 MB and empties the
directory at the parent as here); both programs carry a constant of
130-170 MB that the benchmark could pass as an argument, and then no cell
meets the rule (``PERF.md`` sections 6-7, PR 60).
"""
from __future__ import annotations

import inspect
import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first ``jit``.

    Every executable is kept, not only those that took JAX's default
    1.0 s to compile: measured on the v5e (PR 21), a gpt2-760m server
    builds 81 executables of which 75 compile in under a second — 13.5 s
    of its 58 s of compilation that a second process would pay again."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _leave_room()
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _leave_room() -> bool:
    """Make JAX's size-capped cache leave out an entry that does not fit
    beside what this process has read and written of it and is larger than
    all of that (the module's text has why).  The cache's class is JAX's own
    (``jax._src.lru_cache.LRUCache``, no public hook): where it is not there
    with the names the rule reads (``get``, ``put``, ``max_size``), this says
    so once and changes nothing, and JAX's own rule (an entry above the cap
    is refused) stands.  Returns whether the rule is in place."""
    from .logging import logger

    try:
        from jax._src.lru_cache import LRUCache

        get, put = LRUCache.get, LRUCache.put
        if "max_size" not in inspect.signature(LRUCache).parameters:
            raise AttributeError("LRUCache takes no max_size")
    except (ImportError, AttributeError) as e:
        logger.warning(f"compile cache: JAX's LRUCache is not as this rule "
                       f"knows it ({e}): a capped cache evicts as JAX does")
        return False
    if getattr(put, "leaves_room", False):
        return True

    def used(self) -> dict:             # key -> bytes, of this process
        return self.__dict__.setdefault("_used_here", {})

    def noting(self, key):
        val = get(self, key)
        if val is not None:
            used(self)[key] = len(val)
        return val

    def cap_of(self):                   # None: no cap, or none to be read
        try:
            return self.max_size if self.eviction_enabled else None
        except AttributeError as e:
            logger.warning(f"compile cache: the cache's cap cannot be read "
                           f"({e}): it evicts as JAX does")
            return None

    def guarded(self, key, val):
        cap = cap_of(self)
        if cap is None or len(val) > cap:   # above the cap JAX refuses it
            return put(self, key, val)
        beside = sum(n for k, n in used(self).items() if k != key)
        if len(val) + beside > cap and len(val) > beside:
            logger.info(
                f"compile cache: {key[:48]} is {len(val)} bytes and does not "
                f"fit beside the {beside} this process has used of a cache "
                f"capped at {cap}: not kept (it would evict them)")
            return None
        used(self)[key] = len(val)
        return put(self, key, val)

    guarded.leaves_room = True
    LRUCache.get, LRUCache.put = noting, guarded
    return True
