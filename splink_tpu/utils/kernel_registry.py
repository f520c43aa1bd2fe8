"""The process's one registry of jitted programs, keyed by what they close
over.

``jax.jit`` caches on the identity of the function it wraps, so a program
rebuilt as a fresh closure per linker misses jax's in-memory cache however
equal its body: it is traced and lowered again and its executable is read
back from the persistent cache (ROADMAP A2: 10 s of an 18.7 s job). A caller
that can say what its closure captures — as a hashable KEY made of content,
never of object identity — asks :func:`lookup` for the program instead: two
linkers with equal keys get the same jitted callable, and jax's own dispatch
cache does the rest (same avals: no trace, no lowering, no cache read).

The contract of a registered program:

  * its key holds EVERYTHING the closure reads that is not an argument; what
    a caller cannot sign it passes ``key=None`` for and builds per linker,
    exactly as before;
  * ``build`` captures only the key's parts — never a linker, an encoded
    table or a device array, which the registry would pin for the life of the
    process;
  * shapes, dtypes and shardings of arguments are jax's business and are not
    in the key.

The registry is bounded: past ``MAX_ENTRIES`` the least recently used entry
goes (a linker still running keeps its own reference to the callable, so an
eviction can only cost a later linker a rebuild). Every lookup closes a
``kernel_lookup`` build span with counts ``fun``, ``hit``, ``shared`` and
``devices``.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

from .profiling import add_closed

# An entry keeps its executables loaded, and on the TPU a loaded executable
# holds device memory: the five programs of a six-column dedupe at batch 2^23
# held 834 MB after the job had gone, the two of a three-column link 210 MB
# (PERF.md, PR 28). A linker on the virtual pair index takes one entry per
# blocking rule plus the gamma body; 16 keeps three such models, at most
# ~2.7 GB of a chip's 16.
MAX_ENTRIES = 16

# linkers are built from threads (LinkageService, the fleet)
_LOCK = threading.Lock()
_ENTRIES: OrderedDict = OrderedDict()


def lookup(fun: str, key, build, devices: int = 1):
    """The jitted program ``build()`` makes — the registry's when an equal
    ``key`` was seen before, else built now and kept. ``key=None``: the
    caller could not sign its closure; the program is built and NOT kept.
    ``fun`` names the program in the ``kernel_lookup`` span and ``devices``
    says over how many chips it shards (the size of the mesh in its key)."""
    t0 = time.perf_counter()
    fn = None
    if key is not None:
        with _LOCK:
            fn = _ENTRIES.get(key)
            if fn is not None:
                _ENTRIES.move_to_end(key)
    hit = fn is not None
    if not hit:
        # outside the lock: a build may look up the programs it composes.
        # Two threads racing on one key both build; the first to land wins.
        fn = build()
        if key is not None:
            with _LOCK:
                fn = _ENTRIES.setdefault(key, fn)
                _ENTRIES.move_to_end(key)
                while len(_ENTRIES) > MAX_ENTRIES:
                    _ENTRIES.popitem(last=False)
    add_closed("kernel_lookup", "build", time.perf_counter() - t0,
               fun=fun, hit=int(hit), shared=int(key is not None),
               devices=devices)
    return fn


def mesh_key(mesh):
    """A mesh BY VALUE — device ids, grid shape, axis names — so equal meshes
    from repeated ``mesh_from_settings`` calls are one key; None for None."""
    if mesh is None:
        return None
    return (
        tuple(int(d.id) for d in mesh.devices.flat),
        tuple(mesh.devices.shape),
        tuple(mesh.axis_names),
    )


def keys() -> list:
    """The registered keys, least recently used first (tests, diagnostics)."""
    with _LOCK:
        return list(_ENTRIES)


def clear() -> None:
    """Empty the registry: what a test that counts builds starts from."""
    with _LOCK:
        _ENTRIES.clear()
