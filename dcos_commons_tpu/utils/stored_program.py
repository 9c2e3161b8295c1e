"""A jitted function whose executables are kept beside the compile
cache and LOADED by a warm start, not traced and lowered again.

JAX's persistent compilation cache (utils/compile_cache.py) is keyed by
the lowered HLO: to find an executable that is already on disk the host
must trace the Python and lower it to MLIR (every Mosaic module with
it) only to compute that key, seconds a program at serving widths.
``StoredProgram`` is ``jax.jit(fn, donate_argnums=...)`` to its
callers, with two differences:

* what is called is the compiled executable itself
  (``jit(fn).lower(*the call's arguments).compile()``, once a set of
  argument types; the compile cache serves that compile as ever), so
  nothing is lowered a second time on any path;
* given a ``directory``, the executable is first looked up there under
  a key the host computes WITHOUT tracing (``program_key``) and
  deserialized; on a miss the compiled one is serialized, written to a
  temporary name and renamed into place.

The key holds what the lowering can depend on and nothing that moves
between runs: a digest of the package's sources, the versions of jax,
jaxlib and the backend (libtpu's build), the device's kind, the device
count, ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS`` and the ``jax.config``
values that change a lowering, the program's name, what the function
closes over (as its owner describes it: ``closed_over``), the donated
arguments, and the tree, shapes, dtypes and weak-type flags of the
call's arguments.  No path, pid, time or ``id()``.

Nothing the store does may fail a start or a request: an entry that
does not load (truncated, another key's, an executable whose argument
types or donation differ from the call's) is removed, compiled and
replaced, and a directory that cannot be written leaves the program
compiled and unstored.  A directory keeps the ``KEEP`` entries a
program name that were used last.  Deleting it is always safe.

Arguments laid over more than one device are compiled as today and
never stored: no sharded executable has been shown to round-trip.
Nor is an executable that the compile cache served, on a backend that
does not serialize such a one whole (``RESERIALIZES``): it is called
as it is, and a start that finds the cache cold stores its own.

``jax.monitoring`` raises no event for a deserialization, so the
wrapper raises its own, ``LOAD_EVENT`` and ``STORE_EVENT`` (with
``fun_name``, as JAX's compile events carry it), which
``trace/startup.py StartupClock`` listens for.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import json
import logging
import os
import pickle
import tempfile
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from dcos_commons_tpu.trace.startup import LOAD_EVENT, STORE_EVENT

LOG = logging.getLogger(__name__)

PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMAT = 1
SUFFIX = ".program"
# entries kept a program name: the checkouts and sizes a machine
# alternates between, not every edit of a day
KEEP = 4
# a temporary file this old was left by a writer that died
_STALE_TMP_S = 3600.0
# jax.config values a lowering reads
_CONFIG_NAMES = (
    "jax_default_matmul_precision", "jax_enable_x64",
    "jax_default_prng_impl", "jax_threefry_partitionable",
)
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# Platforms whose backend serializes, whole, an executable that was
# itself deserialized (one the compile cache served).  XLA:CPU does
# not (jaxlib 0.9.0): what it writes of such a one is half the size,
# loads, and fails at its first run with "Function ... not found".
RESERIALIZES = frozenset({"tpu"})


@functools.lru_cache(maxsize=None)
def source_digest(package_dir: str = PACKAGE_DIR) -> str:
    """SHA-256 over every ``.py`` under ``package_dir``, by relative
    path (the same checkout reads the same wherever it lies).  Read
    once a process: a program is lowered from the sources the process
    imported."""
    paths = []
    for parent, _dirs, files in os.walk(package_dir):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(parent, name)
                paths.append((os.path.relpath(path, package_dir), path))
    digest = hashlib.sha256()
    for relative, path in sorted(paths):
        with open(path, "rb") as f:
            body = f.read()
        digest.update(f"{relative}\0{len(body)}\0".encode())
        digest.update(body)
    return digest.hexdigest()


def lowering_environment(device, package_dir: str = PACKAGE_DIR) -> dict:
    """What a lowering for ``device`` depends on besides the program
    and its arguments."""
    import jax
    import jaxlib

    client = device.client
    return {
        "format": FORMAT,
        "sources": source_digest(package_dir),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": client.platform,
        "platform_version": client.platform_version,
        "device_kind": device.device_kind,
        "device_id": device.id,
        "device_count": client.device_count(),
        "env": {
            # read (never set) for a stored program's key: XLA's flags
            "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
            # read (never set) for a stored program's key: libtpu's flags
            "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        },
        "config": {
            name: describe(getattr(jax.config, name))
            for name in _CONFIG_NAMES
        },
    }


def describe(value) -> Any:
    """``value`` as JSON that reads the same in every process: a
    dataclass by its fields, a dtype by its name.  Whatever might print
    an address raises, and the program goes unstored."""
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        return {
            field.name: describe(getattr(value, field.name))
            for field in dataclasses.fields(value)
        }
    if isinstance(value, dict):
        return {str(key): describe(item) for key, item in value.items()}
    if isinstance(value, (list, tuple)):
        return [describe(item) for item in value]
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    try:
        return np.dtype(value).name
    except TypeError:
        raise TypeError(
            f"no stable description of {type(value).__name__}"
        ) from None


def signature(args: Sequence) -> tuple:
    """What ``jit`` keys a call by: the arguments' tree and, a leaf,
    shape, dtype, weak type and the sharding of a committed array."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten(tuple(args))
    kinds = []
    for leaf in leaves:
        aval = jax.typeof(leaf)
        committed = isinstance(leaf, jax.Array) and leaf.committed
        kinds.append((
            aval.shape, np.dtype(aval.dtype).name, bool(aval.weak_type),
            leaf.sharding if committed else None,
        ))
    return tree, tuple(kinds)


def program_key(name: str, closed_over, donate_argnums: Sequence[int],
                args_signature: tuple, environment: dict) -> Tuple[str, str]:
    """(key, the text it is the digest of) of one program for one set
    of argument types."""
    tree, kinds = args_signature
    material = json.dumps({
        "program": name,
        "closed_over": describe(closed_over),
        "donate_argnums": list(donate_argnums),
        "tree": str(tree),
        "leaves": [kind[:3] for kind in kinds],
        "environment": environment,
    }, sort_keys=True)
    return hashlib.sha256(material.encode()).hexdigest()[:40], material


def _one_device(args_signature: tuple):
    """The one device a lowering of these arguments is for; None where
    they are laid over several (or JAX's default device was moved)."""
    import jax

    devices = set()
    for *_aval, sharding in args_signature[1]:
        if sharding is not None:
            devices |= set(sharding.device_set)
    if not devices and jax.config.jax_default_device is None:
        devices = {jax.local_devices()[0]}
    return devices.pop() if len(devices) == 1 else None


def _call_types(args: Sequence, donate_argnums: Sequence[int]) -> list:
    """(shape, dtype, donated) of every leaf, as ``Compiled.args_info``
    states them."""
    import jax

    out = []
    for i, arg in enumerate(args):
        for leaf in jax.tree_util.tree_leaves(arg):
            aval = jax.typeof(leaf)
            out.append((aval.shape, np.dtype(aval.dtype), i in donate_argnums))
    return out


def write_entry(path: str, material: str, compiled) -> None:
    """Serialize ``compiled`` into ``path``, whole or not at all: a
    temporary name in the same directory, then a rename."""
    from jax.experimental.serialize_executable import serialize

    executable, _in_tree, out_tree = serialize(compiled)
    data = pickle.dumps({
        "material": material, "out_tree": out_tree,
        "executable": executable,
    })
    directory = os.path.dirname(path)
    os.makedirs(directory, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=directory, prefix=".tmp-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def read_entry(path: str, material: str, args: Sequence,
               donate_argnums: Sequence[int], device):
    """The executable stored at ``path``, loaded on ``device``; raises
    where the file is not a whole entry of this key for these
    arguments."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load

    with open(path, "rb") as f:
        # the store's own bytes, written by write_entry
        entry = pickle.load(f)
    if entry["material"] != material:
        raise ValueError("the entry was written under another key")
    compiled = deserialize_and_load(
        entry["executable"],
        jax.tree_util.tree_structure((tuple(args), {})),
        entry["out_tree"], backend=device.client,
        execution_devices=[device],
    )
    stored = [
        (info.shape, np.dtype(info.dtype), info.donated)
        for info in jax.tree_util.tree_leaves(compiled.args_info)
    ]
    if stored != _call_types(args, donate_argnums):
        raise ValueError(
            "the entry's argument types or donation are not the call's"
        )
    # the output tree must hold the executable's outputs
    compiled.out_info
    return compiled


def prune(directory: str, name: str, keep: int = KEEP) -> None:
    """Keep ``name``'s ``keep`` entries used last; drop temporary
    files a dead writer left."""
    now = time.time()
    entries, stale = [], []
    for entry in os.scandir(directory):
        try:
            mtime = entry.stat().st_mtime
        except OSError:
            continue
        if entry.name.startswith(".tmp-"):
            if now - mtime > _STALE_TMP_S:
                stale.append(entry.path)
        elif entry.name.startswith(name + "-") and \
                entry.name.endswith(SUFFIX):
            entries.append((mtime, entry.path))
    entries.sort(reverse=True)
    for path in stale + [path for _mtime, path in entries[keep:]]:
        with contextlib.suppress(OSError):
            os.unlink(path)


class StoredProgram:
    """``jax.jit(fn, donate_argnums=donate_argnums)`` whose executables
    are stored under ``directory`` (None: compiled, never stored).
    ``closed_over`` describes what ``fn`` closes over that no
    argument's type shows (``describe`` must take it).

    Not thread-safe, like the pool that owns it: one caller at a time.
    """

    def __init__(self, fn: Callable, donate_argnums: Sequence[int] = (),
                 closed_over=None, directory: Optional[str] = None):
        import jax

        self.name = fn.__name__
        self._jit = jax.jit(fn, donate_argnums=tuple(donate_argnums))
        self._donate = tuple(donate_argnums)
        self._closed_over = closed_over
        self._directory = directory
        self._programs: Dict[tuple, Any] = {}
        # the program the last call took, and its signature
        self._last = (None, None)

    def _cache_size(self) -> int:
        """Programs held, one a set of argument types: ``jit``'s own
        count of the same name."""
        return len(self._programs)

    def __call__(self, *args):
        last_signature, program = self._last
        if program is not None:
            try:
                return program(*args)
            except (TypeError, ValueError):
                # raised before anything ran or was donated: arguments
                # of another type than this program's, which get a
                # program of their own, or the call's own error
                now = signature(args)
                if now == last_signature:
                    raise
        else:
            now = signature(args)
        program = self._programs.get(now)
        if program is None:
            program = self._programs[now] = self._obtain(now, args)
        self._last = (now, program)
        return program(*args)

    def _obtain(self, args_signature: tuple, args: Sequence):
        """Load the program for these arguments, or compile and store
        it; what comes back is what is called."""
        path = material = None
        try:
            device = (
                _one_device(args_signature) if self._directory else None
            )
            if device is not None:
                key, material = program_key(
                    self.name, self._closed_over, self._donate,
                    args_signature, lowering_environment(device),
                )
                path = os.path.join(
                    self._directory, f"{self.name}-{key}{SUFFIX}"
                )
                program = self._load(path, material, args, device)
                if program is not None:
                    return program
        except Exception:  # noqa: BLE001 — the store never fails a start
            LOG.warning("%s: not stored", self.name, exc_info=True)
            path = None
        program, cache_served = self._compile(args)
        if path is not None and (
            not cache_served or device.platform in RESERIALIZES
        ):
            self._store(path, material, program)
        return program

    def _compile(self, args: Sequence):
        """(the executable for these arguments, whether the compile
        cache served it)."""
        import jax

        cache_reads = []

        def on_duration(event, duration, **_kwargs):
            if event == _CACHE_READ_EVENT:
                cache_reads.append(duration)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            program = self._jit.lower(*args).compile()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
        return program, bool(cache_reads)

    def _load(self, path, material, args, device):
        import jax

        started = time.monotonic()
        try:
            program = read_entry(path, material, args, self._donate, device)
        except FileNotFoundError:
            return None
        except Exception:  # noqa: BLE001 — any entry that does not load
            LOG.warning(
                "%s: stored entry %s does not load; compiling",
                self.name, path, exc_info=True,
            )
            with contextlib.suppress(OSError):
                os.unlink(path)
            return None
        with contextlib.suppress(OSError):
            os.utime(path)  # used now: pruned last
        jax.monitoring.record_event_duration_secs(
            LOAD_EVENT, time.monotonic() - started, fun_name=self.name
        )
        return program

    def _store(self, path, material, program) -> None:
        import jax

        started = time.monotonic()
        try:
            write_entry(path, material, program)
            prune(self._directory, self.name)
        except Exception:  # noqa: BLE001 — compiled and unstored
            LOG.warning(
                "%s: entry %s not written", self.name, path, exc_info=True
            )
            return
        jax.monitoring.record_event_duration_secs(
            STORE_EVENT, time.monotonic() - started, fun_name=self.name
        )
