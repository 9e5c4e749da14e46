"""A serving engine's programs, loaded ahead of their first call.

A replica that starts on a warm persistent cache compiles nothing, and
still spends most of its constructor on its programs: each first call
traces, lowers, reads the cached executable and runs, one program after
another on the caller's thread, while the device and the other cores wait
(PERF.md section 5, "Set-up from inside": 52 s of reads and 16 s of trace +
lower in the Kimi replica's 86 s). The programs a replica serves with all go
through its cache's `prefill`, `write` and `decode`, and each is told apart
by host scalars the method already has: the token block's (batch, bucket),
or `attn_len`. So a life of an engine LISTS those keys as it first calls
them, and the next life of the same engine loads the list's programs from
its constructor's end on, off the caller's thread:

  * ONE thread traces and lowers them in the list's order, from abstract
    arguments (tracing in several threads at once is slower than in one:
    they fight over the interpreter);
  * each lowered program's `.compile()`, the cache read, goes to the
    process's MAIN thread where that is lent (`util/main_thread.py`: a
    worker's is), one read at a time. On a TPU v5e the client's
    `deserialize_executable` takes 0.46-0.59 s for a 43-51 MiB prompt pass
    on the main thread and 2.1-7.5 s on any other, the replica's constructor
    thread included (glibc gives every other thread an arena of its own,
    and the client's allocations are slow there: with `M_ARENA_MAX` 1 a
    fresh thread reads in 0.9 s), and two reads at once take 5.2-7.6 s each
    where one after the other takes 0.8-1.0: a pool of reader threads would
    be slower than no pool (PR 64, `ci/chip_calls/pr64/` calls 1-3; PERF.md
    section 6);
  * where no main thread is lent (a script, a test: the engine's caller IS
    the main thread) the caller keeps the read: its first call finds the
    program traced and lowered and reads it where reading is fastest.

A first call waits at the gate (`Programs.run`) for its key's load and then
finds what the load left: same trace, same lowering, and where the read ran
ahead the executable itself (jax keeps all three by the jitted function and
its abstract arguments, whichever thread asked), so nothing is traced or
read twice and the program is the one the plain call would have made, down
to the persistent cache's key.

The list is one file an engine, INSIDE the persistent cache's directory and
named as that directory's owner names its own: `programs-<digest>-cache`,
with the `programs-<digest>-atime` stamp jax's LRU eviction reads. It is
worth what the directory's executables are worth, so it lives where they
live and by their rules: whoever copies, mounts or carries a cache for a new
replica brings the list with the programs it names (the chip tool carries
the directory from one call's machine to the next and nothing beside it: a
list kept BESIDE the directory was gone, PR 64, call 6; the ones inside
came along, call 9); jax counts its two kilobytes under the directory's
cap and evicts it like any entry it has not seen used, which costs the next
life a first life. It holds
a header line that spells out what the digest is of (cache class,
configuration, slots, length, jax and backend version) and a line a key. A
life rewrites it to what IT first called, in that order, whole and under
another name first, so a killed replica leaves the last whole list; a file
that is cut short or holds what no line should is read as far as it makes
sense. A key is listed only if its first call through the cache was the
process's first call of that program: a caller that warms the jitted
functions directly (the benchmark's dense replica) has nothing listed, and
so no thread beside its warm loop tracing the same programs. Deleting a list
is always safe: the next life is a first life.

No persistent cache, or one that is not a local directory: no list and no
thread (`Direct`).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ray_tpu.util import main_thread, tracing

Key = Tuple  # ("admit", batch, bucket) | ("decode", attn_len)


class Direct:
    """No list: every call goes straight to its program."""

    @staticmethod
    def run(key: Key, fn: Callable, *args, **kwargs):
        return fn(*args, **kwargs)

    @staticmethod
    def close() -> None:
        pass


def abstract(tree):
    """What `jit.lower` needs of the arrays a call will bring, so that the
    lowering is the call's own: shape, type, weak type, and the device of a
    committed array (an uncommitted one goes where the program goes)."""
    def one(x):
        if not isinstance(x, jax.Array):   # a host array: placed by the call
            x = np.asarray(x)
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)

    return jax.tree.map(one, tree)


def list_path(cache, cfg, num_slots: int, max_len: int) -> Tuple[Optional[str], dict]:
    """(the engine's list file, its header); no file without a local
    persistent cache."""
    where = jax.config.jax_compilation_cache_dir
    if not where or "://" in where:
        return None, {}
    import jaxlib

    device = jax.devices()[0]
    header = {"cache": type(cache).__name__, "cfg": repr(cfg),
              "num_slots": num_slots, "max_len": max_len,
              "jax": f"{jax.__version__}/{jaxlib.__version__}",
              "backend": f"{device.device_kind}/{device.client.platform_version}"}
    digest = hashlib.sha256(json.dumps(header, sort_keys=True).encode()).hexdigest()
    return os.path.join(where, f"programs-{digest[:24]}-cache"), header


def read_list(path: str, header: dict) -> List[Key]:
    """The keys of a list written under exactly `header`, in order, each
    once; [] if there is none. Lines that are no key are passed over."""
    try:
        with open(path) as f:
            lines = f.read().split("\n")
    except OSError:
        return []
    keys: List[Key] = []
    for i, line in enumerate(lines[:-1]):   # a line is whole once it ended
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if i == 0:
            if row != header:
                return []
        elif isinstance(row, list) and row and row[0] in ("admit", "decode") \
                and all(isinstance(n, int) for n in row[1:]) \
                and tuple(row) not in keys:
            keys.append(tuple(row))
    return keys


def outputs(lowered, *args):
    """The abstract results of a lowered program as its caller will hand
    them on: committed where an argument is."""
    on = next((x.sharding for x in jax.tree.leaves(args)
               if x.sharding is not None), None)
    return jax.tree.map(
        lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=on),
        lowered.out_info)


def int32(*shape):
    return jax.ShapeDtypeStruct(shape, "int32")


class _Load:
    """One entry of the list being replayed."""

    def __init__(self):
        self.done = threading.Event()
        self.programs = 1      # of this entry, once it is lowered
        self.left = 0          # of them, not yet read
        self.failed = False


class Programs:
    """The list of one engine life and the replay of the life before.
    `lower(key)` returns the key's lowered programs (the cache's `lowered`
    over the engine's abstract arguments). `n`, the arguments of the life's
    one `programs.ahead` span, counts programs: `listed` those of the list's
    keys the replay reached, `loaded` those lowered ahead (and read ahead,
    where the main thread is lent), `failed` those that did not lower or
    load (their callers compile them), `ready_at_first_call` those whose
    first call did not wait, `waited_us` what first calls waited in sum."""

    def __init__(self, path: str, header: dict, lower: Callable[[Key], list]):
        self._path, self._header = path, header
        self._seen: set = set()              # keys this life has called
        self._order: List[Key] = []          # those it listed, in order
        self._lock = threading.Lock()
        self._loads: Dict[Key, _Load] = {k: _Load() for k in read_list(path, header)}
        # held while the replay runs and no longer: it leads back to the
        # cache, whose state would otherwise wait for the cycle collector
        self._lower = lower if self._loads else None
        self._wanted: Optional[Key] = None   # a caller waits for this one
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._said = False
        self._t0 = self._t_last = tracing.now_us()
        self.n = {"listed": 0, "loaded": 0, "failed": 0,
                  "ready_at_first_call": 0, "waited_us": 0}
        if self._loads:
            self._thread = threading.Thread(
                target=self._replay, name="programs-ahead", daemon=True)
            self._thread.start()

    # ------------------------------------------------------------ the gate
    def run(self, key: Key, fn: Callable, *args, **kwargs):
        """`fn(*args, **kwargs)`, the jitted program of `key`. A seen key
        costs a set lookup; a first call waits for the key's load if one is
        under way, and lists the key."""
        if key in self._seen:
            return fn(*args, **kwargs)
        load = self._loads.get(key)
        if load is not None:
            if load.done.is_set():
                self.n["ready_at_first_call"] += 0 if load.failed else load.programs
            else:
                self._wanted = key
                t_ask = time.perf_counter()
                load.done.wait()
                self.n["waited_us"] += int(1e6 * (time.perf_counter() - t_ask))
        # the jitted function's count of executables it has called: it
        # grows iff this is the process's first call of the program
        programs = fn._cache_size()
        out = fn(*args, **kwargs)
        self._seen.add(key)
        if fn._cache_size() > programs:
            self._record(key)
        self._say_once()
        return out

    def _record(self, key: Key) -> None:
        with self._lock:
            self._order.append(key)
            lines = [self._header] + [list(k) for k in self._order]
            tmp = f"{self._path}.{os.getpid()}"
            try:   # jax makes the directory at its first write: maybe not yet
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                with open(tmp, "w") as f:
                    f.write("".join(json.dumps(row) + "\n" for row in lines))
                # the stamp first: jax's eviction reads one for every
                # `*-cache` it finds (`jax/_src/lru_cache.py`)
                with open(self._path[:-len("cache")] + "atime", "wb") as f:
                    f.write(time.time_ns().to_bytes(8, "little"))
                os.replace(tmp, self._path)
            except OSError as e:   # a list is a saving, never a need
                print(f"[programs] {self._path} not written: {e}", file=sys.stderr)

    # ---------------------------------------------------------- the replay
    def _replay(self) -> None:
        tracing.thread_compiles(ahead=True)
        pending = list(self._loads)
        while pending and not self._stop:
            key = self._wanted if self._wanted in pending else pending[0]
            pending.remove(key)
            load = self._loads[key]
            try:
                lowered = self._lower(key)
            except Exception as e:  # noqa: BLE001 - whatever tracing raises
                self._finish(load, f"{key} does not lower: {e!r}")
                continue
            load.left = load.programs = len(lowered)
            notes = tracing.compile_notes()
            for program in lowered:
                reading = main_thread.submit(self._read, load, program, notes)
                if reading is None:
                    self._finish(load)   # lowered: the caller reads it
                else:   # a main thread that left reads nothing: nobody waits
                    reading.add_done_callback(
                        lambda f, load=load: f.cancelled() and self._abandon(load))
        self._lower = None
        for key in pending:       # stopped: their callers go the plain way
            self._abandon(self._loads[key])
        self._say_once()

    def _read(self, load: _Load, program, notes) -> None:
        """On the lent main thread: the cache read of one lowered program."""
        if self._stop:
            return self._abandon(load)
        tracing.thread_compiles(notes, ahead=True)
        try:
            program.compile()
            self._finish(load)
        except Exception as e:  # noqa: BLE001
            self._finish(load, f"a program does not load: {e!r}")
        finally:
            tracing.thread_compiles()

    def _finish(self, load: _Load, error: Optional[str] = None) -> None:
        """One program of `load` is loaded, or (`error`) will not be."""
        with self._lock:
            self.n["listed"] += 1
            self.n["failed" if error else "loaded"] += 1
            load.failed |= bool(error)
            load.left -= 1
            self._t_last = tracing.now_us()
            if load.left <= 0:
                load.done.set()
        if error:
            print(f"[programs] skipped, the call will compile it: {error[:400]}",
                  file=sys.stderr)

    @staticmethod
    def _abandon(load: _Load) -> None:
        """A replay that was stopped: the entry's caller goes the plain way."""
        load.failed = True
        load.done.set()

    def _say_once(self) -> None:
        """`programs.ahead`, once a life that replayed a list: when every
        load has ended and every listed key has been called, or at `close`."""
        with self._lock:
            if self._said or not self._loads:
                return
            if not self._stop and not all(
                    l.done.is_set() and (k in self._seen or l.failed)
                    for k, l in self._loads.items()):
                return
            self._said = True
            wall = self._t_last - self._t0
            tracing.add_complete("programs.ahead", "compile", self._t0, wall,
                                 wall_us=int(wall), **self.n)

    def close(self) -> None:
        """Stops the replay where it stands and joins its threads."""
        self._stop = True
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._say_once()
