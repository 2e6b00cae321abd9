"""Out-of-core per-client state store (DESIGN.md §11).

Every algorithm with persistent per-client state — Scaffold/FedDyn control
variates, FedComLoc's EF memory, LoCoDL's per-client iterates — used to
stack that state ``(n_clients, ...)`` on device, capping the simulated
population at what fits in device memory.  This module owns the state
instead, behind one cohort-row contract every ``_round_impl`` writes
against:

* ``init_slot(name, template, n_clients, init)`` — allocate a named slot
  at algorithm ``init`` time; the returned value is what the algorithm
  puts in its state NamedTuple;
* ``gather(name, slot, idx)`` — the sampled cohort's rows, on device, at
  round start;
* ``scatter(name, slot, idx, rows, ctx)`` — write the cohort's updated
  rows back at round end; returns the new slot value for the next state.

Two backends:

* :class:`InMemoryStore` (the default) — the slot IS the stacked device
  array; ``gather``/``scatter`` emit *exactly* the gather/scatter ops the
  round bodies used to inline (``t[idx]`` / ``ctx.scatter_rows``), so the
  in-memory path is bit-identical to the historical stacked-state
  behaviour, works under every §6/§9 mesh, and checkpoints through the
  state tree unchanged.

* :class:`HostStore` — rows live host-side in numpy buffers (optionally
  ``np.memmap`` files under ``mmap_dir``, so the population can exceed
  host RAM too); the slot is an int32 *version token* and
  ``gather``/``scatter`` cross the jit boundary through **ordered**
  ``io_callback``\\ s, which sequence correctly inside the fused
  ``lax.scan`` engine (scatter of round r happens-before gather of round
  r+1).  Buffers are **lazily materialised**: allocation writes one fill
  row plus a ``touched`` bitmap, and a gather reads only rows previously
  scattered (everything else is served from the fill row) — so a
  million-client slot that has only ever seen 64-client cohorts costs
  64·rounds rows of host memory, not ``n_clients`` (``init="broadcast"``
  — LoCoDL's ``xs`` — never materialises the broadcast at all).  Device
  memory holds cohort rows only.  The backend is host-side by nature and
  cannot run inside ``shard_map`` meshes (``RoundEngine.use_mesh``
  rejects the combination).

``HostStore(prefetch=True)`` (DESIGN.md §12) takes the host off the
critical path.  The plain store does all row movement *inside* the
ordered callbacks, serializing host I/O against device compute.  With
prefetching, a background worker owns the buffers between rounds:

* **write-behind scatter** — the scatter callback only copies the
  cohort's rows and enqueues them; the worker applies them to the
  buffers (and memmap files) while the device runs the next round's
  compute.  This also removes the large-buffer writes from the XLA
  callback thread, where they can deadlock the single-threaded CPU
  runtime (see :func:`_disable_async_dispatch`);
* **cohort prefetch** — ``submit_cohort_plan`` hands the store the
  round-by-round cohort index schedule (the engine derives it from the
  key chain before launching the scan); after applying round t's
  scatter for a slot, the worker immediately stages round t+1's rows,
  so the gather callback usually just hands over a staged buffer;
* **hazard rules** — the ordered callbacks remain the commit point: a
  gather that misses the staging buffer (mispredicted plan) drains the
  write-behind queue (a *flush stall*) and reads synchronously; a
  scatter whose index set overlaps a staged entry invalidates it (a
  *RAW hazard*); a stage that raced an apply to the same slot is
  discarded unpublished.  Every served row therefore equals what the
  plain store would have read at the same point in the ordered-effect
  sequence — the pipelined store is **bit-identical** to the plain one
  (the plan is purely a performance hint).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

INIT_MODES = ("zeros", "broadcast")


class ClientStore:
    """The cohort-row contract round implementations write against."""

    #: True if gather/scatter cross the jit boundary via host callbacks —
    #: incompatible with shard_map meshes (RoundEngine.use_mesh checks).
    host_side: bool = False

    def init_slot(self, name: str, template: PyTree, n_clients: int,
                  init: str = "zeros") -> PyTree:
        raise NotImplementedError

    def gather(self, name: str, slot: PyTree, idx: jax.Array) -> PyTree:
        raise NotImplementedError

    def scatter(self, name: str, slot: PyTree, idx: jax.Array,
                rows: PyTree, ctx) -> PyTree:
        raise NotImplementedError


class InMemoryStore(ClientStore):
    """Stacked-device-array backend: the slot is the ``(n, ...)`` tree.

    Every method emits exactly the op the round bodies historically
    inlined, so this backend reproduces the pre-store graphs (and hence
    trajectories, goldens, and checkpoints) byte-for-byte.
    """

    def init_slot(self, name: str, template: PyTree, n_clients: int,
                  init: str = "zeros") -> PyTree:
        if init not in INIT_MODES:
            raise ValueError(f"init must be one of {INIT_MODES}")
        if init == "broadcast":
            return jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p, (n_clients,) + p.shape),
                template)
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros((n_clients,) + p.shape, p.dtype), template)

    def gather(self, name: str, slot: PyTree, idx: jax.Array) -> PyTree:
        return jax.tree_util.tree_map(lambda t: take_rows(t, idx), slot)

    def scatter(self, name: str, slot: PyTree, idx: jax.Array,
                rows: PyTree, ctx) -> PyTree:
        return ctx.scatter_rows(slot, idx, rows)


def take_rows(t: jax.Array, idx: jax.Array) -> jax.Array:
    """``t[idx]`` as one dynamic slice per index, in a single loop.

    The same rows, bit for bit.  A plain gather of whole model-sized rows
    is split by the TPU compiler into one loop per ~400-row chunk of the
    row (~1,400 loops for a qwen2-0.5b control-variate tree), which
    dominated the round's compile time; this is one loop per leaf."""
    return jax.lax.map(
        lambda i: jax.lax.dynamic_index_in_dim(t, i, keepdims=False), idx)


def _disable_async_dispatch() -> None:
    """Ordered host callbacks + JAX's async CPU dispatch can deadlock.

    On CPU, jax dispatches computations asynchronously on a background
    thread; a program with several ordered ``io_callback``\\ s moving
    large buffers can then deadlock inside the runtime (readily reproduced
    on 1-core hosts under jax 0.4.37 — the first such program hangs
    forever, racily).  Synchronous dispatch is the documented remedy and
    costs nothing here: every HostStore round already round-trips to the
    host, so there is no dispatch pipeline left to overlap.  Flipped once,
    at first HostStore construction, so in-memory runs keep the default.
    """
    jax.config.update("jax_cpu_enable_async_dispatch", False)


@dataclasses.dataclass
class _HostSlot:
    """One named slot's host-side storage."""

    leaves: List[np.ndarray]          # (n, ...) buffers (numpy or memmap)
    fill: List[np.ndarray]            # one (...) fill row per leaf
    touched: np.ndarray               # (n,) bool — rows ever scattered
    treedef: Any
    row_structs: List[jax.ShapeDtypeStruct]
    # write fds for memmap leaves (None per RAM leaf): scatters go through
    # pwrite into the backing file — page-cache-coherent with the mapping,
    # but ONE syscall per row instead of a storm of first-touch page
    # faults, each of which can park the writing thread behind a spinning
    # compute thread for a full timeslice (measured 1000x slower on a
    # busy single-core host)
    fds: List[Optional[int]] = dataclasses.field(default_factory=list)


class HostStore(ClientStore):
    """Host-memory (optionally memory-mapped) backend.

    ``mmap_dir`` spools each leaf buffer to a ``np.memmap`` file under
    that directory (created sparse — untouched rows cost no disk), so the
    population can exceed host RAM as well as device memory.

    ``prefetch=True`` adds the §12 pipelining layer: write-behind
    scatters and plan-driven cohort prefetch on a background worker,
    bit-identical to the plain store (see the module docstring for the
    hazard rules).  The engine feeds the plan via
    :meth:`submit_cohort_plan`; without a plan the store still benefits
    from write-behind alone.
    """

    host_side = True

    def __init__(self, mmap_dir: Optional[str | Path] = None, *,
                 prefetch: bool = False):
        _disable_async_dispatch()
        self._mmap_dir = Path(mmap_dir) if mmap_dir is not None else None
        self._slots: Dict[str, _HostSlot] = {}
        self.prefetch = bool(prefetch)
        # host-side telemetry for benchmarks: rows/bytes actually moved,
        # pipeline health, and wall-seconds per phase (gather/scatter are
        # critical-path callback time; apply/prefetch run on the worker)
        self.bytes_gathered = 0
        self.bytes_scattered = 0
        self.rows_gathered = 0
        self.rows_scattered = 0
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self.flush_stalls = 0
        self.raw_hazards = 0
        self.phase_seconds = {"gather": 0.0, "scatter": 0.0,
                              "apply": 0.0, "prefetch": 0.0}
        # pipeline state (prefetch mode): all mutated under _cond
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._pending = 0
        self._staged: Dict[str, tuple] = {}      # name -> (idx, leaves)
        self._plan: Optional[List[np.ndarray]] = None
        self._next_stage: Dict[str, int] = {}
        self._apply_seq: Dict[str, int] = {}
        self._worker: Optional[threading.Thread] = None
        self._worker_error: Optional[BaseException] = None

    def telemetry(self) -> dict:
        """All counters as one flat dict (benchmark artifact rows)."""
        out = {k: getattr(self, k) for k in (
            "rows_gathered", "rows_scattered", "bytes_gathered",
            "bytes_scattered", "prefetch_hits", "prefetch_misses",
            "flush_stalls", "raw_hazards")}
        out.update({f"{k}_seconds": round(v, 6)
                    for k, v in self.phase_seconds.items()})
        return out

    # -- allocation ------------------------------------------------------ #

    def _alloc(self, name: str, i: int, shape, dtype):
        if self._mmap_dir is None:
            # calloc'd pages: untouched rows stay zero-page-backed, and
            # the touched bitmap keeps gathers from ever faulting them in
            return np.zeros(shape, dtype), None
        self._mmap_dir.mkdir(parents=True, exist_ok=True)
        path = self._mmap_dir / f"{name}.leaf_{i}.mm"
        buf = np.memmap(path, dtype=dtype, mode="w+", shape=shape)
        # writes go through this fd (pwrite), reads through the mapping —
        # the Linux page cache keeps the two coherent (see _HostSlot.fds)
        return buf, os.open(path, os.O_WRONLY)

    def init_slot(self, name: str, template: PyTree, n_clients: int,
                  init: str = "zeros") -> jax.Array:
        if init not in INIT_MODES:
            raise ValueError(f"init must be one of {INIT_MODES}")
        leaves, treedef = jax.tree_util.tree_flatten(template)
        bufs, fds, fills, structs = [], [], [], []
        for i, leaf in enumerate(leaves):
            leaf = np.asarray(leaf)
            buf, fd = self._alloc(name, i, (n_clients,) + leaf.shape,
                                  leaf.dtype)
            bufs.append(buf)
            fds.append(fd)
            # the fill row serves every never-scattered gather, so a
            # "broadcast" init never writes n_clients copies of the model
            fills.append(leaf.copy() if init == "broadcast"
                         else np.zeros(leaf.shape, leaf.dtype))
            structs.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
        self._slots[name] = _HostSlot(
            leaves=bufs, fill=fills,
            touched=np.zeros((n_clients,), bool),
            treedef=treedef, row_structs=structs, fds=fds)
        # the slot value is a version token: an int32 the scatter bumps,
        # giving the state tree a real (checkpointable) leaf and the
        # engine's scan carry a data dependence on top of the ordered-
        # effect sequencing
        return jnp.zeros((), jnp.int32)

    # -- host-side row movement ------------------------------------------ #

    def _gather_host(self, name: str, idx: np.ndarray) -> List[np.ndarray]:
        slot = self._slots[name]
        idx = np.asarray(idx)
        t = slot.touched[idx]
        out = []
        for buf, fill in zip(slot.leaves, slot.fill):
            rows = np.empty((idx.shape[0],) + fill.shape, fill.dtype)
            # read ONLY previously-scattered rows: untouched rows come
            # from the fill row without faulting buffer pages in
            rows[:] = fill
            if t.any():
                rows[t] = buf[idx[t]]
            out.append(rows)
            self.bytes_gathered += rows.nbytes
        return out

    def _scatter_host(self, name: str, idx: np.ndarray,
                      leaves: List[np.ndarray]) -> None:
        slot = self._slots[name]
        idx = np.asarray(idx)
        for buf, fd, rows in zip(slot.leaves, slot.fds, leaves):
            if fd is None:
                buf[idx] = rows
            else:
                # memmap leaf: pwrite through the fd instead of storing
                # through the mapping.  A store into a fresh mapped page
                # takes a minor fault; on a busy single-core host each
                # fault can deschedule this thread behind a spinning
                # compute thread for a whole timeslice (~1000x slowdown,
                # measured).  pwrite lands in the same page cache the
                # mapping reads from, so gathers stay coherent.
                row_bytes = buf.dtype.itemsize * int(
                    np.prod(buf.shape[1:], dtype=np.int64))
                flat = np.ascontiguousarray(
                    rows, dtype=buf.dtype).reshape(idx.shape[0], -1)
                for k in range(idx.shape[0]):
                    os.pwrite(fd, flat[k], int(idx[k]) * row_bytes)
            self.bytes_scattered += rows.nbytes
        slot.touched[idx] = True

    # -- pipeline worker (prefetch mode) --------------------------------- #

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._worker_loop, name="hoststore-pipeline",
                daemon=True)
            self._worker.start()

    def _worker_loop(self) -> None:
        while True:
            with self._cond:
                while not self._queue:
                    self._cond.wait()
                op = self._queue.popleft()
            try:
                if self._worker_error is None:
                    if op[0] == "apply":
                        t0 = time.perf_counter()
                        _, name, idx, leaves = op
                        self._scatter_host(name, idx, leaves)
                        self.phase_seconds["apply"] += (
                            time.perf_counter() - t0)
                        self._do_stage(name)
                    else:                      # ("stage", name)
                        self._do_stage(op[1])
            except BaseException as e:         # surfaced by the callbacks
                with self._cond:
                    self._worker_error = e
            finally:
                with self._cond:
                    self._pending -= 1
                    self._cond.notify_all()

    def _do_stage(self, name: str) -> None:
        """Read the slot's next planned cohort into the staging buffer.

        The read runs without the lock (the worker is the only buffer
        writer, and sync reads in the gather callback only happen after
        the queue drains); the result is published under the lock and
        discarded if an apply to the same slot raced past it.
        """
        with self._cond:
            if self._plan is None:
                return
            j = self._next_stage.get(name, len(self._plan))
            if j >= len(self._plan):
                return
            idx = self._plan[j]
            self._next_stage[name] = j + 1
            seq0 = self._apply_seq.get(name, 0)
        t0 = time.perf_counter()
        leaves = self._gather_host(name, idx)
        with self._cond:
            if self._apply_seq.get(name, 0) == seq0:
                self._staged[name] = (idx, leaves)
            self.phase_seconds["prefetch"] += time.perf_counter() - t0

    def _raise_worker_error(self) -> None:
        if self._worker_error is not None:
            err = self._worker_error
            raise RuntimeError(
                "HostStore pipeline worker failed") from err

    def flush(self) -> None:
        """Barrier: wait until every write-behind scatter has been
        applied and every queued stage has landed.  Re-raises worker
        errors.  No-op on a plain store."""
        with self._cond:
            while self._pending and self._worker_error is None:
                self._cond.wait()
        self._raise_worker_error()

    def submit_cohort_plan(self, cohorts: Sequence[np.ndarray]) -> None:
        """Hand the store the upcoming rounds' cohort index schedule.

        ``cohorts[t]`` is the (s,) client-index array the engine expects
        round t to gather/scatter.  The plan is a performance hint only:
        a mispredicted entry costs a prefetch miss (sync fallback), never
        a wrong row.  Replaces any previous plan; flushes first so stale
        staged rows cannot survive a re-plan.
        """
        if not self.prefetch:
            return
        self.flush()
        self._ensure_worker()
        with self._cond:
            self._staged.clear()
            self._plan = [np.asarray(c) for c in cohorts]
            self._next_stage = {name: 0 for name in self._slots}
            for name in self._slots:
                self._queue.append(("stage", name))
                self._pending += 1
            self._cond.notify_all()

    # -- the in-graph contract ------------------------------------------- #

    def gather(self, name: str, slot: jax.Array, idx: jax.Array) -> PyTree:
        from jax.experimental import io_callback
        hs = self._slots[name]
        s = idx.shape[0]
        shapes = [jax.ShapeDtypeStruct((s,) + r.shape, r.dtype)
                  for r in hs.row_structs]

        def cb(idx_h, _token):
            t0 = time.perf_counter()
            try:
                if not self.prefetch:
                    return tuple(self._gather_host(name, idx_h))
                self._raise_worker_error()
                idx_np = np.asarray(idx_h)
                with self._cond:
                    entry = self._staged.get(name)
                    if entry is not None and np.array_equal(entry[0],
                                                            idx_np):
                        del self._staged[name]
                        self.prefetch_hits += 1
                        return tuple(entry[1])
                    if self._pending:
                        # a planned stage (or a preceding write-behind
                        # scatter this gather must observe) is still in
                        # flight: drain, then retry the staging buffer
                        self.flush_stalls += 1
                        while (self._pending
                               and self._worker_error is None):
                            self._cond.wait()
                        entry = self._staged.get(name)
                        if entry is not None and np.array_equal(
                                entry[0], idx_np):
                            del self._staged[name]
                            self.prefetch_hits += 1
                            return tuple(entry[1])
                self._raise_worker_error()
                self.prefetch_misses += 1
                return tuple(self._gather_host(name, idx_np))
            finally:
                self.rows_gathered += int(s)
                self.phase_seconds["gather"] += time.perf_counter() - t0

        rows = io_callback(cb, tuple(shapes), idx, slot, ordered=True)
        return jax.tree_util.tree_unflatten(hs.treedef, list(rows))

    def scatter(self, name: str, slot: jax.Array, idx: jax.Array,
                rows: PyTree, ctx) -> jax.Array:
        from jax.experimental import io_callback
        leaves, treedef = jax.tree_util.tree_flatten(rows)
        hs = self._slots[name]
        if treedef != hs.treedef:
            raise ValueError(
                f"scatter to slot {name!r} with mismatched tree structure")

        def cb(idx_h, *leaves_h):
            t0 = time.perf_counter()
            try:
                if not self.prefetch:
                    self._scatter_host(name, idx_h, list(leaves_h))
                    return np.zeros((), np.int32)
                self._raise_worker_error()
                self._ensure_worker()
                # write-behind: copy (the runtime may reuse the callback
                # operands) and enqueue; the worker applies + restages
                idx_np = np.array(idx_h, copy=True)
                copies = [np.array(l, copy=True) for l in leaves_h]
                with self._cond:
                    entry = self._staged.get(name)
                    if (entry is not None
                            and np.intersect1d(entry[0], idx_np).size):
                        # RAW hazard: staged rows predate this write
                        del self._staged[name]
                        self.raw_hazards += 1
                    self._apply_seq[name] = (
                        self._apply_seq.get(name, 0) + 1)
                    self._queue.append(("apply", name, idx_np, copies))
                    self._pending += 1
                    self._cond.notify_all()
                return np.zeros((), np.int32)
            finally:
                self.rows_scattered += int(idx_h.shape[0])
                self.phase_seconds["scatter"] += time.perf_counter() - t0

        io_callback(cb, jax.ShapeDtypeStruct((), jnp.int32), idx, *leaves,
                    ordered=True)
        return slot + 1

    def __del__(self):
        for slot in getattr(self, "_slots", {}).values():
            for fd in slot.fds:
                if fd is not None:
                    try:
                        os.close(fd)
                    except OSError:
                        pass

    # -- persistence (checkpoint-resume) --------------------------------- #

    def state_dict(self) -> dict:
        """The store's full host state as one nested-dict pytree, ready
        for ``repro.checkpoint.save``.  Buffers are materialised dense —
        checkpointing is for resumable *experiments*, not for spooling a
        million-client population (keep ``mmap_dir`` for that).  Flushes
        the write-behind queue first, so a mid-pipeline checkpoint
        captures every committed scatter."""
        self.flush()
        out = {}
        for name, slot in self._slots.items():
            out[name] = {
                "touched": slot.touched.copy(),
                "fill": {f"leaf_{i}": f.copy()
                         for i, f in enumerate(slot.fill)},
                "data": {f"leaf_{i}": np.asarray(buf).copy()
                         for i, buf in enumerate(slot.leaves)},
            }
        return out

    def load_state_dict(self, d: dict) -> None:
        """Restore buffers saved by :meth:`state_dict` into the slots
        registered by ``init_slot`` (call the algorithm's ``init`` first —
        it defines the slot names/shapes this fills).  Drops any staged
        rows and cohort plan — they described the pre-restore timeline."""
        self.flush()
        with self._cond:
            self._staged.clear()
            self._plan = None
            self._next_stage = {}
        for name, payload in d.items():
            if name not in self._slots:
                raise KeyError(
                    f"state_dict slot {name!r} was never registered; call "
                    "the algorithm's init() before load_state_dict()")
            slot = self._slots[name]
            slot.touched[:] = np.asarray(payload["touched"])
            for i in range(len(slot.leaves)):
                slot.fill[i][...] = np.asarray(payload["fill"][f"leaf_{i}"])
                slot.leaves[i][...] = np.asarray(payload["data"][f"leaf_{i}"])


def resolve_store(store: Optional[ClientStore]) -> ClientStore:
    """Default + type-check the ``store=`` argument every algorithm takes."""
    if store is None:
        return InMemoryStore()
    if not isinstance(store, ClientStore):
        raise TypeError(
            f"store must be a ClientStore, got {type(store).__name__}")
    return store
