"""DataLoader (ref: python/mxnet/gluon/data/dataloader.py:23-73).

The reference forks worker processes and passes batches back through
POSIX shared memory (its CPUSharedStorageManager role): workers run
``dataset[i]`` + batchify, the parent receives only small descriptors
and maps the batch bytes out of ``/dev/shm``.  Same design here:

* ``num_workers=0``  — synchronous loading in the caller (reference
  parity).
* ``num_workers>0``, ``thread_pool=True`` — thread workers.  No
  pickling and zero setup cost; right when transforms release the GIL
  (numpy/cv2) or the bottleneck is host->HBM transfer anyway.
* ``num_workers>0`` (default) — forked worker *processes*.  Batches
  come back as ``multiprocessing.shared_memory`` segments (one memcpy
  from ``/dev/shm`` into the jax staging buffer), so Python-level
  transforms scale past the GIL exactly like the reference's
  process workers.

.. note:: migration
   Earlier rounds defaulted ``num_workers>0`` to *threads*; processes
   are now the default (reference parity).  Custom ``batchify_fn``s
   that build NDArrays must stay numpy-only under processes (an error
   is raised when an accelerator is live); pass ``thread_pool=True``
   to keep the previous thread-based behavior unchanged.

Workers deliberately touch only numpy: forking a process that has
already initialized an accelerator backend is only safe if the child
never re-enters that runtime, so batchify inside workers produces
numpy arrays and the parent promotes them to NDArray.
"""
import concurrent.futures as _futures
import multiprocessing as _mp
import os
import warnings
from multiprocessing import shared_memory as _shm

import numpy as np

from ...ndarray import array as nd_array
from ...ndarray.ndarray import NDArray
from ...resilience import DataPipelineError, inject
from ...utils.concurrent import bounded_window as _bounded_window
from ...utils.env import get_env
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn"]

_SHM_PREFIX = "mxtpu_dl_"


def _sweep_segments(prefix):
    """Unlink every /dev/shm segment under ``prefix`` (leaked by a
    dead worker or an abandoned iteration); returns the count."""
    import glob as _glob
    removed = 0
    for path in _glob.glob("/dev/shm/" + prefix + "*"):
        try:
            os.unlink(path)
            removed += 1
        except OSError:
            pass
    return removed


def default_batchify_fn(data):
    """Stack samples into a batch (ref: dataloader.py default_batchify)."""
    if isinstance(data[0], NDArray):
        return nd_array(np.stack([d.asnumpy() for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(list(i)) for i in data]
    data = np.asarray(data)
    return nd_array(data)


def _numpy_batchify_fn(data):
    """default_batchify_fn that stays in numpy — run inside workers."""
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [_numpy_batchify_fn(list(i)) for i in data]
    if isinstance(data[0], NDArray):
        _check_fork_safe_ndarray()
        return np.stack([d.asnumpy() for d in data])
    return np.asarray(data)


def _check_fork_safe_ndarray():
    """NDArray samples force the forked child back into the device
    runtime — only safe when the parent's backend is host CPU."""
    if _worker_accel:
        raise RuntimeError(
            "dataset samples are NDArrays but an accelerator backend "
            "is initialized: a forked DataLoader worker cannot touch "
            "the device. Return numpy from the dataset (transform on "
            "host), or use thread_pool=True / num_workers=0.")


def _accel_backend_initialized():
    """True iff an accelerator backend is ALREADY live in this
    process.  Must never initialize one (probing via
    jax.default_backend() would itself claim the device and spawn the
    runtime threads whose post-fork use the flag exists to prevent);
    an uninitialized jax is fork-safe by definition.  No public call
    answers this without initializing a backend."""
    from jax._src import xla_bridge as _xb
    return any(p != "cpu" for p in _xb._backends)


def _dtype_from_name(name):
    """dtype.name round-trip that also covers ml_dtypes extension
    dtypes (bfloat16, fp8...), whose .str is an opaque void code."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _tracker_unregister(name):
    """Keep the resource_tracker out of segment lifetime accounting.

    Segment ownership crosses the worker/parent boundary (worker
    creates, parent unlinks), which the per-process tracker cannot
    model — left registered it both double-unlinks and warns at exit.
    """
    try:
        from multiprocessing import resource_tracker
        resource_tracker.unregister("/" + name.lstrip("/"),
                                    "shared_memory")
    except Exception:
        pass


def _to_shm(obj, prefix):
    """Recursively move numpy payloads into shared-memory descriptors."""
    if isinstance(obj, NDArray):          # custom batchify may produce
        _check_fork_safe_ndarray()
        inner = _to_shm(obj.asnumpy(), prefix)
        if inner[0] == "np":
            return ("nd",) + inner[1:]
        return ("ndpy", inner[1])         # zero-size: carried inline
    if isinstance(obj, np.ndarray) and obj.nbytes > 0:
        arr = np.ascontiguousarray(obj)
        seg = _shm.SharedMemory(
            create=True, size=arr.nbytes,
            name=prefix + os.urandom(8).hex())
        # the parent unlinks; unregister here so the worker-side
        # tracker does not also try to (unlink() re-unregisters)
        _tracker_unregister(seg.name)
        view = np.frombuffer(seg.buf, dtype=arr.dtype).reshape(arr.shape)
        view[...] = arr
        del view                        # release the buffer export
        name = seg.name
        seg.close()
        return ("np", name, arr.shape, arr.dtype.name)
    if isinstance(obj, (list, tuple)):
        return ("seq", type(obj) is tuple,
                [_to_shm(o, prefix) for o in obj])
    return ("py", obj)


def _from_shm(desc):
    """Parent side: map descriptors back; one memcpy out of /dev/shm.

    Attaching registers the name with the parent's resource tracker
    and ``unlink()`` unregisters it, so no manual tracker bookkeeping
    is needed here.
    """
    tag = desc[0]
    if tag in ("np", "nd"):
        _, name, shape, dtype = desc
        seg = _shm.SharedMemory(name=name)
        try:
            arr = np.frombuffer(
                seg.buf, dtype=_dtype_from_name(dtype)).reshape(shape)
            out = arr.copy()        # never alias the shm page: jax's
            del arr                 # CPU device_put may zero-copy
        finally:
            seg.close()
            seg.unlink()
        return nd_array(out) if tag == "nd" else out
    if tag == "ndpy":
        return nd_array(desc[1])
    if tag == "seq":
        _, is_tuple, items = desc
        items = [_from_shm(i) for i in items]
        return tuple(items) if is_tuple else items
    return desc[1]


def _promote(obj):
    """numpy → NDArray, preserving the default-batchify list shape."""
    if isinstance(obj, np.ndarray):
        return nd_array(obj)
    if isinstance(obj, list):
        return [_promote(o) for o in obj]
    if isinstance(obj, tuple):
        return tuple(_promote(o) for o in obj)
    return obj


_worker_dataset = None
_worker_batchify = None
_worker_prefix = None
_worker_accel = False


def _worker_init(dataset, batchify_fn, prefix, accel):
    global _worker_dataset, _worker_batchify, _worker_prefix, \
        _worker_accel
    _worker_dataset = dataset
    _worker_batchify = batchify_fn
    _worker_prefix = prefix
    _worker_accel = accel


def _worker_fn(indices, token):
    """Build one batch under a per-task shm prefix: when the parent
    declares this task lost (worker died holding it), it can sweep
    exactly this task's segments — completed batches from the same
    worker keep theirs."""
    inject("dataloader", "worker")
    batch = _worker_batchify([_worker_dataset[i] for i in indices])
    return _to_shm(batch, _worker_prefix + token + "_")


class DataLoader:
    """(ref: dataloader.py DataLoader)"""

    def __init__(self, dataset, batch_size=None, shuffle=False,
                 sampler=None, last_batch=None, batch_sampler=None,
                 batchify_fn=None, num_workers=0, thread_pool=False):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size required unless batch_sampler given")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle and sampler are exclusive")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._batches_served = 0
        self._epoch_rng = None
        self._resume = None

    # ------------------------------------------------- resumable state
    def state_dict(self):
        """Checkpointable position: batches served this epoch + the
        numpy RNG state snapshotted when the epoch's iteration began
        (the sampler's shuffle source), so a restore replays the same
        sampler order and skips exactly the served batches."""
        if self._resume is not None:
            return dict(self._resume)    # armed but not yet applied
        rng = self._epoch_rng if self._epoch_rng is not None \
            else np.random.get_state()
        return {"type": "DataLoader",
                "batches_served": self._batches_served,
                "epoch_rng": rng}

    def load_state_dict(self, state):
        """Arm a resume: the next ``iter()`` restores the saved RNG
        state, regenerates the identical sampler order, and skips the
        already-served index batches without loading their data."""
        if state.get("type") != "DataLoader":
            raise ValueError(
                f"state_dict type {state.get('type')!r} does not "
                "match DataLoader")
        self._resume = dict(state)

    def _sampler_batches(self, skip):
        for j, idxs in enumerate(self._batch_sampler):
            if j < skip:
                continue
            yield idxs

    def __iter__(self):
        resume, self._resume = self._resume, None
        if resume is not None:
            np.random.set_state(resume["epoch_rng"])
            skip = int(resume["batches_served"])
        else:
            skip = 0
        self._epoch_rng = np.random.get_state()
        self._batches_served = skip
        batches = self._sampler_batches(skip)
        batchify = self._batchify_fn or default_batchify_fn
        if self._num_workers == 0:
            for batch in batches:
                out = batchify([self._dataset[i] for i in batch])
                self._batches_served += 1
                yield out
            return
        if self._thread_pool:
            with _futures.ThreadPoolExecutor(self._num_workers) as pool:
                def submit(idxs):
                    return pool.submit(
                        lambda: batchify(
                            [self._dataset[i] for i in idxs]))
                for fut in _bounded_window(
                        batches, submit, 2 * self._num_workers):
                    out = fut.result()
                    self._batches_served += 1
                    yield out
            return
        yield from self._iter_multiprocess(batches)

    def _iter_multiprocess(self, batches):
        # fork: the dataset is inherited copy-on-write (no pickling);
        # workers are numpy-only so re-entering an already-initialized
        # accelerator runtime in the child never happens.
        # the NDArray-building default batchify must not run in the
        # forked child (creating jax arrays re-enters the inherited
        # PJRT client, which can deadlock): substitute the numpy
        # equivalent and promote to NDArray in the parent.  Custom
        # batchify fns must themselves stay numpy-only in workers.
        if (self._batchify_fn is None
                or self._batchify_fn is default_batchify_fn):
            worker_batchify, promote = _numpy_batchify_fn, _promote
        else:
            worker_batchify, promote = self._batchify_fn, lambda b: b
        # unique per-iteration segment prefix: in-flight batches whose
        # descriptors never reach the parent (early abandon, crash)
        # are reclaimed by the glob below once the workers are dead
        prefix = "%s%x_%s_" % (_SHM_PREFIX, os.getpid(),
                               os.urandom(4).hex())
        accel = _accel_backend_initialized()
        with warnings.catch_warnings():
            # the at-fork warnings do not apply (the children are
            # numpy-only), but only those two specific warnings are
            # known-benign — anything else about fork must surface
            warnings.filterwarnings(
                "ignore", category=RuntimeWarning,
                message=r"os\.fork\(\) was called\.")
            warnings.filterwarnings(
                "ignore", category=DeprecationWarning,
                message=r"This process .* is multi-threaded")
            pool = _mp.get_context("fork").Pool(
                self._num_workers, initializer=_worker_init,
                initargs=(self._dataset, worker_batchify, prefix,
                          accel))
        try:
            import itertools as _it
            import time as _time
            grace = get_env("MXTPU_DL_DEAD_GRACE")
            max_restarts = get_env("MXTPU_DATA_WORKER_RESTARTS")
            restarts_used = 0
            tokens = _it.count()
            # respawn-generation bookkeeping: a task is only suspect
            # if the worker set changed AFTER it was submitted.  A
            # global "pids look healthy now" snapshot cannot express
            # that (a batch completing after a respawn would reset it
            # and mask an earlier lost task forever).
            known_pids = {w.pid for w in getattr(pool, "_pool", [])}
            respawn_gen = 0

            def _observe_pids():
                nonlocal known_pids, respawn_gen
                pids = {w.pid for w in getattr(pool, "_pool", [])}
                if pids != known_pids:
                    respawn_gen += 1
                    known_pids = pids
                return respawn_gen

            def _submit(idxs):
                # observe at submission: a respawn that happened
                # while no result was being polled must not count
                # against tasks submitted after it
                token = "%x" % next(tokens)
                return (pool.apply_async(_worker_fn, (idxs, token)),
                        _observe_pids(), idxs, token)

            for res, submit_gen, idxs, token in _bounded_window(
                    batches, _submit, 2 * self._num_workers):
                # poll with a timeout: if a worker dies hard (native
                # segfault, OOM-kill), Pool respawns it but the lost
                # task's result never arrives — a bare get() would
                # hang the training loop forever.  A respawn alone is
                # not proof THIS result is lost (the died worker may
                # have held a different task), so a result submitted
                # before the respawn gets a grace window to arrive.
                # A task declared lost has its half-built segments
                # swept and its index batch re-dispatched to the
                # (Pool-respawned) workers, up to the
                # MXTPU_DATA_WORKER_RESTARTS budget.
                deadline = None
                data_timeout = get_env("MXTPU_DATA_TIMEOUT")
                hard_deadline = _time.monotonic() + data_timeout \
                    if data_timeout > 0 else None
                while True:
                    try:
                        desc = res.get(1.0)
                        break
                    except _mp.TimeoutError:
                        if _observe_pids() == submit_gen:
                            # no respawn since (re)submission.  Only
                            # here does the absolute backstop apply —
                            # a pool wedged with no death evidence
                            # (e.g. a worker killed at the worst
                            # moment) must still bound the wait.  A
                            # respawn hands over to the grace +
                            # re-dispatch path below instead, so a
                            # short MXTPU_DATA_TIMEOUT can never
                            # preempt the recovery budget
                            if hard_deadline is not None and \
                                    _time.monotonic() > hard_deadline:
                                raise DataPipelineError(
                                    "DataLoader: no batch arrived "
                                    f"within {data_timeout:g}s "
                                    "(MXTPU_DATA_TIMEOUT); the "
                                    "worker pool is stalled — check "
                                    "dataset __getitem__ for hangs "
                                    "or raise the timeout for slow "
                                    "sources") from None
                            continue
                        if deadline is None:
                            deadline = _time.monotonic() + grace
                            continue
                        if _time.monotonic() <= deadline:
                            continue
                        _sweep_segments(prefix + token + "_")
                        if restarts_used >= max_restarts:
                            raise DataPipelineError(
                                "a DataLoader worker died and its "
                                f"batch never arrived (waited "
                                f"{grace:.0f}s after the respawn, "
                                f"re-dispatched {restarts_used} "
                                "time(s), MXTPU_DATA_WORKER_RESTARTS"
                                f"={max_restarts}); check dataset "
                                "__getitem__/batchify_fn for crashes "
                                "in native code or OOM "
                                "(MXTPU_DL_DEAD_GRACE overrides the "
                                "wait)")
                        restarts_used += 1
                        from ... import telemetry
                        telemetry.counter(
                            "dataloader_worker_restarts_total").inc()
                        warnings.warn(
                            "a DataLoader worker died holding batch "
                            f"{idxs[:4]}{'...' if len(idxs) > 4 else ''}; "
                            "re-dispatching it (restart "
                            f"{restarts_used}/{max_restarts})",
                            RuntimeWarning)
                        token = "%x" % next(tokens)
                        res = pool.apply_async(_worker_fn,
                                               (idxs, token))
                        submit_gen = _observe_pids()
                        deadline = None
                        if hard_deadline is not None:
                            # fresh dispatch, fresh backstop window
                            hard_deadline = _time.monotonic() \
                                + data_timeout
                    except Exception as exc:
                        # a worker that *raised* (vs died): surface
                        # as a typed pipeline failure with the cause
                        raise DataPipelineError(
                            "DataLoader worker raised "
                            f"{type(exc).__name__}: {exc}") from exc
                out = promote(_from_shm(desc))
                self._batches_served += 1
                yield out
        finally:
            pool.terminate()
            pool.join()
            _sweep_segments(prefix)

    def __len__(self):
        return len(self._batch_sampler)
