"""Native (C++) batch assembly: threaded gather + fused image augment.

The reference feeds GPUs through torch DataLoader worker processes doing
decode/augment in native code; the TPU-host equivalent is
``native/prefetch.cpp`` — ctypes calls release the GIL, so one Python
process drives all host cores assembling batches (gather -> random crop ->
flip -> u8->f32 normalize in a single pass with a per-channel LUT), which
is what ImageNet-rate feeding needs (SURVEY.md §7 hard part b).

Randomness stays in Python: ``ImageBatchPipeline`` draws crop/flip
parameters from a seeded generator keyed by the batch indices, so a resumed
run replays identical augmentations.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time
from typing import Callable, Optional, Sequence

import numpy as np

from pytorch_distributed_tpu.utils.logging import get_logger
from pytorch_distributed_tpu.utils.native_build import build_native_library

logger = get_logger(__name__)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SRC = os.path.join(_NATIVE_DIR, "prefetch.cpp")
_SO = os.path.join(_NATIVE_DIR, "libprefetch.so")

_lib: Optional[ctypes.CDLL] = None


def build_library(force: bool = False) -> str:
    """Compile libprefetch.so if missing/stale; returns the path."""
    return build_native_library(
        _SRC, _SO, extra_flags=("-pthread",), force=force
    )


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_library())
        lib.pf_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
        ]
        lib.pf_gather_rows.restype = ctypes.c_int
        lib.pf_image_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.pf_image_batch.restype = ctypes.c_int
        lib.pf_image_batch_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_int, ctypes.c_int,
        ]
        lib.pf_image_batch_u8.restype = ctypes.c_int
        _lib = lib
    return _lib


def _check(rc: int, what: str) -> None:
    if rc != 0:
        raise RuntimeError(f"prefetch {what} failed (rc={rc})")


class HostStagingRing:
    """Rotating pool of reusable host batch buffers.

    The gather/crop hot path used to ``np.empty`` a fresh
    ``(B, crop, crop, C)`` output every batch (~19 MB at bench shapes):
    each allocation is an mmap the kernel must zero-fault in, and the
    munmap on free throws the pages away — pure allocator churn on the
    feed's critical path. The ring hands the same ``depth`` buffers out
    round-robin instead.

    Reuse is only sound if a buffer's previous contents are DONE before
    it is rewritten. Two mechanisms guarantee that:

    * Buffers are allocated deliberately OFF 64-byte alignment. XLA's
      CPU client zero-copy *aliases* 64-byte-aligned numpy arrays in
      ``device_put`` (measured on this jaxlib: the returned Array shares
      the host pointer), which would let a ring rewrite corrupt batches
      still queued in the async dispatch stream. A misaligned source
      forces the eager-copy path, so the put owns its bytes before it
      returns.
    * For real accelerator transfers (which always copy, but
      asynchronously) the ring is fenced: ``DataLoader._place`` calls
      ``register_transfer`` after each put, and ``get`` waits on a
      slot's registered transfer before handing the buffer back out
      (double-buffered: with depth 2, batch N's transfer overlaps batch
      N+1's assembly and is awaited only before batch N+2).

    Thread-safe: one pipeline may feed two DataLoaders whose background
    threads interleave fetches. A buffer is BUSY from ``get`` until its
    transfer is registered (device-fed) or the pipeline finishes
    assembling it (host-fed ``release``); if rotation lands on a busy
    buffer — another thread still assembling into it, or a consumer that
    never proved the copy-out — ``get`` hands back a fresh one-shot
    buffer instead. Reuse therefore only ever happens with proof that
    the previous contents are done.
    """

    def __init__(self, depth: int = 2):
        if depth < 2:
            raise ValueError(f"staging depth must be >= 2, got {depth}")
        import threading

        self.depth = depth
        self._slots = {}  # (shape, dtype) -> (buffers, next_index)
        self._pending = {}  # id(buffer) -> per-shard 0-d sync handles
        self._busy = set()  # id(buffer): handed out, completion unproven
        self._lock = threading.Lock()

    def register_transfer(self, host_arr: np.ndarray, placed) -> None:
        """Record that ``placed`` (a device Array) is an in-flight copy of
        ring buffer ``host_arr``; the next ``get`` that would hand that
        buffer out blocks on the transfer first. No-op for arrays the
        ring does not own (derived/fresh batches).

        If the placed Array turns out to ALIAS the host buffer (XLA CPU
        zero-copy — possible for odd shapes where a shard offset lands
        back on 64-byte alignment despite the unaligned base), the buffer
        is evicted from the ring: it now belongs to the device Array and
        must never be rewritten. The ring allocates a replacement on the
        next get, so reuse is strictly proven-copied buffers.

        What the ring stores is NOT ``placed`` itself but one tiny
        derived scalar per addressable shard, dispatched HERE — before
        the consumer step runs. The trainer donates batch buffers into
        the step on accelerators, which deletes ``placed``'s buffers and
        makes any later ``block_until_ready(placed)`` raise; the scalar
        handles are the ring's own arrays, they depend on every shard's
        H2D copy having landed, and they stay valid through donation.
        """
        with self._lock:
            # ownership by POINTER RANGE, not identity: a loader
            # transform may hand _place a numpy VIEW of a ring buffer
            # (e.g. a reversed slice) — the transfer still reads the
            # buffer's memory and must fence it
            owner_key, owner_buf = self._find_owner(host_arr)
            if owner_buf is None:
                return
            if self._aliases(owner_buf, placed):
                slots, i = self._slots[owner_key]
                slots = [b for b in slots if b is not owner_buf]
                self._slots[owner_key] = (
                    slots, i % self.depth if slots else 0
                )
                self._pending.pop(id(owner_buf), None)
                self._busy.discard(id(owner_buf))
                return
        # dispatch the sync handles OUTSIDE the lock (they may trigger a
        # tiny compile); racing registrations for the same buffer are
        # fine — last writer wins, and its handles still cover the
        # latest transfer
        handles = self._transfer_handles(placed)
        with self._lock:
            self._pending[id(owner_buf)] = handles
            self._busy.discard(id(owner_buf))  # copy-out proven pending

    def _find_owner(self, host_arr: np.ndarray):
        """(key, slot buffer) whose memory contains ``host_arr``'s, or
        (None, None). Caller holds the lock."""
        try:
            start = host_arr.ctypes.data
            end = start + host_arr.nbytes
        except Exception:
            return None, None
        for key, (slots, _) in self._slots.items():
            for b in slots:
                b0 = b.ctypes.data
                if b0 <= start and end <= b0 + b.nbytes:
                    return key, b
        return None, None

    def release(self, bufs) -> None:
        """Host-fed path: the pipeline finished assembling these buffers
        and handed the batch to a synchronous consumer — rotation may
        reuse them (the documented host-fed contract: a batch is valid
        until ``depth - 1`` further fetches)."""
        with self._lock:
            for b in bufs:
                self._busy.discard(id(b))

    @staticmethod
    def _transfer_handles(placed):
        """One 0-d derived array per addressable shard of ``placed``.

        Each scalar read is enqueued against the shard's device buffer
        before any donation can delete it; the scalar being ready
        implies that shard's host->device copy has completed.
        """
        try:
            shards = placed.addressable_shards
        except Exception:  # not a jax Array: nothing to fence
            return []
        handles = []
        for s in shards:
            data = s.data
            handles.append(data[(0,) * data.ndim])
        return handles

    @staticmethod
    def _aliases(host_arr: np.ndarray, placed) -> bool:
        """Does any addressable shard of ``placed`` point into
        ``host_arr``'s memory? False when pointers are unavailable
        (a real accelerator buffer lives in device memory)."""
        start = host_arr.ctypes.data
        end = start + host_arr.nbytes
        try:
            for s in placed.addressable_shards:
                p = s.data.unsafe_buffer_pointer()
                if start <= p < end:
                    return True
        except Exception:
            return False
        return False

    @staticmethod
    def _alloc_unaligned(shape, dtype) -> np.ndarray:
        """An ndarray deliberately 1 element off 64-byte alignment (see
        class docstring: defeats XLA CPU's zero-copy aliasing)."""
        dt = np.dtype(dtype)
        n = int(np.prod(shape)) * dt.itemsize
        raw = np.empty(n + 64 + dt.itemsize, np.uint8)
        off = (-raw.ctypes.data) % 64 + dt.itemsize
        return raw[off:off + n].view(dt).reshape(shape)

    def get(self, shape, dtype) -> np.ndarray:
        """Next buffer for ``(shape, dtype)`` — valid until ``depth - 1``
        further ``get``s of the same key. Blocks until any registered
        in-flight transfer out of the returned buffer has completed; if
        the candidate is still BUSY (another fetch assembling into it,
        or a consumer that never proved the copy-out), falls back to a
        fresh one-shot buffer rather than ever risking a concurrent
        rewrite."""
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            slots, i = self._slots.get(key, ([], 0))
            if len(slots) < self.depth:
                buf = self._alloc_unaligned(shape, dtype)
                slots.append(buf)
                self._slots[key] = (slots, 0)
                self._busy.add(id(buf))
                return buf
            self._slots[key] = (slots, (i + 1) % self.depth)
            buf = slots[i]
            if id(buf) in self._busy:
                return self._alloc_unaligned(shape, dtype)  # one-shot
            self._busy.add(id(buf))
            handles = self._pending.pop(id(buf), None)
        if handles:
            self._wait_transfer(handles)
        return buf

    @staticmethod
    def _wait_transfer(handles) -> None:
        """Block until the device copy out of a ring buffer has landed
        (``handles`` from :meth:`_transfer_handles`). On the CPU backend
        the put already copied eagerly (unaligned source) and this
        returns immediately.
        """
        import jax

        jax.block_until_ready(handles)


class BadSampleBudgetExceeded(RuntimeError):
    """More samples were quarantined than the pipeline's budget allows —
    the dataset (or the storage under it) is damaged beyond "a few rotten
    files", and silently substituting a meaningful fraction of the epoch
    would corrupt the training distribution."""


class SampleQuarantine:
    """Thread-safe registry of samples that failed to read/decode.

    One bad JPEG three hours into an epoch must cost one log line and one
    substituted sample, not the job — but *unbounded* substitution would
    silently train on a different distribution, so crossing ``budget``
    quarantined samples raises :class:`BadSampleBudgetExceeded`. Decode
    pool threads share one instance; re-quarantining a known path is free
    and unlogged (every epoch revisits the same bad files).

    Only PERMANENT rot (undecodable bytes, missing files) is
    quarantined. A transient error that merely outlasted its retries (a
    storage blip longer than the backoff window) is recorded as
    :meth:`note_transient` — the sample is substituted for *this* batch
    but stays eligible for future epochs and does not join the skip set:
    a few seconds of NFS outage across a fanned-out decode pool must not
    permanently evict hundreds of healthy files. Transient substitutions
    still have their own (much larger) ceiling, ``transient_budget``
    (default ``10 * budget``): a disk persistently returning EIO looks
    transient per-event but reshapes the distribution all the same, and
    must eventually be a hard stop too.
    """

    def __init__(self, budget: int = 100, transient_budget: Optional[int] = None):
        if budget < 0:
            raise ValueError(f"bad-sample budget must be >= 0, got {budget}")
        self.budget = int(budget)
        self.transient_budget = (
            10 * self.budget if transient_budget is None
            else int(transient_budget)
        )
        self._paths: set = set()
        self._lock = threading.Lock()
        self.transient_events = 0  # substitutions due to exhausted retries

    def __contains__(self, path: str) -> bool:
        with self._lock:
            return path in self._paths

    def __len__(self) -> int:
        with self._lock:
            return len(self._paths)

    @property
    def paths(self) -> list:
        with self._lock:
            return sorted(self._paths)

    def note_transient(self, path: str, reason: str) -> None:
        """A healthy-looking sample failed transiently past its retries:
        substituted this once, retried next epoch, never quarantined."""
        with self._lock:
            self.transient_events += 1
            count = self.transient_events
        logger.warning(
            "substituting sample %s for this batch after exhausted "
            "transient-I/O retries (%s) — %d transient substitution(s) "
            "so far; the sample stays eligible", path, reason, count,
        )
        if count > self.transient_budget:
            raise BadSampleBudgetExceeded(
                f"{count} transient-substitution events (ceiling "
                f"{self.transient_budget}) — the storage is persistently "
                f"failing, not blinking; latest: {path} ({reason})"
            )

    def add(self, path: str, reason: str) -> None:
        with self._lock:
            if path in self._paths:
                return
            self._paths.add(path)
            count = len(self._paths)
        logger.warning(
            "quarantined unreadable/undecodable sample %s (%s) — "
            "%d bad sample(s) so far (budget %d)",
            path, reason, count, self.budget,
        )
        if count > self.budget:
            raise BadSampleBudgetExceeded(
                f"{count} samples quarantined (budget {self.budget}) — "
                f"latest: {path} ({reason}); the dataset needs repair, "
                f"not more substitution"
            )


def is_transient_io_error(e: BaseException) -> bool:
    """Is retrying this read plausibly useful? Transient: OS-level I/O
    errors (NFS hiccup, EMFILE under pressure) and the ``data.fetch``
    injection site. Permanent: decode failures — a rotted JPEG does not
    get better on the third read, nor does the ``data.decode`` site.

    PIL muddies the classes by raising plain ``OSError`` for damaged
    image DATA too (``UnidentifiedImageError`` for junk headers, bare
    ``OSError("image file is truncated...")`` from the decoder). The
    discriminator is ``errno``: a real I/O failure from the OS carries
    one (EIO, EMFILE, ...); PIL's synthetic decode errors are
    constructed from a message alone and have ``errno is None``. A
    MISSING file (ENOENT/ENOTDIR) is the exception: it carries an errno
    but is permanent damage — a dataset that lost files after indexing
    must hit the quarantine budget, not be silently substituted (and
    retried) forever."""
    import errno as _errno

    from pytorch_distributed_tpu.runtime import faults

    if isinstance(e, faults.InjectedFault):
        return e.site == "data.fetch"
    try:
        from PIL import UnidentifiedImageError
    except Exception:  # pragma: no cover - PIL always present here
        UnidentifiedImageError = ()
    if isinstance(e, UnidentifiedImageError):
        return False
    return (
        isinstance(e, OSError)
        and e.errno is not None
        and e.errno not in (_errno.ENOENT, _errno.ENOTDIR)
    )


def read_with_retries(
    fn: Callable[[], "object"],
    *,
    retries: int = 2,
    backoff_s: float = 0.05,
    max_backoff_s: float = 1.0,
    what: str = "",
):
    """``fn()`` with capped exponential backoff on *transient* errors.

    Permanent errors (undecodable bytes) and exhausted retries propagate
    to the caller — quarantine/substitution policy lives there, not here.
    """
    delay = backoff_s
    for attempt in range(retries + 1):
        try:
            return fn()
        except Exception as e:
            if attempt >= retries or not is_transient_io_error(e):
                raise
            logger.warning(
                "transient read error on %s (attempt %d/%d): %s — "
                "retrying in %.2fs", what or "<sample>", attempt + 1,
                retries + 1, e, delay,
            )
            time.sleep(delay)
            delay = min(delay * 2.0, max_backoff_s)


def _accelerator_backend() -> bool:
    """True when the default jax backend is a real accelerator (H2D
    transfers copy; staging reuse pays). False on the CPU backend, where
    zero-copy aliasing of fresh buffers beats the ring's forced copy."""
    try:
        import jax

        return jax.default_backend() != "cpu"
    except Exception:  # jax not initialized/usable: play it fresh
        return False


class _StagingMixin:
    """Shared staging-ring plumbing for the batch pipelines.

    ``reuse_staging``: True forces the ring, False forces fresh
    allocations, None (default) auto-enables it when a DataLoader marks
    this pipeline device-fed (``sharding`` was passed, so every batch is
    copied out by ``device_put`` under the loader's ring fence before
    the ring wraps) AND the backend is a real accelerator. On the CPU
    backend auto mode stays on fresh buffers: XLA:CPU zero-copy ALIASES
    each aligned fresh batch into the "device" array (no copy at all —
    measured faster than the ring's forced copy), and a never-rewritten
    buffer is safe to alias. On accelerators the transfer genuinely
    copies, so the ring saves the per-batch alloc/page-fault churn.
    Consumers of host batches (no sharding) keep fresh per-batch arrays
    — those batches may live arbitrarily long.

    The device-fed mark is STICKY and per pipeline instance: once any
    sharded DataLoader has wrapped a pipeline, a direct
    ``pipeline(ds, idx)`` call (debug probe, host-fed second loader)
    returns ring buffers that the next fetches will rewrite — copy what
    you need to keep, or use a separate pipeline / ``reuse_staging=
    False`` for host-fed consumption.
    """

    reuse_staging = None
    _staging: Optional[HostStagingRing] = None
    _staging_depth = 2
    _device_fed = False

    def _init_staging(self, reuse_staging) -> None:
        """Call from the pipeline's ``__init__``: eagerly creates the
        per-thread bookkeeping and creation lock so two loaders'
        background threads can't race the first fetch into orphaning
        each other's state."""
        import threading

        self.reuse_staging = reuse_staging
        self._staging_tls = threading.local()
        self._staging_lock = threading.Lock()

    def mark_device_fed(self, depth: int = 2) -> None:
        """DataLoader hook: batches are device_put (copied out) promptly;
        staging reuse with a ring of ``depth`` buffers is safe."""
        self._device_fed = True
        self._staging_depth = max(self._staging_depth, depth)

    @property
    def staging_active(self) -> bool:
        if self.reuse_staging is not None:
            return bool(self.reuse_staging)
        return self._device_fed and _accelerator_backend()

    @property
    def staging_depth(self) -> int:
        return self._staging_depth

    @property
    def staging_ring(self) -> Optional[HostStagingRing]:
        """The live ring (None until the first staged batch) — the
        DataLoader registers in-flight transfers against it."""
        return self._staging

    def _out_buffer(self, shape, dtype) -> np.ndarray:
        if not self.staging_active:
            return np.empty(shape, dtype)
        if self._staging is None or self._staging.depth < self._staging_depth:
            with self._staging_lock:
                if (
                    self._staging is None
                    or self._staging.depth < self._staging_depth
                ):
                    self._staging = HostStagingRing(self._staging_depth)
        buf = self._staging.get(shape, dtype)
        self._call_bufs().append(buf)
        return buf

    def _call_bufs(self) -> list:
        """Per-thread list of this call's staging buffers (two loaders'
        background threads may assemble through one pipeline; the
        threading.local is created eagerly in ``_init_staging``)."""
        tls = self._staging_tls
        if not hasattr(tls, "bufs"):
            tls.bufs = []
        return tls.bufs

    def _finish_staging(self) -> None:
        """End-of-fetch hook. Host-fed: release this call's buffers back
        to rotation (the consumer holds the batch synchronously; it is
        valid until ``depth - 1`` further fetches). Device-fed: keep
        them BUSY — the DataLoader's ``register_transfer`` releases each
        buffer only once its device copy-out is proven, so a buffer
        whose batch never reaches a device_put is simply never reused.
        """
        if self._staging is None:
            return
        bufs = self._call_bufs()
        if bufs and not self._device_fed:
            self._staging.release(bufs)
        bufs.clear()


def gather_rows(
    src: np.ndarray, indices, num_threads: int = 0,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """out[i] = src[indices[i]] with GIL-free threaded memcpy.

    ``src`` may be any contiguous array (incl. np.memmap); rows are
    src[j] slices of fixed byte size. ``out`` (optional) is a
    preallocated destination — e.g. a staging-ring buffer.
    """
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(indices, np.int64)
    if out is None:
        out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    elif (
        out.shape != (len(idx),) + src.shape[1:]
        or out.dtype != src.dtype
        or not out.flags.c_contiguous
    ):
        raise ValueError("gather_rows out buffer has the wrong shape/dtype")
    row_bytes = src.strides[0] if src.ndim > 1 else src.itemsize
    rc = _load().pf_gather_rows(
        src.ctypes.data_as(ctypes.c_void_p), row_bytes, src.shape[0],
        idx.ctypes.data_as(ctypes.c_void_p), len(idx),
        out.ctypes.data_as(ctypes.c_void_p), num_threads,
    )
    _check(rc, "gather_rows")
    return out


def make_device_normalizer(mean, stdinv, *, key: str = "image",
                           scale: float = 1.0, flip: bool = False):
    """Jittable ``(img * scale - mean) * stdinv`` batch transform for u8
    batches (the on-device half of a pipeline's ``device_normalize`` mode).

    Shared by the native and PIL/folder pipelines so the contract — u8
    pass-through detection, channel-count validation — lives once.

    ``flip=True`` fuses a per-sample random horizontal flip BEFORE the
    normalize (the cheap half of the ImageNet augmentation, previously a
    host-side transform): the returned callable then takes
    ``(batch, rng)`` and ``build_train_step`` feeds it the step's PRNG
    stream, so XLA fuses select + normalize into the first conv's input
    and the host never touches the pixels.
    """
    import jax
    import jax.numpy as jnp

    mean = np.asarray(mean, np.float32)
    stdinv = np.asarray(stdinv, np.float32)

    def _normalize_img(img):
        if img.dtype == jnp.uint8:
            c = img.shape[-1]
            if mean.size not in (1, c) or stdinv.size not in (1, c):
                # the host f32 paths fail their broadcast_to loudly for
                # this mismatch; match that instead of silently
                # broadcasting [..., 1] against (3,) into 3 channels
                raise ValueError(
                    f"normalizer mean/std have {mean.size} channels "
                    f"but the image has {c}"
                )
            img = (img.astype(jnp.float32) * scale - mean) * stdinv
        return img

    if not flip:

        def normalize(batch):
            return {**batch, key: _normalize_img(batch[key])}

        return normalize

    def flip_normalize(batch, rng):
        img = batch[key]
        coin = jax.random.bernoulli(rng, 0.5, shape=(img.shape[0],))
        # flip the RAW pixels (u8 select is 1/4 the bytes of f32), then
        # normalize — same order as the host pipelines (flip at assembly)
        img = jnp.where(coin[:, None, None, None], img[:, :, ::-1, :], img)
        return {**batch, key: _normalize_img(img)}

    # explicit marker for build_train_step's rng plumbing (signature
    # sniffing stays a fallback for user transforms)
    flip_normalize._ptd_takes_rng = True
    return flip_normalize


def device_normalizer_for(mean, std, *, flip: bool = False,
                          key: str = "image"):
    """Device normalizer from UNIT-domain (torchvision-convention)
    mean/std for raw uint8 batches — the one helper the recipes share
    instead of each pre-scaling mean/std to the 0..255 domain."""
    mean = np.asarray(mean, np.float32)
    stdinv = 1.0 / np.asarray(std, np.float32)
    return make_device_normalizer(
        mean, stdinv, key=key, scale=1.0 / 255.0, flip=flip
    )


def host_flip_transform(seed: int, *, key: str = "image"):
    """Host-side random horizontal flip, a DataLoader ``transform`` —
    the f32 escape-hatch counterpart of the fused on-device flip
    (``make_device_normalizer(flip=True)``)."""
    rng = np.random.default_rng(seed)

    def transform(batch):
        flip = rng.random(batch[key].shape[0]) < 0.5
        batch[key] = np.where(
            flip[:, None, None, None], batch[key][:, :, ::-1, :],
            batch[key],
        )
        return batch

    return transform


class ImageBatchPipeline(_StagingMixin):
    """Fetch callable for :class:`DataLoader`: native augmenting assembly.

    Expects the dataset to expose uint8 images ``[N, H, W, C]`` and int
    labels via ``dataset.arrays`` (ArrayDataset layout). Produces
    ``{"image": [B, crop, crop, C], "label": i32 [B]}`` — raw uint8 by
    DEFAULT (the ingest fast path, docs/DESIGN.md §3d), host-normalized
    f32 with ``device_normalize=False``.

    train=True: random crop (after ``pad`` reflected/zero padding is NOT
    applied — crops sample within the source frame, ImageNet-style; for
    CIFAR pass ``pad`` to pre-pad once) + horizontal flip.
    train=False: deterministic center crop, no flip.

    ``device_normalize`` (the default) ships the batch as **uint8** (1/4
    the host->device bytes — the host-to-device link is the input pipeline's
    scarcest resource) and defers the ``(px/255 - mean) * stdinv``
    arithmetic to the accelerator: apply ``self.device_normalizer()``
    inside the jitted step (``build_train_step(batch_transform=...)``),
    where XLA fuses it into the first conv's input. ``False`` restores
    the reference-parity host f32 normalize.

    ``reuse_staging``: rotate output batches through a
    :class:`HostStagingRing` instead of a fresh ``np.empty`` per batch.
    Default (None) auto-enables when the consuming DataLoader device-puts
    every batch (see ``_StagingMixin``).
    """

    def __init__(
        self,
        crop: int,
        *,
        train: bool = True,
        flip: bool = True,
        pad: int = 0,
        mean: Sequence[float] = (0.485, 0.456, 0.406),
        std: Sequence[float] = (0.229, 0.224, 0.225),
        seed: int = 0,
        num_threads: int = 0,
        image_key: str = "image",
        label_key: str = "label",
        device_normalize: bool = True,
        reuse_staging: Optional[bool] = None,
    ):
        self.crop = crop
        self.train = train
        self.flip = flip
        self.pad = pad
        self.mean = np.asarray(mean, np.float32)
        self.stdinv = 1.0 / np.asarray(std, np.float32)
        self.seed = seed
        self.num_threads = num_threads
        self.image_key = image_key
        self.label_key = label_key
        self.device_normalize = device_normalize
        self._init_staging(reuse_staging)
        self.epoch = 0
        self._padded: Optional[np.ndarray] = None

    def device_normalizer(self):
        """Jittable batch transform applying this pipeline's normalization
        on-device (use with ``device_normalize=True``)."""
        return make_device_normalizer(
            self.mean, self.stdinv, key=self.image_key, scale=1.0 / 255.0
        )

    def set_epoch(self, epoch: int) -> None:
        """Advance the augmentation stream (DataLoader forwards this)."""
        self.epoch = epoch

    def _source(self, dataset) -> np.ndarray:
        imgs = dataset.arrays[self.image_key]
        if self.pad:
            if self._padded is None:
                p = self.pad
                self._padded = np.pad(
                    imgs, ((0, 0), (p, p), (p, p), (0, 0)), mode="reflect"
                )
            return self._padded
        if not imgs.flags.c_contiguous:
            imgs = np.ascontiguousarray(imgs)
            dataset.arrays[self.image_key] = imgs  # cache the copy once
        return imgs

    def __call__(self, dataset, indices: np.ndarray):
        imgs = self._source(dataset)
        if imgs.dtype != np.uint8:
            raise TypeError(
                f"native image pipeline needs uint8 images, got {imgs.dtype}"
            )
        idx = np.ascontiguousarray(indices, np.int64)
        n = len(idx)
        N, H, W, C = imgs.shape
        crop = self.crop
        if self.train:
            # augmentation params derived from (seed, epoch, batch indices)
            # so a resumed epoch replays the same crops/flips while distinct
            # epochs — and distinct batches even under shuffle=False — get
            # fresh augmentation (the full index array is hashed, not just
            # its head)
            import zlib

            rng = np.random.default_rng(
                [self.seed, self.epoch, zlib.crc32(idx.tobytes()), n]
            )
            cy = rng.integers(0, H - crop + 1, size=n, dtype=np.int32)
            cx = rng.integers(0, W - crop + 1, size=n, dtype=np.int32)
            fl = (
                rng.integers(0, 2, size=n, dtype=np.uint8)
                if self.flip else np.zeros(n, np.uint8)
            )
        else:
            cy = np.full(n, (H - crop) // 2, np.int32)
            cx = np.full(n, (W - crop) // 2, np.int32)
            fl = np.zeros(n, np.uint8)
        if self.device_normalize:
            out = self._out_buffer((n, crop, crop, C), np.uint8)
            rc = _load().pf_image_batch_u8(
                imgs.ctypes.data_as(ctypes.c_void_p), N, H, W, C,
                idx.ctypes.data_as(ctypes.c_void_p), n,
                cy.ctypes.data_as(ctypes.c_void_p),
                cx.ctypes.data_as(ctypes.c_void_p),
                fl.ctypes.data_as(ctypes.c_void_p),
                out.ctypes.data_as(ctypes.c_void_p), crop, crop,
                self.num_threads,
            )
            _check(rc, "image_batch_u8")
        else:
            out = self._out_buffer((n, crop, crop, C), np.float32)
            mean = np.ascontiguousarray(
                np.broadcast_to(self.mean, (C,)), np.float32
            )
            stdinv = np.ascontiguousarray(
                np.broadcast_to(self.stdinv, (C,)), np.float32
            )
            rc = _load().pf_image_batch(
                imgs.ctypes.data_as(ctypes.c_void_p), N, H, W, C,
                idx.ctypes.data_as(ctypes.c_void_p), n,
                cy.ctypes.data_as(ctypes.c_void_p),
                cx.ctypes.data_as(ctypes.c_void_p),
                fl.ctypes.data_as(ctypes.c_void_p),
                mean.ctypes.data_as(ctypes.c_void_p),
                stdinv.ctypes.data_as(ctypes.c_void_p),
                out.ctypes.data_as(ctypes.c_void_p), crop, crop,
                self.num_threads,
            )
            _check(rc, "image_batch")
        batch = {self.image_key: out}
        labels = dataset.arrays.get(self.label_key)
        if labels is not None:
            labels = np.ascontiguousarray(labels)
            if labels.dtype == np.int32 and self.staging_active:
                # gather straight into a staging-ring buffer: no label
                # alloc and no astype copy on the hot path
                batch[self.label_key] = gather_rows(
                    labels, idx, self.num_threads,
                    out=self._out_buffer(
                        (n,) + labels.shape[1:], np.int32
                    ),
                )
            else:
                batch[self.label_key] = gather_rows(
                    labels, idx, self.num_threads
                ).astype(np.int32)
        self._finish_staging()
        return batch
