"""Device contexts: ``mx.cpu()`` / ``mx.tpu(i)`` (+ ``mx.gpu`` compat alias).

Reference: ``python/mxnet/context.py`` (SURVEY.md §2.2 "Context/device" — "the
seam where mx.tpu() goes").  A Context names a device; NDArray creation places
buffers there via ``jax.device_put``.  Unlike the reference there is no CUDA
stream machinery behind this — XLA/PjRt owns ordering (SURVEY.md §7 design
stance).

Contexts also stretch to *meshes*: ``mx.tpu_mesh(...)`` (see
``mxnet_tpu.parallel``) returns a context whose "device" is a
``jax.sharding.Mesh``, the TPU-native replacement for the reference's
device-list data parallelism.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]

_DEVTYPE_IDS = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}


class Context:
    """A device context.  Usable as a ``with`` scope to set the default device."""

    _tls = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in _DEVTYPE_IDS:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = device_id

    # -- jax resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device (None = let jax place it).

        Multi-process: only this process's local devices are addressable —
        a Context always resolves within them (reference: a worker's ctx
        list is its own GPUs)."""
        import jax
        kind = self.device_type
        if kind in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = [d for d in jax.local_devices() if d.platform == "cpu"]
            if not devs:
                # on an accelerator host the default backend's local
                # devices are TPUs only — the host CPU lives on the "cpu"
                # backend (reference semantics: mx.cpu() data stays on
                # the host even when GPUs exist)
                try:
                    devs = jax.local_devices(backend="cpu")
                except RuntimeError:
                    devs = []
            if devs:
                return devs[self.device_id % len(devs)]
            return None
        # tpu / gpu: accelerator ``device_id`` of the default backend.  A
        # missing accelerator is an error, never a CPU stand-in: work
        # placed on mx.tpu(i) must not run somewhere else unnoticed.
        devs = [d for d in jax.local_devices() if d.platform != "cpu"]
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                f"{self!r}: no such accelerator device — jax backend "
                f"{jax.default_backend()!r} has {len(devs)} local "
                f"accelerator device(s)")
        return devs[self.device_id]

    # -- scope -------------------------------------------------------------
    def __enter__(self):
        stack = getattr(Context._tls, "stack", None)
        if stack is None:
            stack = Context._tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._tls.stack.pop()

    # -- protocol ----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return repr(self)

    @property
    def device_typeid(self):
        return _DEVTYPE_IDS[self.device_type]

    def empty_cache(self):
        """Reference: ``Context.empty_cache``.  XLA owns the memory pool; jax
        exposes no portable pool flush, so this is best-effort."""
        import gc
        gc.collect()


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compat alias: reference code says ``mx.gpu(i)``; on this stack it means
    'accelerator i' and resolves to the TPU backend."""
    return Context("gpu", device_id)


def num_gpus() -> int:
    return num_tpus()


def num_tpus() -> int:
    import jax
    return len([d for d in jax.devices() if d.platform != "cpu"])


def current_context() -> Context:
    stack = getattr(Context._tls, "stack", None)
    if stack:
        return stack[-1]
    return Context._default()


def _default_context() -> Context:
    import jax
    return cpu(0) if jax.default_backend() == "cpu" else tpu(0)


Context._default = staticmethod(_default_context)
