"""NDArray: imperative tensor over a jax.Array buffer.

Reference: ``src/ndarray/ndarray.cc`` + ``python/mxnet/ndarray/ndarray.py``
(SURVEY.md N2).  The reference's NDArray is a ref-counted chunk whose ops are
pushed through the ThreadedEngine; here the buffer is a ``jax.Array`` (PjRt
buffer underneath) and *JAX's own async dispatch is the engine* — every eager
op returns immediately with a future-backed buffer, and ``asnumpy()`` /
``wait_to_read()`` are the sync points (reference ``WaitToRead``).  Under a
``jit`` trace the same NDArray wraps a tracer, which is how one op library
serves both the imperative path and the hybridized (compiled) path.

Autograd: ops flow through :func:`apply_op`, which under ``autograd.record()``
captures the op's ``jax.vjp`` on the tape (see ``mxnet_tpu/autograd.py``).
"""
from __future__ import annotations

import os
import json
import struct

import numpy as onp

from ..base import MXNetError, dtype_name, is_tracer, np_dtype
from ..context import Context, cpu, current_context
from .. import autograd
from .. import engine as _engine
from .. import memory as _memory
from .. import telemetry as _telemetry

# sync spans shorter than this are not recorded: a trivial host read of
# already-materialized data is not an execute wait and would flood the
# flight-recorder ring (50us ~= noise floor of a real device wait)
_SYNC_SPAN_MIN_NS = 50_000

__all__ = [
    "NDArray", "apply_op", "wrap", "unwrap", "array", "zeros", "ones", "full",
    "empty", "arange", "linspace", "eye", "zeros_like", "ones_like",
    "full_like", "save", "load", "from_numpy", "waitall", "concatenate",
]


def unwrap(x):
    """NDArray -> raw jax array; everything else passes through.

    This is the sanctioned flush point: a pending (lazily recorded) NDArray
    is materialized here, so any code path that needs the raw buffer is
    automatically a materialization boundary (docs/ENGINE.md)."""
    if isinstance(x, NDArray):
        if x._data is None:
            _engine.flush_array(x)
        return x._data
    return x


def wrap(raw):
    return NDArray(raw)


def _is_array_like(x):
    import jax
    return isinstance(x, (NDArray, jax.Array, onp.ndarray)) or is_tracer(x)


def _is_inexact(raw):
    import jax.numpy as jnp
    return jnp.issubdtype(jnp.result_type(raw), jnp.inexact)


def apply_op(fun, *args, op_name="", has_aux=False, **static_kwargs):
    """Execute a pure jax function as a framework op.

    * unwraps NDArray args, calls ``fun(*raws, **static_kwargs)``
    * under ``autograd.record()`` with in-graph inputs, runs ``jax.vjp``
      instead and registers a tape node (reference ``Imperative::RecordOp``)
    * wraps outputs back into NDArray

    ``has_aux``: ``fun`` returns ``(outputs, aux)``; aux is returned raw and
    never differentiated (used by the CachedOp path for BatchNorm moving-stat
    updates etc.).
    """
    import jax

    from .. import profiler as _profiler
    if _profiler.is_running():
        import time as _time
        t0 = _time.perf_counter_ns() // 1000
        try:
            return _apply_op_impl(fun, args, op_name, has_aux, static_kwargs)
        finally:
            t1 = _time.perf_counter_ns() // 1000
            _profiler.record_event(op_name or getattr(fun, "__name__", "op"),
                                   "op_dispatch", t0, t1 - t0)
    return _apply_op_impl(fun, args, op_name, has_aux, static_kwargs)


_INEXACT_CACHE: dict = {}


def _is_inexact_dtype(dt):
    # jnp.result_type costs ~20us; this runs ~3x per captured op record
    try:
        return _INEXACT_CACHE[dt]
    except (KeyError, TypeError):
        import jax.numpy as jnp
        r = bool(jnp.issubdtype(jnp.result_type(dt), jnp.inexact))
        try:
            _INEXACT_CACHE[dt] = r
        except TypeError:
            pass
        return r


def _record_taped(fun, args, op_name, static_kwargs):
    """Whole-step capture of one recorded op: defer it into the live lazy
    segment AND attach a :class:`autograd.LazyTapeNode` to its placeholder
    outputs — no ``jax.vjp`` runs now; residuals stay symbolic.  Returns
    ``NotImplemented`` when the op cannot be captured (unkeyable fun,
    unsupported arg, eval_shape-hostile fun) — the caller then takes the
    eager per-op vjp path, which is the documented fallback."""
    fkey = _engine._fun_key(fun, static_kwargs)
    if fkey is None:
        return NotImplemented
    diff_pos = []
    for i, a in enumerate(args):
        if isinstance(a, NDArray):
            if _is_inexact_dtype(a._aval.dtype):
                diff_pos.append(i)
        # raw array args (dropout PRNG keys, CachedOp rng) are non-diff
        # externals: the eager path nominally differentiates inexact raws
        # but always discards those grads (a fresh wrapper can be neither
        # requires_grad nor on the tape), so skipping them is equivalent
    res = _engine.record_lazy(fun, args, op_name, static_kwargs,
                              key_override=fkey, tape=True)
    if res is NotImplemented:
        return NotImplemented
    outs = res if isinstance(res, tuple) else (res,)
    # integer/bool outputs skip the tape entirely (argmax/topk indices),
    # matching the eager path's abstract-eval gate
    if not diff_pos or not all(_is_inexact_dtype(o._aval.dtype)
                               for o in outs):
        return res
    node = autograd.LazyTapeNode(
        fun, static_kwargs, args, diff_pos,
        [(o.shape, o._aval.dtype) for o in outs],
        isinstance(res, tuple), fkey,
        name=op_name or getattr(fun, "__name__", "op"),
        block=_engine.current_block())
    for slot, o in enumerate(outs):
        o._tape_node = node
        o._tape_slot = slot
    return res


def _apply_op_impl(fun, args, op_name, has_aux, static_kwargs):
    import jax

    record = False
    ag_state = autograd._state()
    if ag_state.recording:
        for a in args:
            if isinstance(a, NDArray) and (a._requires_grad or a._tape_node is not None):
                record = True
                break

    # ag_state.capture caches the ENV half of engine.capture_active()
    # (one getenv per record() scope, not per op); lazy_enabled() is
    # still consulted per op — it is env-free, and it is what makes
    # naive_engine_scope / set_engine_type("NaiveEngine") INSIDE an open
    # record scope actually force synchronous execution
    if record and not has_aux and ag_state.capture \
            and _engine.lazy_enabled():
        # whole-step capture: the op joins the pending segment with a
        # symbolic tape node instead of paying an eager jax.vjp
        res = _record_taped(fun, args, op_name, static_kwargs)
        if res is not NotImplemented:
            return res
        _engine.bump_stat("step_capture_fallbacks")

    if not record:
        # lazy tier: defer the op into the current segment (LazyEngine /
        # bulk scope).  Autograd-recorded ops and CachedOp aux updates
        # never defer; an already-jitted fun (jax.nn.relu, a hybridized
        # program) simply inlines into the segment trace.
        if not has_aux and _engine.lazy_enabled():
            res = _engine.record_lazy(fun, args, op_name, static_kwargs)
            if res is not NotImplemented:
                return res
        raws = [unwrap(a) for a in args]
        # eager tier: per-op executable cache — a jit-compiled program
        # keyed by (fun, static kwargs, input avals) instead of re-paying
        # full JAX tracing per call.  Skipped under an outer trace, for
        # funs that are already jit wrappers, and for aux-carrying funs.
        if not has_aux and not hasattr(fun, "lower") \
                and _engine.op_cache_enabled() \
                and not any(is_tracer(r) for r in raws):
            ok, out = _engine.cached_call(fun, raws, static_kwargs, op_name)
            if not ok:
                out = fun(*raws, **static_kwargs)
        else:
            out = fun(*raws, **static_kwargs)
        if has_aux:
            out, aux = out
            return _wrap_outputs(out), aux
        return _wrap_outputs(out)

    raws = [unwrap(a) for a in args]

    # positions participating in differentiation: inexact array args
    diff_pos = [i for i, (a, r) in enumerate(zip(args, raws))
                if _is_array_like(a) and _is_inexact(r)]

    def f(*diff_args):
        full = list(raws)
        for p, v in zip(diff_pos, diff_args):
            full[p] = v
        return fun(*full, **static_kwargs)

    diff_raws = [raws[p] for p in diff_pos]
    if not diff_pos:
        out = fun(*raws, **static_kwargs)
        if has_aux:
            out, aux = out
            return _wrap_outputs(out), aux
        return _wrap_outputs(out)
    if not has_aux:
        # abstract-eval first: ops with integer outputs (argmax/topk indices)
        # are non-differentiable and skip the tape entirely.
        avals = jax.eval_shape(f, *diff_raws)
        avals_flat = avals if isinstance(avals, (tuple, list)) else (avals,)
        if not all(_is_inexact(o) for o in avals_flat):
            return _wrap_outputs(fun(*raws, **static_kwargs))
    # the vjp runs over a cached JITTED core when the op is keyable: the
    # op body stays one compiled unit on the eager tape exactly as it is
    # inside a whole-step capture, so contraction/FMA rounding matches
    # between the two paths (bit-identical eager-vs-captured training)
    jfn, other_pos = _engine.vjp_jit_fn(fun, static_kwargs,
                                        tuple(diff_pos), len(raws))
    if jfn is not None:
        other = tuple(raws[i] for i in other_pos)
        fcall = lambda *diff_args: jfn(diff_args, other)  # noqa: E731
    else:
        fcall = f
    try:
        if has_aux:
            out, vjp_fn, aux = jax.vjp(fcall, *diff_raws, has_aux=True)
        else:
            out, vjp_fn = jax.vjp(fcall, *diff_raws)
            aux = None
    except Exception:
        if jfn is None:
            raise
        # jit-hostile op body: remember, and re-run through the un-jitted
        # closure (a genuine user error raises identically from there)
        _engine.vjp_jit_blacklist(fun, static_kwargs, tuple(diff_pos),
                                  len(raws))
        jfn = None
        if has_aux:
            out, vjp_fn, aux = jax.vjp(f, *diff_raws, has_aux=True)
        else:
            out, vjp_fn = jax.vjp(f, *diff_raws)
            aux = None
    if jfn is not None and not has_aux and _engine.step_capture_enabled():
        # Outputs come from the PLAIN per-op jit program (the tier-1
        # cache), not from the vjp's partial-eval'd primal: the linearized
        # primal saves residuals and therefore compiles (and rounds)
        # differently by ~1 ulp on multi-primitive ops like BatchNorm.
        # Whole-step capture executes ops as plain calls, so taking eager
        # outputs from the same plain program is what keeps eager and
        # captured training bit-identical.  jax.vjp above still supplies
        # the backward closure (its residuals are consistent with the
        # same inputs).  Cost: the eager tape executes each op's forward
        # twice (vjp primal + plain program) — residuals cannot be
        # extracted from the plain program, and reusing the vjp primal
        # for outputs breaks the bit-parity contract; whole-step capture
        # (where the forward runs once) is the fast path.  With capture
        # off (MXNET_STEP_CAPTURE=0) there is no captured run to match,
        # so the parity re-execution is skipped and eager pays ONE
        # forward (outputs then come from the vjp primal).
        if _engine.op_cache_enabled():
            ok, plain = _engine.cached_call(fun, raws, static_kwargs,
                                            op_name)
            if ok:
                out = plain

    outs_flat = list(out) if isinstance(out, (tuple, list)) else [out]
    node = autograd.TapeNode(
        vjp_fn,
        [args[p] if isinstance(args[p], NDArray) else NDArray(raws[p])
         for p in diff_pos],
        [(o.shape, o.dtype) for o in outs_flat],
        name=op_name or getattr(fun, "__name__", "op"),
        block=_engine.current_block(),
    )
    wrapped = []
    for slot, o in enumerate(outs_flat):
        nd = NDArray(o)
        nd._tape_node = node
        nd._tape_slot = slot
        wrapped.append(nd)
    res = wrapped[0] if not isinstance(out, (tuple, list)) else tuple(wrapped)
    if has_aux:
        return res, aux
    return res


def _maybe_sync(raws):
    """NaiveEngine mode: block after every op (reference naive_engine.cc)."""
    from .. import engine
    if engine.is_sync():
        for r in raws:
            if hasattr(r, "block_until_ready"):
                r.block_until_ready()


def _wrap_outputs(out):
    if isinstance(out, (tuple, list)):
        if not (out and is_tracer(out[0])):
            _maybe_sync(out)
        return tuple(NDArray(o) for o in out)
    if not is_tracer(out):
        _maybe_sync([out])
    return NDArray(out)


class NDArray:
    """Imperative multi-dim array on a device (or a tracer under jit)."""

    __slots__ = ("_data", "_grad", "_grad_req", "_requires_grad",
                 "_tape_node", "_tape_slot", "_pending", "_pending_aval",
                 "_sparse_grad_cleared", "__weakref__")

    def __init__(self, data):
        if type(data) is onp.ndarray:
            # a raw numpy array held here would be re-uploaded host->device
            # on EVERY jit call that takes it as an argument (measured:
            # ~700 ms/step for int8-quantized R50 whose weights were set
            # from numpy); commit it once, honoring the active Context like
            # every other creation path
            data = _place(data, None)
        self._data = data
        self._grad = None
        self._grad_req = "write"
        self._requires_grad = False
        self._tape_node = None
        self._tape_slot = 0
        self._pending = None
        self._pending_aval = None
        self._sparse_grad_cleared = False
        # live-array census (docs/OBSERVABILITY.md memory/*): default
        # origin "activation"; parameters/grads/states are retagged at
        # their creation sites.  One attribute read when the census is off.
        if _memory._census_active:
            _memory.register(self)

    @classmethod
    def _new_pending(cls, aval):
        """Placeholder backed by a deferred lazy-segment slot: ``_data`` is
        None until the owning segment flushes; shape/dtype come from the
        abstract value (no device work)."""
        nd = cls.__new__(cls)
        nd._data = None
        nd._grad = None
        nd._grad_req = "write"
        nd._requires_grad = False
        nd._tape_node = None
        nd._tape_slot = 0
        nd._pending = None
        nd._pending_aval = aval
        nd._sparse_grad_cleared = False
        # census: deferred placeholders are accounted at the SEGMENT
        # level (engine new_slot -> "pending" bytes); the flush writeback
        # registers whatever actually materializes (memory.materialized)
        return nd

    @property
    def _aval(self):
        """Shape/dtype carrier: the raw buffer, or the pending abstract
        value while this array is deferred."""
        return self._pending_aval if self._data is None else self._data

    # ------------------------------------------------------------------
    # properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._aval.shape)

    @property
    def dtype(self):
        a = self._aval
        return onp.dtype(a.dtype) if a.dtype != "bfloat16" else a.dtype

    @property
    def ndim(self):
        return len(self._aval.shape)

    @property
    def size(self):
        s = 1
        for d in self._aval.shape:
            s *= d
        return s

    @property
    def context(self) -> Context:
        import jax
        if self._data is None or is_tracer(self._data):
            return current_context()
        try:
            dev = next(iter(self._data.devices()))
        except Exception:
            return current_context()
        if dev.platform == "cpu":
            return cpu(dev.id)
        from ..context import tpu
        return tpu(dev.id)

    ctx = context

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return self.transpose()

    @property
    def stype(self):
        return "default"

    # ------------------------------------------------------------------
    # sync / host transfer (reference: WaitToRead, asnumpy, waitall)
    # ------------------------------------------------------------------
    def asnumpy(self) -> onp.ndarray:
        if self._data is None:
            _engine.flush_array(self)       # materialization boundary
        if is_tracer(self._data):
            raise MXNetError("asnumpy() called inside a traced (hybridized) "
                             "computation — this is a host sync point and "
                             "cannot be compiled.")
        if not _telemetry.enabled():
            return onp.asarray(self._data)
        # this conversion is where the host actually BLOCKS on in-flight
        # device work (dispatch is async), i.e. the step's execute wait —
        # record it as a "sync" phase so per-step phase sums account for
        # device time, not just python dispatch.  Threshold-gated: a
        # trivial host read must not flood the flight recorder.
        import time as _time
        t0 = _time.perf_counter_ns()
        out = onp.asarray(self._data)
        dur = _time.perf_counter_ns() - t0
        if dur > _SYNC_SPAN_MIN_NS:
            _telemetry.add_span("sync", t0 // 1000, dur / 1000)
        return out

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        if self._data is None:
            _engine.flush_array(self)       # materialization boundary
        if hasattr(self._data, "block_until_ready"):
            if _telemetry.enabled():
                import time as _time
                t0 = _time.perf_counter_ns()
                self._data.block_until_ready()
                dur = _time.perf_counter_ns() - t0
                if dur > _SYNC_SPAN_MIN_NS:
                    _telemetry.add_span("sync", t0 // 1000, dur / 1000)
            else:
                self._data.block_until_ready()
        return self

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # ------------------------------------------------------------------
    # device movement
    # ------------------------------------------------------------------
    def as_in_context(self, ctx: Context) -> "NDArray":
        import jax
        if self._data is not None and is_tracer(self._data):
            return self
        raw = unwrap(self)
        dev = ctx.jax_device()
        if dev is None or dev in raw.devices():
            return self
        return NDArray(jax.device_put(raw, dev))

    as_in_ctx = as_in_context

    def copyto(self, other):
        import jax
        if isinstance(other, Context):
            dev = other.jax_device()
            return NDArray(jax.device_put(unwrap(self), dev))
        if isinstance(other, NDArray):
            if other._data is None:
                # overwriting a pending target: flush it first so the
                # segment's later writeback cannot clobber this store
                _engine.flush_array(other)
            other._data = unwrap(self)
            return other
        raise TypeError(f"copyto does not support type {type(other)}")

    def copy(self):
        import jax.numpy as jnp
        if jnp.issubdtype(jnp.result_type(self._aval.dtype), jnp.inexact):
            return apply_op(lambda x: x + 0, self, op_name="copy")
        return NDArray(unwrap(self))

    def astype(self, dtype, copy=True):
        return apply_op(lambda x: x.astype(np_dtype(dtype)), self, op_name="cast")

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sparse
        return _sparse.tostype(self, stype)

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        import jax.numpy as jnp
        self._requires_grad = grad_req != "null"
        self._grad_req = grad_req
        self._grad = NDArray(jnp.zeros(self.shape, self._aval.dtype))
        self._tape_node = None
        if _memory._census_active:
            _memory.tag(self._grad, "gradient")

    def detach(self):
        return NDArray(unwrap(self))

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def zero_grad(self):
        if self._grad is not None:
            import jax.numpy as jnp
            if not isinstance(self._grad, NDArray):
                # row-sparse grad (Embedding sparse_grad=True): next
                # backward writes a fresh one.  Mark the clear so
                # Parameter.grad() can return zeros (reference behavior)
                # instead of a misleading grad_req='null' error.
                self._grad = None
                self._sparse_grad_cleared = True
                return
            if self._grad._pending is not None:
                # grad still pending from a captured step: detach it from
                # the segment (the flush writeback skips detached arrays)
                # so the deferred value cannot clobber the zeros
                self._grad._pending = None
                self._grad._pending_aval = None
            self._grad._data = jnp.zeros(self.shape, self._aval.dtype)

    # ------------------------------------------------------------------
    # shape ops (methods delegate to the op library for tape coverage)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = tuple(int(s) for s in shape)
        # reference reshape specials: 0 = copy dim, -1 = infer
        new = []
        for i, s in enumerate(shape):
            if s == 0:
                new.append(self.shape[i])
            else:
                new.append(s)
        return apply_op(lambda x: x.reshape(tuple(new)), self, op_name="reshape")

    def reshape_like(self, other):
        return apply_op(lambda x, y: x.reshape(y.shape), self, other,
                        op_name="reshape_like")

    def transpose(self, axes=None):
        import jax.numpy as jnp
        if axes is not None and len(axes) == 0:
            axes = None
        return apply_op(lambda x: jnp.transpose(x, axes), self, op_name="transpose")

    def swapaxes(self, a1, a2):
        import jax.numpy as jnp
        return apply_op(lambda x: jnp.swapaxes(x, a1, a2), self, op_name="swapaxes")

    def flatten(self):
        """Reference semantics: collapse all trailing dims -> 2D."""
        n = self.shape[0] if self.ndim > 0 else 1
        return apply_op(lambda x: x.reshape((n, -1)), self, op_name="flatten")

    def expand_dims(self, axis):
        import jax.numpy as jnp
        return apply_op(lambda x: jnp.expand_dims(x, axis), self,
                        op_name="expand_dims")

    def squeeze(self, axis=None):
        import jax.numpy as jnp
        return apply_op(lambda x: jnp.squeeze(x, axis), self, op_name="squeeze")

    def broadcast_to(self, shape):
        import jax.numpy as jnp
        return apply_op(lambda x: jnp.broadcast_to(x, shape), self,
                        op_name="broadcast_to")

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def tile(self, reps):
        import jax.numpy as jnp
        return apply_op(lambda x: jnp.tile(x, reps), self, op_name="tile")

    def repeat(self, repeats, axis=None):
        import jax.numpy as jnp
        return apply_op(lambda x: jnp.repeat(x, repeats, axis), self,
                        op_name="repeat")

    def split(self, num_outputs, axis=0, squeeze_axis=False):
        from . import ops
        return ops.split(self, num_outputs=num_outputs, axis=axis,
                         squeeze_axis=squeeze_axis)

    # ------------------------------------------------------------------
    # reductions / math methods
    # ------------------------------------------------------------------
    def _reduce(self, fname, axis=None, keepdims=False):
        import jax.numpy as jnp
        fn = getattr(jnp, fname)
        return apply_op(lambda x: fn(x, axis=axis, keepdims=keepdims), self,
                        op_name=fname)

    def sum(self, axis=None, keepdims=False, **kw):
        return self._reduce("sum", axis, keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        return self._reduce("mean", axis, keepdims)

    def prod(self, axis=None, keepdims=False, **kw):
        return self._reduce("prod", axis, keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return self._reduce("max", axis, keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return self._reduce("min", axis, keepdims)

    def argmax(self, axis=None, keepdims=False):
        import jax.numpy as jnp
        return apply_op(
            lambda x: jnp.argmax(x, axis=axis, keepdims=keepdims).astype("float32"),
            self, op_name="argmax")

    def argmin(self, axis=None, keepdims=False):
        import jax.numpy as jnp
        return apply_op(
            lambda x: jnp.argmin(x, axis=axis, keepdims=keepdims).astype("float32"),
            self, op_name="argmin")

    def norm(self, ord=2, axis=None, keepdims=False):
        from . import ops
        return ops.norm(self, ord=ord, axis=axis, keepdims=keepdims)

    def clip(self, a_min=None, a_max=None):
        import jax.numpy as jnp
        return apply_op(lambda x: jnp.clip(x, a_min, a_max), self, op_name="clip")

    def abs(self):
        import jax.numpy as jnp
        return apply_op(jnp.abs, self, op_name="abs")

    def sqrt(self):
        import jax.numpy as jnp
        return apply_op(jnp.sqrt, self, op_name="sqrt")

    def exp(self):
        import jax.numpy as jnp
        return apply_op(jnp.exp, self, op_name="exp")

    def log(self):
        import jax.numpy as jnp
        return apply_op(jnp.log, self, op_name="log")

    def dot(self, other):
        from . import ops
        return ops.dot(self, other)

    def sigmoid(self):
        import jax
        return apply_op(jax.nn.sigmoid, self, op_name="sigmoid")

    def relu(self):
        import jax
        return apply_op(jax.nn.relu, self, op_name="relu")

    def tanh(self):
        import jax.numpy as jnp
        return apply_op(jnp.tanh, self, op_name="tanh")

    def softmax(self, axis=-1):
        import jax
        return apply_op(lambda x: jax.nn.softmax(x, axis=axis), self,
                        op_name="softmax")

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        from . import ops
        return ops.one_hot(self, depth, on_value=on_value, off_value=off_value)

    def take(self, indices, axis=0, mode="clip"):
        from . import ops
        return ops.take(self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        from . import ops
        return ops.pick(self, index, axis=axis, keepdims=keepdims)

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        from . import ops
        return ops.topk(self, axis=axis, k=k, ret_typ=ret_typ,
                        is_ascend=is_ascend)

    def slice_axis(self, axis, begin, end):
        from . import ops
        return ops.slice_axis(self, axis=axis, begin=begin, end=end)

    # ------------------------------------------------------------------
    # arithmetic (numpy broadcasting; superset of reference nd semantics)
    # ------------------------------------------------------------------
    def _binop(self, other, fn, name):
        if isinstance(other, NDArray) or _is_array_like(other) or \
           isinstance(other, (int, float, bool, onp.number)):
            return apply_op(fn, self, other, op_name=name)
        return NotImplemented

    def __add__(self, o):
        return self._binop(o, lambda a, b: a + b, "add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, lambda a, b: a - b, "sub")

    def __rsub__(self, o):
        return self._binop(o, lambda a, b: b - a, "rsub")

    def __mul__(self, o):
        return self._binop(o, lambda a, b: a * b, "mul")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, lambda a, b: a / b, "div")

    def __rtruediv__(self, o):
        return self._binop(o, lambda a, b: b / a, "rdiv")

    def __floordiv__(self, o):
        return self._binop(o, lambda a, b: a // b, "floordiv")

    def __mod__(self, o):
        return self._binop(o, lambda a, b: a % b, "mod")

    def __pow__(self, o):
        return self._binop(o, lambda a, b: a ** b, "pow")

    def __rpow__(self, o):
        return self._binop(o, lambda a, b: b ** a, "rpow")

    def __matmul__(self, o):
        from . import ops
        return ops.matmul(self, o)

    def __neg__(self):
        return apply_op(lambda a: -a, self, op_name="neg")

    def __abs__(self):
        return self.abs()

    def __eq__(self, o):
        return self._binop(o, lambda a, b: (a == b), "eq")

    def __ne__(self, o):
        return self._binop(o, lambda a, b: (a != b), "ne")

    def __lt__(self, o):
        return self._binop(o, lambda a, b: (a < b), "lt")

    def __le__(self, o):
        return self._binop(o, lambda a, b: (a <= b), "le")

    def __gt__(self, o):
        return self._binop(o, lambda a, b: (a > b), "gt")

    def __ge__(self, o):
        return self._binop(o, lambda a, b: (a >= b), "ge")

    def __hash__(self):
        return id(self)

    # in-place: swap the underlying buffer (python-level mutation; the
    # reference mutates the chunk through the engine).
    def _inplace(self, other, fn, name):
        if autograd.is_recording() and (self._requires_grad or
                                        self._tape_node is not None):
            raise MXNetError(f"in-place {name} on an array in a recorded "
                             "graph is not supported")
        # mutation of a pending array is a materialization boundary:
        # unwrap() flushes self before its buffer is rebound
        self._data = fn(unwrap(self), unwrap(other))
        return self

    def __iadd__(self, o):
        return self._inplace(o, lambda a, b: a + b, "add")

    def __isub__(self, o):
        return self._inplace(o, lambda a, b: a - b, "sub")

    def __imul__(self, o):
        return self._inplace(o, lambda a, b: a * b, "mul")

    def __itruediv__(self, o):
        return self._inplace(o, lambda a, b: a / b, "div")

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _clean_index(self, key):
        if isinstance(key, tuple):
            return tuple(unwrap(k) for k in key)
        return unwrap(key)

    def __getitem__(self, key):
        key = self._clean_index(key)
        return apply_op(lambda x: x[key], self, op_name="getitem")

    def __setitem__(self, key, value):
        if autograd.is_recording() and (self._requires_grad or
                                        self._tape_node is not None):
            raise MXNetError("in-place assignment on an array in a recorded "
                             "graph is not supported")
        import jax.numpy as jnp
        key = self._clean_index(key)
        value = unwrap(value)
        raw = unwrap(self)   # mutation boundary: flush self if pending
        if isinstance(value, (int, float, bool)) or _is_array_like(value):
            if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
                self._data = jnp.broadcast_to(
                    jnp.asarray(value, raw.dtype), self.shape) + \
                    jnp.zeros(self.shape, raw.dtype)
            else:
                self._data = raw.at[key].set(value)
        else:
            raise TypeError(f"cannot assign {type(value)} to NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asnumpy().reshape(())[()])
        raise MXNetError("The truth value of an NDArray with multiple elements "
                         "is ambiguous.")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __repr__(self):
        if is_tracer(self._data):
            return f"<NDArray traced {self.shape} {dtype_name(self._data.dtype)}>"
        arr = self.asnumpy()
        return f"\n{arr}\n<NDArray {'x'.join(map(str, self.shape))} @{self.context}>"


# ---------------------------------------------------------------------------
# creation (reference: src/operator/tensor/init_op.*)
# ---------------------------------------------------------------------------
def _place(raw, ctx):
    import jax
    ctx = ctx or current_context()
    dev = ctx.jax_device()
    return jax.device_put(raw, dev) if dev is not None else jax.device_put(raw)


def array(source_array, ctx=None, dtype=None) -> NDArray:
    import jax
    if isinstance(source_array, NDArray):
        raw = unwrap(source_array)
        if dtype is not None:
            raw = raw.astype(np_dtype(dtype))
        return NDArray(_place(raw, ctx))
    if is_tracer(source_array):
        return NDArray(source_array)
    # reference semantics: dtype defaults to source dtype for ndarray input,
    # float32 for python lists/scalars
    if dtype is None:
        if isinstance(source_array, onp.ndarray):
            a = source_array
            dtype = "float32" if a.dtype == onp.float64 else a.dtype
        else:
            a = onp.asarray(source_array)
            dtype = "float32"
    else:
        a = onp.asarray(source_array)
    a = a.astype(np_dtype(dtype)) if str(a.dtype) != dtype_name(dtype) else a
    return NDArray(_place(a, ctx))


def from_numpy(a, zero_copy=False):
    return array(a)


def zeros(shape, ctx=None, dtype="float32") -> NDArray:
    import jax.numpy as jnp
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.zeros(shape, np_dtype(dtype)), ctx))


def ones(shape, ctx=None, dtype="float32") -> NDArray:
    import jax.numpy as jnp
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.ones(shape, np_dtype(dtype)), ctx))


def full(shape, val, ctx=None, dtype="float32") -> NDArray:
    import jax.numpy as jnp
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.full(shape, val, np_dtype(dtype)), ctx))


def empty(shape, ctx=None, dtype="float32") -> NDArray:
    return zeros(shape, ctx, dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    import jax.numpy as jnp
    a = jnp.arange(start, stop, step, np_dtype(dtype))
    if repeat > 1:
        a = jnp.repeat(a, repeat)
    return NDArray(_place(a, ctx))


def linspace(start, stop, num, endpoint=True, ctx=None, dtype="float32"):
    import jax.numpy as jnp
    return NDArray(_place(jnp.linspace(start, stop, num, endpoint=endpoint,
                                       dtype=np_dtype(dtype)), ctx))


def eye(N, M=0, k=0, ctx=None, dtype="float32"):
    import jax.numpy as jnp
    return NDArray(_place(jnp.eye(N, M if M else None, k, np_dtype(dtype)), ctx))


def zeros_like(a):
    import jax.numpy as jnp
    return apply_op(jnp.zeros_like, a, op_name="zeros_like")


def ones_like(a):
    import jax.numpy as jnp
    return apply_op(jnp.ones_like, a, op_name="ones_like")


def full_like(a, fill_value):
    import jax.numpy as jnp
    return apply_op(lambda x: jnp.full_like(x, fill_value), a, op_name="full_like")


def concatenate(arrays, axis=0):
    from . import ops
    return ops.concat(*arrays, dim=axis)


def waitall():
    """Block until all async work completes (reference ``mx.nd.waitall``).
    Materialization boundary: every live lazy segment flushes first."""
    import jax
    _engine.flush_all()
    try:
        jax.effects_barrier()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# save / load — NDArray container formats (reference: NDArray::Save/Load +
# the C-API list container, src/ndarray/ndarray.cc §5.4 / src/c_api/c_api.cc).
# Two on-disk layouts:
#   "mxtpu"  — own fast path: magic + JSON header + raw blobs.
#   "mxnet"  — the reference 1.x binary .params container, byte-compatible:
#              uint64 list magic 0x112, uint64 reserved, uint64 count,
#              per-array [uint32 V2 magic 0xF993FAC9, int32 stype(=0 dense),
#              uint32 ndim + int64[ndim] shape, int32 dev_type + int32
#              dev_id (cpu(0)), int32 dtype flag, raw blob], then uint64
#              name count + dmlc strings (uint64 length + bytes).
# ``load`` auto-detects either format (and the reference Module convention
# of "arg:"/"aux:" name prefixes is preserved verbatim — gluon's
# load_parameters strips them).  int64/float64 payloads follow the
# framework-wide 32-bit convention on load (jax x64 off): values are
# preserved, the container dtype flag round-trips on save.
# ---------------------------------------------------------------------------
_MAGIC = b"MXTPU\x00\x01\n"
_MX_LIST_MAGIC = 0x112              # c_api.cc kMXAPINDArrayListMagic
_MX_ND_V2_MAGIC = 0xF993FAC9        # ndarray.cc NDARRAY_V2_FILE_MAGIC
_MX_ND_V3_MAGIC = 0xF993FACA        # numpy-shape-semantics variant
# mshadow type flags (mshadow/base.h TypeFlag)
_MX_DTYPE = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3,
             "int32": 4, "int8": 5, "int64": 6, "bool": 7, "bfloat16": 12}
_MX_DTYPE_INV = {v: k for k, v in _MX_DTYPE.items()}


def _to_numpy_pair(a):
    """(numpy array, framework dtype name); bf16 data is kept as bf16 via
    ml_dtypes so the reference flag 12 round-trips bit-exactly."""
    if isinstance(a, NDArray):
        raw = unwrap(a)
        return onp.asarray(raw), dtype_name(raw.dtype)
    np_a = onp.asarray(a)
    return np_a, str(np_a.dtype)


def save(fname, data, format=None):
    """Save NDArrays (list or name dict).  ``format``: "mxtpu" (default,
    own container) or "mxnet" (the reference's binary .params layout —
    use for weight portability with the reference stack)."""
    fmt = format or os.environ.get("MXNET_SAVE_FORMAT", "mxtpu")
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        names = None
        arrays = list(data)
    if fmt in ("mxnet", "reference", "params"):
        return _save_mxnet(fname, names, arrays)
    if fmt != "mxtpu":
        raise MXNetError(f"unknown save format '{fmt}' "
                         "(expected 'mxtpu' or 'mxnet')")
    blobs = []
    header = {"names": names, "tensors": []}
    for a in arrays:
        np_a = a.asnumpy() if isinstance(a, NDArray) else onp.asarray(a)
        dt = dtype_name(a._aval.dtype) if isinstance(a, NDArray) \
            else str(np_a.dtype)
        if dt == "bfloat16":
            np_a = onp.asarray(a.astype("float32").asnumpy())
        blob = np_a.tobytes()
        header["tensors"].append(
            {"dtype": dt, "shape": list(np_a.shape), "nbytes": len(blob),
             "saved_as": str(np_a.dtype)})
        blobs.append(blob)
    hdr = json.dumps(header).encode()
    with open(fname, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack("<Q", len(hdr)))
        f.write(hdr)
        for b in blobs:
            f.write(b)


def _save_mxnet(fname, names, arrays):
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", _MX_LIST_MAGIC, 0))
        f.write(struct.pack("<Q", len(arrays)))
        for a in arrays:
            np_a, dt = _to_numpy_pair(a)
            if dt not in _MX_DTYPE:
                raise MXNetError(
                    f"dtype {dt} has no reference .params type flag")
            f.write(struct.pack("<I", _MX_ND_V2_MAGIC))
            f.write(struct.pack("<i", 0))                 # kDefaultStorage
            f.write(struct.pack("<I", np_a.ndim))
            f.write(struct.pack(f"<{np_a.ndim}q", *np_a.shape))
            f.write(struct.pack("<ii", 1, 0))             # Context cpu(0)
            f.write(struct.pack("<i", _MX_DTYPE[dt]))
            f.write(onp.ascontiguousarray(np_a).tobytes())
        ns = names if names is not None else []
        f.write(struct.pack("<Q", len(ns)))
        for n in ns:
            b = n.encode()
            f.write(struct.pack("<Q", len(b)))
            f.write(b)


def _load_mxnet(f, fname):
    (reserved,) = struct.unpack("<Q", f.read(8))
    (count,) = struct.unpack("<Q", f.read(8))
    arrays = []
    for _ in range(count):
        (magic,) = struct.unpack("<I", f.read(4))
        if magic not in (_MX_ND_V2_MAGIC, _MX_ND_V3_MAGIC):
            raise MXNetError(
                f"{fname}: unsupported NDArray record magic {magic:#x} "
                "(legacy V1 records are not supported)")
        (stype,) = struct.unpack("<i", f.read(4))
        if stype != 0:
            raise MXNetError(
                f"{fname}: sparse storage type {stype} in .params not "
                "supported; densify in the reference before exporting")
        (ndim,) = struct.unpack("<I", f.read(4))
        shape = struct.unpack(f"<{ndim}q", f.read(8 * ndim))
        dev_type, dev_id = struct.unpack("<ii", f.read(8))
        (tf,) = struct.unpack("<i", f.read(4))
        if tf not in _MX_DTYPE_INV:
            raise MXNetError(f"{fname}: unknown dtype flag {tf}")
        dt = _MX_DTYPE_INV[tf]
        if dt == "bfloat16":
            import ml_dtypes
            np_dt = onp.dtype(ml_dtypes.bfloat16)
        else:
            np_dt = onp.dtype(dt)
        n = int(onp.prod(shape)) if ndim else 1
        raw = f.read(n * np_dt.itemsize)
        np_a = onp.frombuffer(raw, dtype=np_dt).reshape(shape)
        if dt == "bfloat16":
            arrays.append(array(onp.asarray(np_a, onp.float32))
                          .astype("bfloat16"))
        else:
            arrays.append(array(np_a))
    names = []
    rest = f.read(8)
    if len(rest) == 8:
        (nnames,) = struct.unpack("<Q", rest)
        for _ in range(nnames):
            (ln,) = struct.unpack("<Q", f.read(8))
            names.append(f.read(ln).decode())
    if not names:
        return arrays
    return dict(zip(names, arrays))


def load(fname):
    """Load an NDArray container — auto-detects the own ("mxtpu") and the
    reference binary .params formats."""
    with open(fname, "rb") as f:
        magic = f.read(len(_MAGIC))
        if magic != _MAGIC:
            if len(magic) == 8 and \
                    struct.unpack("<Q", magic)[0] == _MX_LIST_MAGIC:
                return _load_mxnet(f, fname)
            raise MXNetError(f"{fname}: not an NDArray container file")
        (hlen,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(hlen).decode())
        arrays = []
        for t in header["tensors"]:
            raw = f.read(t["nbytes"])
            a = onp.frombuffer(raw, dtype=t["saved_as"]).reshape(t["shape"])
            nd = array(a, dtype=t["dtype"] if t["dtype"] != "bfloat16" else None)
            if t["dtype"] == "bfloat16":
                nd = nd.astype("bfloat16")
            arrays.append(nd)
    if header["names"] is None:
        return arrays
    return dict(zip(header["names"], arrays))
