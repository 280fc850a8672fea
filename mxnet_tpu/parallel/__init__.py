"""TPU-native distribution layer (SURVEY.md §2.3/§5.8 — replaces N17–N20).

The reference distributes by *runtime machinery*: per-parameter KVStore
push/pull over NCCL rings or a ZMQ parameter server.  Here distribution is a
*compiler property*: parameters and batches carry ``jax.sharding``
annotations over a ``Mesh``, the train step is one pjit program, and XLA
inserts all-reduce/reduce-scatter/all-gather over ICI (intra-slice) and DCN
(across slices).  ``SPMDTrainer`` is the TPU-native ``gluon.Trainer``: its
compiled step fuses forward, backward, gradient all-reduce and the optimizer
update — the reference needs 4 subsystems (engine, autograd, kvstore,
optimizer ops) for the same loop.

Axis convention: ``data`` (DP), ``model`` (TP), ``pipe`` (PP), ``seq`` (SP).
"""
from __future__ import annotations

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, unwrap
from .. import autograd
from .. import random as _random

__all__ = ["make_mesh", "shard", "replicate", "constraint", "SPMDTrainer",
           "global_put", "ring_attention_config",
           "all_reduce_global", "global_barrier", "DataParallelModel",
           "shard_params", "init_distributed"]


# Mesh size of the SPMD step currently tracing/executing: kernel
# dispatchers (fused FFN, fused conv) consult this instead of the host
# device count — a single-device model on a multi-chip host still fuses,
# while a >1-device mesh falls back to auto-partitionable ops.
_ACTIVE_MESH_SIZE = 1


def active_mesh_size():
    return _ACTIVE_MESH_SIZE


import contextlib as _contextlib


@_contextlib.contextmanager
def _active_mesh(size):
    """Context manager: advertise the executing mesh's size to kernel
    dispatchers for the duration of a traced step."""
    global _ACTIVE_MESH_SIZE
    saved = _ACTIVE_MESH_SIZE
    _ACTIVE_MESH_SIZE = size
    try:
        yield
    finally:
        _ACTIVE_MESH_SIZE = saved


# ring-attention promotion (SPMDTrainer(ring_attention=True)): while a
# ring-enabled step traces, attention dispatchers (ops.flash_attention)
# consult this config and route full-sequence self-attention through the
# ppermute ring instead of the dense/flash single-device paths.
_RING_CFG = [None]


def ring_attention_config():
    """(mesh, seq_axis) while a ring-enabled SPMD step traces, else None."""
    return _RING_CFG[0]


@_contextlib.contextmanager
def _ring_scope(mesh, seq_axis):
    saved = _RING_CFG[0]
    _RING_CFG[0] = (mesh, seq_axis)
    try:
        yield
    finally:
        _RING_CFG[0] = saved


# telemetry backing for the parallel/* metric family (collector at module
# bottom): updated by SPMDTrainer._build and the dryrun overlap referee
_STATS = {"trainers_built": 0, "zero_stage": 0, "mesh_devices": 0,
          "pipeline_stages": 0, "ring_attention_active": 0,
          "collective_overlap_pct": 0.0}


def make_mesh(shape=None, devices=None, axis_names=None):
    """Create a device Mesh.  ``shape`` is a dict like {'data': 4, 'model': 2}
    (one value may be -1 = infer)."""
    import numpy as onp
    import jax
    from jax.sharding import Mesh
    devices = devices if devices is not None else jax.devices()
    if shape is None:
        shape = {"data": len(devices)}
    names = list(shape.keys())
    sizes = list(shape.values())
    n = len(devices)
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total > n:
        raise MXNetError(f"mesh {dict(zip(names, sizes))} needs {total} "
                         f"devices, have {n}")
    dev_array = onp.array(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def global_put(raw, sharding):
    """Place an array on a (possibly multi-process) sharding.

    Single-process: plain device_put.  Multi-process: every host holds the
    SAME full array (SPMD single-program convention) and contributes its
    addressable shards — device_put would need cross-host transfers, which
    the CPU/TPU backends reject for host arrays."""
    import jax
    if jax.process_count() == 1 or getattr(sharding, "mesh", None) is None:
        return jax.device_put(raw, sharding)
    if isinstance(raw, jax.Array) and not raw.is_fully_addressable:
        # already a global (multi-host) array — e.g. an optimizer master
        # copy derived from a sharded param; it cannot round-trip through
        # numpy.  Same sharding: reuse; else reshard device-to-device.
        if raw.sharding == sharding:
            return raw
        return jax.device_put(raw, sharding)
    import numpy as onp
    arr = onp.asarray(raw)
    return jax.make_array_from_process_local_data(sharding, arr,
                                                  global_shape=arr.shape)


def _pspec(spec):
    from jax.sharding import PartitionSpec as P
    if spec is None:
        return P()
    if isinstance(spec, P):
        return spec
    if isinstance(spec, str):
        return P(spec)
    return P(*spec)


def shard(x, mesh, spec):
    """Place an array on the mesh with the given partition spec."""
    import jax
    from jax.sharding import NamedSharding
    raw = unwrap(x)
    out = global_put(raw, NamedSharding(mesh, _pspec(spec)))
    return NDArray(out) if isinstance(x, NDArray) else out


def replicate(x, mesh):
    return shard(x, mesh, None)


def constraint(x, spec):
    """In-program sharding constraint (use inside hybrid_forward)."""
    import jax
    from ..ndarray.ndarray import apply_op
    return apply_op(
        lambda r: jax.lax.with_sharding_constraint(r, _pspec(spec)),
        x, op_name="sharding_constraint")


def shard_params(net, mesh, rules=(), default=None):
    """Assign NamedShardings to a Block's parameters by regex rules.

    ``rules``: list of (regex, spec) matched against structural names; first
    match wins; unmatched -> ``default`` (replicated if None).  The shardings
    are applied immediately (resharding the data) and remembered on the
    Parameter for SPMDTrainer.
    """
    import re
    import jax
    from jax.sharding import NamedSharding
    for name, p in net._collect_params_with_prefix().items():
        spec = default
        for pat, s in rules:
            if re.search(pat, name):
                spec = s
                break
        sharding = NamedSharding(mesh, _pspec(spec))
        p._sharding = sharding
        if p._nd is not None:
            p._nd._data = global_put(p._nd._data, sharding)


class SPMDTrainer:
    """Compiled SPMD training step over a mesh.

    One call = forward + backward + (XLA-inserted) gradient all-reduce +
    optimizer update, compiled once.  Batch arrays are sharded along
    ``data_axis``; parameters use their assigned sharding (replicated by
    default -> pure DP; matrix-sharded via ``shard_params`` -> TP).
    """

    def __init__(self, net, loss_fn, optimizer, mesh, data_axis="data",
                 donate_params=None, zero1=False, zero2=False, zero3=False,
                 skip_nonfinite=False, remat=None, remat_budget_bytes=None,
                 pipeline_stages=None, ring_attention=False,
                 seq_axis="seq", grad_accum=1):
        from .. import optimizer as opt_mod
        self._net = net
        self._loss = loss_fn
        self._optimizer = opt_mod.create(optimizer) \
            if isinstance(optimizer, str) else optimizer
        self._mesh = mesh
        self._data_axis = data_axis
        # ZeRO ladder (each stage implies the previous): 1 = optimizer
        # states sharded over the data axis; 2 = gradients reduce-scattered
        # per-block as backward produces them, each replica updates only
        # its shard, fresh params all-gathered in-step; 3 = parameters
        # also sharded AT REST (all-gathered per use site on demand in
        # forward/backward, the gathered copy discarded after use).  All
        # three compile into the ONE fused step program — donation,
        # skip_nonfinite and remat compose unchanged (docs/PARALLEL.md
        # "Pod-scale training").
        self._zero = 3 if zero3 else (2 if zero2 else (1 if zero1 else 0))
        if self._zero and data_axis not in mesh.shape:
            raise MXNetError(f"zero{self._zero} requires a {data_axis!r} "
                             f"mesh axis, mesh has {dict(mesh.shape)}")
        # pipeline promotion: the net's GPipe block(s) get the mesh and
        # the P('pipe') stacked-param sharding applied here, so the same
        # capture/donation/resume discipline as every other config
        self._pipeline_stages = None
        if pipeline_stages is not None:
            from .pipeline import GPipe
            gps = [b for b in self._iter_blocks(net)
                   if isinstance(b, GPipe)]
            if not gps:
                raise MXNetError("pipeline_stages=%r: the net contains no "
                                 "GPipe block" % (pipeline_stages,))
            for gp in gps:
                if gp._num_stages != int(pipeline_stages):
                    raise MXNetError(
                        f"pipeline_stages={pipeline_stages} != GPipe "
                        f"num_stages={gp._num_stages}")
                if gp._mesh is None:
                    gp._mesh = mesh
                if gp._axis not in mesh.shape or \
                        mesh.shape[gp._axis] != gp._num_stages:
                    raise MXNetError(
                        f"GPipe axis {gp._axis!r}={gp._num_stages} does "
                        f"not match mesh {dict(mesh.shape)}")
                shard_params(gp, mesh, gp.pipe_sharding_rules())
            self._pipeline_stages = int(pipeline_stages)
        # ring-attention promotion: full-sequence self-attention inside
        # the captured step routes through the ppermute ring over
        # ``seq_axis`` (ops.flash_attention consults ring_attention_config
        # while the step traces)
        self._ring = bool(ring_attention)
        self._seq_axis = seq_axis
        if self._ring and seq_axis not in mesh.shape:
            raise MXNetError(f"ring_attention=True requires a "
                             f"{seq_axis!r} mesh axis, mesh has "
                             f"{dict(mesh.shape)}")
        # dedupe shared parameters (e.g. tied src/tgt embeddings) — the same
        # buffer must not be passed/donated twice.  Structural names are
        # kept per param: the in-graph diagnostics tail groups its
        # per-block norms by the owning block's structural path
        # (docs/OBSERVABILITY.md "Training-dynamics observability")
        seen = set()
        self._params = []
        self._param_paths = {}
        for name, p in net._collect_params_with_prefix().items():
            if id(p) not in seen:
                seen.add(id(p))
                self._params.append(p)
                self._param_paths[id(p)] = \
                    name.rsplit(".", 1)[0] if "." in name else name
        self._step_fn = None
        self._states = None
        self._num_update = 0
        # donate_params=None resolves through the ONE donation policy the
        # captured gluon step also follows (engine.donation_enabled —
        # MXNET_STEP_DONATE, default on); an explicit bool overrides.
        # donation-recovery: tests/test_donation.py::test_spmd_policy_follows_env
        from .. import engine as _engine_mod
        self._donate = _engine_mod.donation_enabled() \
            if donate_params is None else bool(donate_params)
        # remat policy: None = respect the net's own block.remat() flags;
        # True/False = force every candidate boundary on/off; 'auto' =
        # ledger-guided search over candidate checkpointing boundaries at
        # first-step build (mxnet_tpu.memory.remat_policy, docs/COMPILE.md)
        if remat not in (None, True, False, "auto"):
            raise MXNetError(f"remat must be None, bool or 'auto', "
                             f"got {remat!r}")
        self._remat_mode = remat
        self._remat_budget = remat_budget_bytes
        self.remat_report = None
        # gradient accumulation (microbatching): the fused step splits
        # the SAME global batch into grad_accum sequential microbatches
        # and accumulates the grads in fp32 inside the one program — the
        # global batch, the optimizer math and the update count are
        # unchanged while the live activation footprint shrinks ~1/N.
        # The Autopilot's OOM-degrade lever doubles it (set_grad_accum)
        if grad_accum is None:
            grad_accum = 1
        if int(grad_accum) < 1:
            raise MXNetError(f"grad_accum must be >= 1, got {grad_accum}")
        self._grad_accum = int(grad_accum)
        self._aux_params = None
        # all-finite skip-step guard, compiled INTO the fused step: when
        # loss or any grad is non-finite the program selects the old
        # params/states (a device-side no-op update) and returns the
        # finite flag — the host never syncs per-parameter
        # (docs/RESILIENCE.md; set before the first step builds)
        self._skip_nonfinite = bool(skip_nonfinite)
        self._last_finite = None
        # shared host->device batch placement policy (io.prefetch.
        # BatchStager): step() and any attached DevicePrefetcher stage
        # through the SAME object, so prefetched batches arrive already
        # on the mesh batch layout and step() passes them through with
        # zero placement dispatches
        self._stager = None
        # in-graph step diagnostics (mxnet_tpu.health): resolved at
        # _build so the fused step compiles the diagnostics tail in (or
        # not) — None when MXNET_STEP_DIAGNOSTICS was off at build
        self._diag_spec = None

    # -- setup -------------------------------------------------------------
    @staticmethod
    def _iter_blocks(block):
        """Depth-first walk over a Block tree (the block itself first)."""
        yield block
        for c in getattr(block, "_children", {}).values():
            yield from SPMDTrainer._iter_blocks(c)

    def _step_ctx(self):
        """The context every trace/dispatch of the fused step runs under:
        mesh size advertised to kernel dispatchers, plus the ring-attention
        config when promoted."""
        ctx = _contextlib.ExitStack()
        ctx.enter_context(_active_mesh(self._mesh.size))
        if self._ring:
            ctx.enter_context(_ring_scope(self._mesh, self._seq_axis))
        return ctx

    def _complete_deferred(self, x):
        """Finish deferred (shape-unknown) parameter init without running
        real compute: one abstract forward under ``jax.eval_shape`` walks the
        net so each layer's ``_ensure_shapes`` fires (reference: first Gluon
        call runs imperatively to complete deferred init — gluon/block.py)."""
        import jax
        from ..gluon.block import Block
        from ..ndarray.ndarray import is_tracer
        net = self._net
        leaves = x if isinstance(x, (tuple, list)) else (x,)
        # snapshot deferred configs: _finish_deferred_init consumes them, and
        # any init that fires *inside* the abstract trace leaves tracers
        confs = {id(p): p._deferred_conf
                 for p in net._collect_params_with_prefix().values()}

        def probe(*raws):
            with autograd._Scope(recording=False, training=False):
                Block.__call__(net, *[NDArray(r) for r in raws])
            return 0

        saved_key = dict(_random._global)
        try:
            jax.eval_shape(probe, *[
                jax.ShapeDtypeStruct(r.shape, r.dtype) for r in leaves])
        finally:
            _random._global.update(saved_key)
        # re-materialize outside the trace anything the probe staged
        seen = {id(p) for p in self._params}
        for name, p in net._collect_params_with_prefix().items():
            raw = None if p._nd is None else p._nd._data
            if raw is None or is_tracer(raw):
                p._nd = None
                if p._deferred_conf is None:
                    p._deferred_conf = confs.get(id(p))
                p._finish_deferred_init()
            if id(p) not in seen:
                seen.add(id(p))
                self._params.append(p)
                self._param_paths[id(p)] = \
                    name.rsplit(".", 1)[0] if "." in name else name

    def _ensure_placed(self):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        for p in self._params:
            if getattr(p, "_sharding", None) is None:
                p._sharding = NamedSharding(self._mesh, P())
                p._nd._data = global_put(p._nd._data, p._sharding)

    def _data_shard_sharding(self, base_sharding, shape):
        """NamedSharding adding the data axis on the first unsharded dim
        of ``shape`` divisible by the dp degree (composes with TP:
        tp-sharded dims keep their axis).  None when no dim qualifies —
        small/odd tensors stay on ``base_sharding``."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        n = self._mesh.shape[self._data_axis]
        spec = tuple(base_sharding.spec) \
            if isinstance(base_sharding, NamedSharding) else ()
        if self._data_axis in spec:
            return None         # already data-sharded (e.g. zero3 params)
        spec = spec + (None,) * (len(shape) - len(spec))
        for d in range(len(shape)):
            if spec[d] is None and shape[d] % n == 0:
                newspec = list(spec)
                newspec[d] = self._data_axis
                return NamedSharding(self._mesh, P(*newspec))
        return None

    def _state_sharding(self, p, s):
        """Sharding for one optimizer-state tensor.

        Default: the owning parameter's sharding. ``zero1`` and up: shard
        parameter-shaped states over the data axis too (ZeRO-1 / XLA's
        cross-replica weight-update sharding — pinning these in/out
        shardings makes XLA compute each state slice on one replica and
        all-gather only the updated weights; reference analogue:
        optimizer-on-server sharding, src/kvstore/kvstore_dist_server.h).
        """
        psh = p._sharding
        if not self._zero or getattr(s, "ndim", 0) == 0:
            return psh
        # first unsharded dim divisible by the dp degree; at zero3 the
        # param itself already carries the data axis and the state simply
        # inherits it (shard-aligned with its parameter)
        return self._data_shard_sharding(psh, s.shape) or psh

    def _apply_zero3_param_sharding(self):
        """zero3: parameters live SHARDED at rest — assign the data-axis
        sharding (first divisible dim, composing with any TP rules) and
        re-place each param buffer.  XLA all-gathers a block's weights at
        its use sites in forward/backward and discards the gathered copy;
        only the 1/N shard persists between steps."""
        for p in self._params:
            if p.grad_req == "null":
                continue        # frozen params stay on their assigned sharding
            sh = self._data_shard_sharding(p._sharding, p.shape)
            if sh is not None:
                p._sharding = sh
                if p._nd is not None:
                    p._nd._data = global_put(p._nd._data, sh)

    def _place_states(self):
        """Compute mp flags + state shardings and (re)place self._states
        onto the mesh — shared by fresh init and checkpoint restore."""
        ps = self._params
        if len(self._states) != len(ps):
            raise MXNetError(
                f"optimizer state count {len(self._states)} does not match "
                f"trainer parameter count {len(ps)} — was this checkpoint "
                f"saved from a different model?")
        self._mp = [self._optimizer.wants_master(unwrap(p.data()))
                    for p in ps]
        self._state_sh = [tuple(self._state_sharding(p, s) for s in st)
                          for p, st in zip(ps, self._states)]
        self._states = [
            tuple(global_put(s, sh) for s, sh in zip(st, shs))
            for st, shs in zip(self._states, self._state_sh)]
        from .. import memory as _memory
        _memory.tag_tree(self._states, "optimizer_state")

    def _init_states(self):
        self._states = [
            tuple(self._optimizer.create_state_multi_precision(0, p.data()))
            for p in self._params]
        self._place_states()

    def _build(self):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # the step's jit compile goes through XLA's persistent cache: a
        # plain step() loop warm-starts, not only precompile()
        from .. import compile as _compile
        _compile.enable_persistent_cache()
        net, loss_fn, optimizer = self._net, self._loss, self._optimizer
        ps = self._params
        n = len(ps)
        if getattr(self, "_state_sh", None) is None:
            # states (and possibly params, via set_data) were installed
            # directly — checkpoint restore before the first step. Re-place
            # BOTH onto the mesh (params keep their assigned sharding, e.g.
            # TP rules; states get fresh shardings incl. ZeRO-1).
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P2
            for p in ps:
                if getattr(p, "_sharding", None) is None:
                    p._sharding = NamedSharding(self._mesh, P2())
                p._nd._data = global_put(p._nd._data, p._sharding)
            if self._zero >= 3:
                self._apply_zero3_param_sharding()
            self._place_states()
        mp_flags = self._mp
        lr_mults = [p.lr_mult for p in ps]
        wd_mults = [p.wd_mult for p in ps]
        trainables = [p.grad_req != "null" for p in ps]
        aux_box = []

        def forward(param_raws, x, y, key):
            from ..gluon.block import _AuxCapture, Block
            olds = [p._nd._data for p in ps]
            try:
                for p, r in zip(ps, param_raws):
                    p._nd._data = r
                cap = _AuxCapture()
                with autograd._Scope(recording=False, training=True), \
                        _random.key_scope(key), cap:
                    xs = [NDArray(r) for r in x] if isinstance(x, (tuple, list)) \
                        else [NDArray(x)]
                    out = Block.__call__(net, *xs)
                    ys = tuple(NDArray(r) for r in y) \
                        if isinstance(y, (tuple, list)) else NDArray(y)
                    loss = loss_fn(out, ys)
                    loss_scalar = unwrap(loss.mean())
            finally:
                for p, o in zip(ps, olds):
                    p._nd._data = o
            if not aux_box:
                aux_box.append([p for p, _ in cap.items])
            return loss_scalar, [r for _, r in cap.items]

        guard = self._skip_nonfinite
        # zero2/zero3 gradient shardings: pinning each gradient to the
        # data-sharded spec AT ITS PRODUCTION POINT (before the barrier
        # materializes the grad set) makes XLA schedule one reduce-scatter
        # per block as backward emits it — interleaved with the remaining
        # backward compute — instead of one fused collective at the end.
        # zero3 grads inherit their (already data-sharded) param spec; odd
        # tensors with no dp-divisible dim stay replicated.
        grad_sh = [None] * n
        if self._zero >= 2:
            grad_sh = []
            for i, p in enumerate(ps):
                if not trainables[i]:
                    grad_sh.append(None)
                    continue
                sh = self._data_shard_sharding(p._sharding, p.shape)
                if sh is None and self._zero >= 3 and self._data_axis in \
                        tuple(getattr(p._sharding, "spec", ()) or ()):
                    sh = p._sharding
                grad_sh.append(sh)
        # exposed for the dryrun memory referee: per-grad pinned shardings
        # (None = full/replicated grad), the basis for its analytic
        # per-device gradient-byte accounting
        self._grad_sh = grad_sh
        # diagnostics tail, compiled INTO the fused step exactly like the
        # all-finite guard: loss + grad/param/update norms + per-block
        # folds + nonfinite counts as one extra fp32 vector output — the
        # co-compiled reductions are near-free, and the host reads the
        # whole vector once per step (one step behind the dispatch)
        from .. import health as _health
        diag_spec = diag_fn = None
        if _health.enabled():
            diag_spec = _health.make_spec(
                ps, block_paths=[self._param_paths.get(id(p), "unscoped")
                                 for p in ps])
            diag_fn = _health.build_diag_fn(diag_spec)
            if self._zero >= 2:
                # sharded-state diag discipline: fold each tensor across
                # the mesh (all-gather, riding the same in-step gathers
                # zero2/3 already schedule) BEFORE the square-sums, so the
                # reduction order — and therefore every per-block norm the
                # host reads — is bit-identical to the replicated
                # trainer's.  Shard-local partial sums + psum would differ
                # in the last ulps (reduction reassociation), breaking the
                # cross-config comparability the run ledger relies on.
                from jax.sharding import NamedSharding as _NS
                from jax.sharding import PartitionSpec as _P
                _rep = _NS(self._mesh, _P())
                base_diag = diag_fn

                def diag_fn(loss, rescale, *tensors):
                    import jax as _jax
                    tensors = [
                        _jax.lax.with_sharding_constraint(tv, _rep)
                        for tv in tensors]
                    # the barrier pins the gather: without it the
                    # partitioner rewrites gather+reduce into shard-local
                    # partial sums + all-reduce, whose association drifts
                    # from the replicated program in the last ulps
                    tensors = _jax.lax.optimization_barrier(tuple(tensors))
                    return base_diag(loss, rescale, *tensors)
        self._diag_spec = diag_spec

        accum = self._grad_accum
        if accum > 1:
            # microbatch split must divide every batch leaf's leading dim
            # — the global batch is reshaped (accum, B/accum, ...), never
            # padded or dropped
            for proto in (self._x_proto, self._y_proto):
                for leaf in jax.tree_util.tree_leaves(proto):
                    dim = getattr(leaf, "shape", (0,))[0] \
                        if getattr(leaf, "ndim", 0) else 0
                    if dim % accum != 0:
                        raise MXNetError(
                            f"grad_accum={accum} does not divide the "
                            f"batch leading dimension {dim}")

        def step(param_raws, states, x, y, key, lr, t, rescale):
            import jax.numpy as jnp
            # derive the per-step key IN-GRAPH from a cached base key: a
            # host-side jax.random.split every step is one more dispatch
            # on the step's critical path
            key = jax.random.fold_in(key, t)
            grad_fn = jax.value_and_grad(forward, has_aux=True)
            if accum == 1:
                (loss, aux), grads = grad_fn(param_raws, x, y, key)
            else:
                # sequential microbatches inside the ONE program: grads
                # accumulate in fp32 (deterministic association — the
                # unrolled order is fixed), then average back to the
                # param dtype so everything downstream (sharding pins,
                # the barrier, the finite guard, the optimizer loop and
                # the diagnostics tail) is unchanged
                def _micro(tree, i):
                    return jax.tree_util.tree_map(
                        lambda a: a.reshape(
                            (accum, a.shape[0] // accum) + a.shape[1:])[i],
                        tree)

                loss = None
                grads = None
                aux = None
                for i in range(accum):
                    (li, aux), gi = grad_fn(
                        param_raws, _micro(x, i), _micro(y, i),
                        jax.random.fold_in(key, i))
                    li = li.astype(jnp.float32)
                    gi = [g.astype(jnp.float32) for g in gi]
                    loss = li if loss is None else loss + li
                    grads = gi if grads is None else \
                        [a + b for a, b in zip(grads, gi)]
                loss = loss / accum
                grads = [(g / accum).astype(param_raws[i].dtype)
                         for i, g in enumerate(grads)]
            if any(sh is not None for sh in grad_sh):
                # per-block reduce-scatter scheduled where backward
                # produces each grad (zero2/3) — see grad_sh above
                grads = [jax.lax.with_sharding_constraint(g, sh)
                         if sh is not None else g
                         for g, sh in zip(grads, grad_sh)]
            # keep optimizer reductions (e.g. LAMB norms) OUT of the wgrad
            # matmul fusions: a fused reduce epilogue drops the TPU matmul
            # emitter to ~1/3 rate (measured on the BERT step — wgrad
            # fusions at 39-52 TF/s vs 160-180 for clean same-shape
            # matmuls). The barrier materializes grads first; the extra
            # read is epsilon next to the matmul win.
            grads = jax.lax.optimization_barrier(grads)
            # the update, the non-finite skip and LAMB's norms: one part
            # of the step in a device trace.  The forward's operations
            # carry jvp(...) on their name stack and the backward's
            # transpose(jvp(...)), beside the model's own parts
            with _telemetry_mod.part("optimizer"):
                finite = jnp.asarray(True)
                if guard:
                    finite = jnp.isfinite(loss)
                    for i in range(n):
                        if trainables[i]:
                            finite = jnp.logical_and(
                                finite, jnp.all(jnp.isfinite(grads[i])))
                new_params, new_states = [], []
                for i in range(n):
                    if trainables[i]:
                        g = grads[i] * rescale.astype(grads[i].dtype)
                        w, s = optimizer.step_multi_precision(
                            param_raws[i], g, states[i], lr * lr_mults[i],
                            optimizer.wd * wd_mults[i], t=t, mp=mp_flags[i])
                        if self._zero == 2 and grad_sh[i] is not None:
                            # each replica updates only its 1/N weight shard;
                            # the replicated out_sharding then all-gathers the
                            # fresh params in-step (one collective per block)
                            w = jax.lax.with_sharding_constraint(w, grad_sh[i])
                        if guard:
                            # skip-step select: old values win when any
                            # grad/loss is non-finite (a no-op update fused
                            # into the same program — zero extra dispatches)
                            w = jnp.where(finite, w, param_raws[i])
                            s = jax.tree_util.tree_map(
                                lambda sn, so: jnp.where(finite, sn, so),
                                s, states[i])
                    else:
                        w, s = param_raws[i], states[i]
                    new_params.append(w)
                    new_states.append(s)
                if guard and aux_box and aux_box[0]:
                    # aux (BN running stats) must skip too: without this a
                    # NaN batch leaves weights intact but poisons mean/var,
                    # making every later forward non-finite anyway
                    pos = {id(p): i for i, p in enumerate(ps)}
                    aux = [jnp.where(finite, a, param_raws[pos[id(p)]])
                           if id(p) in pos else a
                           for p, a in zip(aux_box[0], aux)]
            if diag_fn is not None:
                with _telemetry_mod.part("optimizer"), \
                        _telemetry_mod.part("health"):
                    diag = diag_fn(loss, rescale, *param_raws, *grads,
                                   *new_params)
                return loss, new_params, new_states, aux, finite, diag
            return loss, new_params, new_states, aux, finite

        param_sh = [p._sharding for p in ps]
        state_sh = self._state_sh
        batch_sh = self._get_stager().sharding
        rep = NamedSharding(self._mesh, P())

        def batch_spec(tree):
            return jax.tree_util.tree_map(lambda _: batch_sh, tree)

        self._batch_sh = batch_sh
        # pin output shardings: without this XLA may return updated params
        # with a layout coupled to the compute (e.g. vocab-sharded bias) and
        # the next call's in_shardings would mismatch.
        # donation-recovery: tests/test_donation.py::test_spmd_donated_failure_recover_and_retry
        out_sh = (rep, param_sh, state_sh, None, rep)
        if diag_fn is not None:
            out_sh = out_sh + (rep,)
        self._step_fn = jax.jit(
            step,
            in_shardings=(param_sh, state_sh, batch_spec(self._x_proto),
                          batch_spec(self._y_proto), rep, rep, rep, rep),
            out_shardings=out_sh,
            donate_argnums=(0, 1) if self._donate else (),
        )
        self._aux_box = aux_box
        _STATS["trainers_built"] += 1
        _STATS["zero_stage"] = self._zero
        _STATS["mesh_devices"] = self._mesh.size
        _STATS["pipeline_stages"] = self._pipeline_stages or 0
        _STATS["ring_attention_active"] = 1 if self._ring else 0

    def _prepare_step_args(self, data, label, t):
        """Lazy init (deferred shapes, placement, states, _build) + batch
        placement + the exact ``_step_fn`` argument tuple for update ``t``
        — ONE code path shared by :meth:`step` and :meth:`precompile`, so
        the lowered avals (and therefore the persistent-cache
        fingerprint) cannot drift between warmup and the hot loop."""
        x = self._unwrap_tree(data)
        y = self._unwrap_tree(label)
        if self._states is None:
            if any(p._nd is None for p in self._params):
                self._complete_deferred(x)
            self._ensure_placed()
            if self._zero >= 3:
                self._apply_zero3_param_sharding()
            self._init_states()
        if self._step_fn is None:
            self._x_proto, self._y_proto = x, y
            self._apply_remat_policy(x, y, t)
            if self._step_fn is None:
                self._build()
        return self._step_args(x, y, t)

    def _step_args(self, x, y, t):
        """Batch placement + the exact ``_step_fn`` argument tuple for
        update ``t`` (split from :meth:`_prepare_step_args` so the remat
        policy search can lower candidate programs on real avals)."""
        import jax
        x = jax.tree_util.tree_map(self._put_batch, x)
        y = jax.tree_util.tree_map(self._put_batch, y)
        if getattr(self, "_base_key", None) is None:
            self._base_key = _random.next_key()
        opt = self._optimizer
        lr = opt.lr_scheduler(t) if opt.lr_scheduler else opt.lr
        return ([unwrap(p.data()) for p in self._params], self._states,
                x, y, self._base_key,
                self._cached_scalar("lr", float(lr)), t,
                self._cached_scalar("rescale", float(opt.rescale_grad)))

    def _apply_remat_policy(self, x, y, t):
        """Resolve the ``remat=`` mode before the first build: bools force
        every candidate boundary, ``'auto'`` runs the ledger-guided search
        (compile each candidate policy, read XLA's temp/peak bytes from
        ``memory.record_program``, pick boundaries — docs/COMPILE.md)."""
        mode = self._remat_mode
        if mode is None:
            return
        from ..memory import remat_policy as _rp
        blocks = _rp.candidate_blocks(self._net)
        if not blocks:
            import warnings
            warnings.warn("SPMDTrainer(remat=%r): no candidate "
                          "checkpointing boundaries found (no repeated "
                          "HybridBlock groups in the net)" % (mode,))
            return
        if mode is True or mode is False:
            _rp.apply_mask(blocks, [mode] * len(blocks))
            return
        args = self._step_args(x, y, t)

        def build_compile():
            self._step_fn = None
            self._build()
            with self._step_ctx():
                return self._step_fn.lower(*args).compile()

        self.remat_report = _rp.search(
            build_compile, blocks, budget_bytes=self._remat_budget,
            label="spmd_step")
        # the winner's flags are applied; the caller rebuilds _step_fn
        # under them (its first dispatch warm-loads the winner's
        # executable through the persistent compile cache)
        self._step_fn = None

    # -- ahead-of-time compilation -----------------------------------------
    def precompile(self, data, label):
        """Compile the fused SPMD step BEFORE the first :meth:`step` —
        ``jit(...).lower(...).compile()`` on example-shaped batches (no
        training step executes, no optimizer state mutates).

        The trainer's build turns the persistent compilation cache on
        (unless ``MXNET_COMPILE_CACHE=0``), so the XLA executable lands on
        disk: a restarted process — or the first :meth:`step` here, which
        re-traces and fetches the same fingerprint — skips the XLA compile.
        Returns ``{"lower_s", "compile_s", "cache_dir", "key", "flops",
        "compiled"}``; ``compiled`` is the ``jax.stages.Compiled`` step
        (chip_smoke.py reads kernels and placement from its HLO text and
        input shardings).
        """
        import time as _time
        from .. import compile as _compile
        cache_dir = _compile.enable_persistent_cache()
        args = self._prepare_step_args(data, label, self._num_update + 1)
        with self._step_ctx():
            t0 = _time.perf_counter()
            lowered = self._step_fn.lower(*args)
            t1 = _time.perf_counter()
            compiled = lowered.compile()
            t2 = _time.perf_counter()
        # both ledgers key the step program by its StableHLO fingerprint
        # (the ProgramCache key the first step() warm-loads by), so a
        # caller of precompile() can read the fused step's flops back
        # out of the cost ledger by the key it returns
        key = None
        try:
            key = _compile.fingerprint_lowered(lowered)
        except Exception:   # noqa: BLE001 — the key is best-effort
            key = None
        from .. import costs as _costs
        from .. import memory as _memory
        _memory.record_program(compiled, key=key, label="spmd_step",
                               kind="spmd_step")
        cost_entry = _costs.record_program(compiled, key=key,
                                           label="spmd_step",
                                           kind="spmd_step")
        return {"lower_s": t1 - t0, "compile_s": t2 - t1,
                "cache_dir": cache_dir, "key": key,
                "flops": (cost_entry or {}).get("flops"),
                "compiled": compiled}

    # -- public ------------------------------------------------------------
    @staticmethod
    def _unwrap_tree(v):
        if isinstance(v, (tuple, list)):
            return tuple(unwrap(e) for e in v)
        return unwrap(v)

    def _cached_scalar(self, name, val):
        """Device fp32 scalar, re-uploaded only when the value changes
        (a fresh jnp.asarray per step is a host->device upload)."""
        import jax.numpy as jnp
        cache = getattr(self, "_scalar_cache", None)
        if cache is None:
            cache = self._scalar_cache = {}
        hit = cache.get(name)
        if hit is None or hit[0] != val:
            hit = (val, jnp.asarray(val, "float32"))
            cache[name] = hit
        return hit[1]

    def _get_stager(self):
        """The trainer's BatchStager (mesh batch layout over
        ``data_axis``), created lazily so import stays light."""
        if self._stager is None:
            from ..io.prefetch import BatchStager
            self._stager = BatchStager(mesh=self._mesh,
                                       data_axis=self._data_axis)
        return self._stager

    def _put_batch(self, raw):
        """Batch-leaf placement through the shared BatchStager: identity
        memoization for repeated buffers, and — the ``from_prefetcher``
        fast path — a jax.Array already laid out on the mesh batch
        sharding (a :class:`~mxnet_tpu.io.DevicePrefetcher`'s output)
        passes through with zero dispatches."""
        return self._get_stager().put(raw)

    def attach_prefetcher(self, source, depth=None):
        """Wrap ``source`` (DataIter / DataLoader / iterable of
        ``(data, label)`` batches) in a
        :class:`~mxnet_tpu.io.DevicePrefetcher` staging onto THIS
        trainer's mesh batch layout.  The prefetcher shares the trainer's
        BatchStager (one memo, one placement policy), so while step N
        computes, batch N+1 uploads on the staging thread and
        :meth:`step` recognizes its leaves as already-sharded — the
        host->device transfer leaves the critical path (docs/IO.md)."""
        from ..io.prefetch import DevicePrefetcher
        return DevicePrefetcher(source, stager=self._get_stager(),
                                depth=depth)

    def step(self, data, label):
        """Run one compiled training step; returns the (device) loss.

        ``data``/``label`` may each be one NDArray or a tuple (multi-input
        models like BERT); every leaf is sharded on the data axis.

        Multi-process convention (SPMD single-program): every process
        passes the SAME full global batch and contributes its addressable
        shard — do NOT pass distinct per-worker batches (half of each
        host's rows would be silently dropped).  Shard at the data source
        instead: give every worker the same global index stream (e.g.
        ImageRecordIter num_parts/part_index composing the global batch in
        the same order on every host).

        Per-step host->device scalar uploads and key splits are kept off
        the critical path: the base key is drawn once (per-step keys are
        folded in-graph from t) and lr/rescale device scalars are cached
        until their value changes (see ``_prepare_step_args``)."""
        from .. import faults as _faults
        from .. import health as _health
        from .. import telemetry as _telemetry
        # step boundary at entry: the previous implicit step closes and a
        # fresh monotonic id opens — a retried (faulted) step gets its own
        # id, so retry timelines stay distinguishable in the flight
        # recorder (docs/OBSERVABILITY.md)
        _telemetry.step_boundary("train")
        if _health.enabled():
            # consume the PREVIOUS step's diagnostics vector: its device
            # work necessarily finished before this step can run, so the
            # one-step-behind read adds no sync point
            _health.poll()
        _faults.point("trainer.step")
        # commit the update count only after the dispatch succeeds: a
        # retried transient failure must re-run with the SAME t, or the
        # LR schedule / Adam bias correction skews by one per retry
        t = self._num_update + 1
        with _telemetry.phase("stage"):
            args = self._prepare_step_args(data, label, t)
        if self._zero >= 2:
            # the step program about to dispatch carries the new
            # collectives; both points fire BEFORE the dispatch so an
            # injected preemption kills the step with params/states/t
            # uncommitted — elastic_run's restore+retry then replays the
            # SAME update and resume stays bit-identical
            # (docs/RESILIENCE.md fault-point registry)
            _faults.point("collective.reduce_scatter")
            _faults.point("collective.all_gather")
        diag = None
        with self._step_ctx(), \
                _telemetry.phase("dispatch"):
            if self._diag_spec is not None:
                (loss, new_params, self._states, aux, self._last_finite,
                 diag) = self._step_fn(*args)
            else:
                loss, new_params, self._states, aux, self._last_finite = \
                    self._step_fn(*args)
        self._num_update = t
        if diag is not None and _health.enabled():
            # gate on the RUNTIME switch, not just the build-time spec:
            # the compiled step keeps returning the diag vector after a
            # mid-run health.enable(False), but nothing would poll the
            # queue anymore — submitting then would grow it unbounded
            opt = self._optimizer
            lr = opt.lr_scheduler(t) if opt.lr_scheduler else opt.lr
            _health.submit_step("spmd", t, diag, self._diag_spec,
                                float(lr))
        for p, w in zip(self._params, new_params):
            p._nd._data = w
        if aux and self._aux_box and self._aux_box[0]:
            for p, raw in zip(self._aux_box[0], aux):
                p._nd._data = raw
        from .. import memory as _memory
        if _memory._census_active:
            # the fused step returned fresh state buffers: keep their
            # census origin (the olds retire through GC)
            _memory.tag_tree(self._states, "optimizer_state")
        return NDArray(loss)

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def grad_accum(self):
        """The microbatch split of the fused step (1 = whole batch)."""
        return self._grad_accum

    def set_grad_accum(self, n):
        """Change the microbatch split; the next step rebuilds the fused
        program (the global batch, optimizer math and update count are
        unchanged — only the live activation footprint shrinks).  The
        Autopilot's OOM-degrade lever doubles this."""
        n = int(n)
        if n < 1:
            raise MXNetError(f"grad_accum must be >= 1, got {n}")
        if n != self._grad_accum:
            self._grad_accum = n
            self._step_fn = None
        return self._grad_accum

    def tighten_remat(self):
        """Degrade lever: spend compute for memory by rematerializing
        more.  ``remat=None/False`` flips to forcing every candidate
        boundary on; ``remat='auto'`` re-searches under a 20%-tighter
        budget.  Returns a description of the change (None when already
        at the tightest setting — no lever left) and invalidates the
        step program so the next step rebuilds under it."""
        mode = self._remat_mode
        if mode is True:
            return None
        if mode == "auto":
            if self._remat_budget is None:
                self._remat_mode = True
                desc = "remat 'auto' (no budget) -> force-all boundaries"
            else:
                self._remat_budget = int(self._remat_budget * 0.8)
                desc = ("remat 'auto' budget tightened 20% -> "
                        f"{self._remat_budget} bytes (re-search)")
        else:
            self._remat_mode = True
            desc = f"remat {mode!r} -> force-all candidate boundaries"
        self._step_fn = None
        return desc

    @property
    def last_step_finite(self):
        """Device-side bool from the fused all-finite guard of the last
        step (None before the first step or with ``skip_nonfinite=False``
        — then the flag is the compiled constant True).  Reading it with
        ``bool()`` is the ONE host sync of the skip-step path."""
        return self._last_finite


class DataParallelModel:
    """Inference-side SPMD wrapper: shard batch, replicate params."""

    def __init__(self, net, mesh, data_axis="data"):
        self._net = net
        self._mesh = mesh
        self._axis = data_axis
        for p in net._collect_params_with_prefix().values():
            replicate_param(p, mesh)

    def __call__(self, x):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        x = shard(x, self._mesh, P(self._axis))
        # advertise the mesh to kernel dispatchers (fused FFN etc.) so
        # non-partitionable custom calls fall back to the layer path
        with _active_mesh(self._mesh.size):
            return self._net(x)


def replicate_param(p, mesh):
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    sh = NamedSharding(mesh, P())
    p._sharding = sh
    if p._nd is not None:
        p._nd._data = global_put(p._nd._data, sh)


# ---------------------------------------------------------------------------
# cross-process collectives for the kvstore dist_* path
# ---------------------------------------------------------------------------
def all_reduce_global(raw):
    import jax
    if jax.process_count() == 1:
        return raw
    from jax.experimental import multihost_utils
    from .. import telemetry as _telemetry
    with _telemetry.phase("collective", op="all_reduce"):
        g = multihost_utils.process_allgather(raw)
        return g.sum(axis=0)


BARRIER_TIMEOUT_EXIT_CODE = 42


def global_barrier(name="mxnet_tpu_barrier", timeout=None):
    """Cross-process barrier with dead-peer detection (SURVEY §5.3).

    A dead peer stalls a collective barrier forever (the reference's
    dist_sync has the same failure mode).  With ``timeout`` seconds (default
    from ``MXNET_BARRIER_TIMEOUT``; launcher flag ``--barrier-timeout``),
    a watchdog turns the silent stall into a detectable worker death: it
    logs and exits with code ``BARRIER_TIMEOUT_EXIT_CODE`` so the
    supervising launcher can abort + relaunch the job, which then resumes
    from the latest checkpoint."""
    import jax
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    from ..util import getenv
    if timeout is None:
        timeout = getenv("MXNET_BARRIER_TIMEOUT") or None
    from .. import telemetry as _telemetry
    if not timeout:
        with _telemetry.phase("collective", op="barrier"):
            multihost_utils.sync_global_devices(name)
        return
    import threading
    done = threading.Event()

    def watchdog():
        if not done.wait(timeout):
            import os as _os
            import sys as _sys
            print(f"[mxnet_tpu] barrier '{name}' timed out after "
                  f"{timeout:.0f}s (peer presumed dead); aborting worker",
                  file=_sys.stderr, flush=True)
            _os._exit(BARRIER_TIMEOUT_EXIT_CODE)

    th = threading.Thread(target=watchdog, daemon=True)
    th.start()
    try:
        with _telemetry.phase("collective", op="barrier"):
            multihost_utils.sync_global_devices(name)
    finally:
        done.set()


from . import ring_attention  # noqa: E402,F401
from .ring_attention import ring_attention as ring_attention_fn  # noqa: E402,F401
from . import pipeline  # noqa: E402,F401
from .pipeline import spmd_pipeline, GPipe  # noqa: E402,F401
from . import moe  # noqa: E402,F401
from .moe import MoE, DroplessMoE, moe_sharding_rules  # noqa: E402,F401

from .. import telemetry as _telemetry_mod  # noqa: E402


def _telemetry_collect():
    return dict(
        (("parallel/" + k), v) for k, v in _STATS.items())


_telemetry_mod.register_collector("parallel", _telemetry_collect, {
    "parallel/trainers_built": ("counter",
                                "fused SPMD step programs built "
                                "(one per SPMDTrainer compile)"),
    "parallel/zero_stage": ("gauge",
                            "ZeRO stage of the most recently built "
                            "trainer (0 = replicated, 1/2/3)"),
    "parallel/mesh_devices": ("gauge",
                              "device count of the most recently built "
                              "trainer's mesh"),
    "parallel/pipeline_stages": ("gauge",
                                 "pipeline stages of the most recently "
                                 "built trainer (0 = no pipeline)"),
    "parallel/ring_attention_active": ("gauge",
                                       "1 while the most recently built "
                                       "trainer routes self-attention "
                                       "through the ppermute ring"),
    "parallel/collective_overlap_pct": ("gauge",
                                        "last measured collective-compute "
                                        "overlap (percent of standalone "
                                        "collective wall hidden by the "
                                        "fused zero2/3 step — the dryrun "
                                        "overlap referee)"),
})


def init_distributed(coordinator=None, num_processes=None, process_id=None):
    """Join the multi-process coordination service (reference:
    ps-lite Postoffice::Start env rendezvous, SURVEY.md §3.4/§5.8).

    Reads ``MXNET_COORDINATOR`` / ``MXNET_NUM_WORKERS`` / ``MXNET_WORKER_ID``
    (set by tools/launch.py; DMLC_* spellings accepted) when arguments are
    omitted.  No-op when launched single-process.  Returns (rank, size)."""
    import os

    import jax
    coordinator = coordinator or os.environ.get("MXNET_COORDINATOR")
    if coordinator is None and os.environ.get("DMLC_PS_ROOT_URI"):
        coordinator = (os.environ["DMLC_PS_ROOT_URI"] + ":" +
                       os.environ.get("DMLC_PS_ROOT_PORT", "9000"))
    num_processes = num_processes if num_processes is not None else int(
        os.environ.get("MXNET_NUM_WORKERS",
                       os.environ.get("DMLC_NUM_WORKER", "1")))
    process_id = process_id if process_id is not None else int(
        os.environ.get("MXNET_WORKER_ID",
                       os.environ.get("DMLC_WORKER_ID", "0")))
    if coordinator is None or num_processes <= 1:
        return 0, 1
    if not jax.distributed.is_initialized():
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    if jax.process_count() != num_processes:
        raise MXNetError(
            f"distributed bootstrap joined {jax.process_count()} processes, "
            f"expected {num_processes} (coordinator {coordinator})")
    return jax.process_index(), jax.process_count()
