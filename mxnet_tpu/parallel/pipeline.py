"""Pipeline parallelism (PP) over the mesh ``pipe`` axis (SURVEY.md §2.3).

The reference has no pipeline parallelism (data parallel only — SURVEY §2.3);
this is TPU-native headroom.  Design: the GPipe/"circulating pipeline"
pattern idiomatic to SPMD meshes (scaling-book recipe) rather than a
per-stage-process scheduler:

- The S pipeline stages are *structurally identical* (the transformer-stack
  case).  Their parameters are **stacked** along a leading stage dimension
  of size S and sharded ``P('pipe')`` — each mesh slot along ``pipe`` holds
  exactly its stage's weights.
- The batch is split into M microbatches.  Inside ``jax.shard_map`` every
  stage runs the *same* program: a ``lax.scan`` over M+S-1 ticks; at each
  tick a stage applies its layer to its current activation and passes the
  result to the next stage with a single ``ppermute`` hop over the ICI
  ring.  Stage 0 feeds fresh microbatches, stage S-1 collects outputs.
- Forward AND backward run through the same scan (the whole pipeline is
  one differentiable jax function — XLA schedules the bubble; no manual
  1F1B scheduler is needed for correctness, and remat can be layered on
  with ``jax.checkpoint`` on the stage function).

Composes with data parallelism: the microbatch dimension can itself be
sharded over the ``data`` mesh axis (dp × pp in one program), and with
tensor parallelism inside the stage function.
"""
from __future__ import annotations

from collections import OrderedDict

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, unwrap
from ..gluon.block import HybridBlock

__all__ = ["spmd_pipeline", "GPipe"]


def spmd_pipeline(stage_fn, stage_params, x, mesh, axis="pipe",
                  data_axis=None):
    """Run a homogeneous S-stage pipeline over the mesh ``axis``.

    ``stage_fn(params, mb) -> mb``   one stage applied to one microbatch;
                                     output shape/dtype must equal input
                                     (the circulating-activation contract).
    ``stage_params``                 pytree whose leaves have leading dim S
                                     (stacked per-stage weights).
    ``x``                            (M, mb, ...) microbatched input.
    ``data_axis``                    optional mesh axis the microbatch dim
                                     (dim 1 of ``x``) is sharded over, for
                                     combined dp x pp.

    Returns the (M, mb, ...) pipeline output (= stage S-1's results).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    S = mesh.shape[axis]
    M = x.shape[0]
    perm = [(i, (i + 1) % S) for i in range(S)]

    x_spec = P(*([None, data_axis] + [None] * (x.ndim - 2))) \
        if data_axis else P()
    out_spec = P(*([axis] + list(x_spec)))

    def worker(params, xs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (clip: past-end ticks re-read the
            # last microbatch; their results never reach the output buffer)
            inp = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            state = jnp.where(idx == 0, inp, state)
            out = stage_fn(params, state)
            # stage S-1 has microbatch t-(S-1)'s final value at tick t; the
            # clipped warmup writes to slot 0 are overwritten at t = S-1
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, out, oidx, 0)
            # one ICI hop: hand the activation to the next stage
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outputs), None

        zero = jnp.zeros(xs.shape[1:], xs.dtype)
        outputs0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(
            tick, (zero, outputs0), jnp.arange(M + S - 1))
        return outputs[None]  # leading stage dim for out_specs

    p_specs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def _place(v, spec):
        from jax.sharding import NamedSharding
        from jax.core import Tracer
        if isinstance(v, Tracer):
            return v
        from . import global_put
        return global_put(v, NamedSharding(mesh, spec))

    stage_params = jax.tree_util.tree_map(
        lambda v: _place(v, P(axis)), stage_params)
    x = _place(x, x_spec)
    # check_vma off: the circulating ppermute produces device-varying
    # values the replication checker mis-models
    out = jax.shard_map(worker, mesh=mesh, in_specs=(p_specs, x_spec),
                        out_specs=out_spec,
                        check_vma=False)(stage_params, x)
    return out[-1]


class _StackedInit:
    """Initializer for stacked (S, ...) stage parameters: each stage slice
    gets an independent draw from ``base`` (the template param's initializer
    if it declared one, else the init the user passed to ``initialize``),
    with per-slice fan computed from the *stage* shape, not the stack."""

    def __init__(self, base, num_stages):
        self.base = base
        self._S = num_stages

    def init_array(self, name, shape, dtype):
        import jax.numpy as jnp
        from .. import initializer as _init_mod
        base = self.base or _init_mod.Xavier()
        if isinstance(base, str):
            base = _init_mod.create(base)
        return jnp.stack([jnp.asarray(base.init_array(name, shape[1:], dtype))
                          for _ in range(self._S)])


class GPipe(HybridBlock):
    """Gluon block wrapping ``spmd_pipeline``: S copies of a stage layer.

    ``stage``            a template HybridBlock with concrete shapes whose
                         output shape equals its input shape (e.g. a
                         transformer encoder cell).
    ``num_stages``       S — must equal ``mesh.shape[axis]`` at call time.
    ``num_microbatches`` M — the batch dim must be divisible by M.

    The template's parameters are re-materialized as stacked ``(S, ...)``
    parameters of this block (independently initialized per stage), so
    checkpointing, ``SPMDTrainer`` and ``shard_params`` all see ordinary
    parameters.  Stacked params should be sharded ``P('pipe')``
    (``pipe_sharding_rules`` below, or ``shard_params(net, mesh,
    rules=[('.*', 'pipe')])`` scoped to this block).

    Stages must be activation-shape-preserving and stateless besides their
    parameters (use LayerNorm, not BatchNorm: moving stats are not
    circulated through the pipeline).
    """

    def __init__(self, stage, num_stages, num_microbatches, mesh=None,
                 axis="pipe", data_axis=None, remat=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        # keep the template out of _children so only the stacked parameters
        # are visible to collect_params/save/load
        object.__setattr__(self, "_stage_template", stage)
        self._num_stages = int(num_stages)
        self._mb = int(num_microbatches)
        self._mesh = mesh
        self._axis = axis
        self._data_axis = data_axis
        self._remat = bool(remat)
        self._stacked: "OrderedDict[str, object]" = OrderedDict()
        # stacked params are declared NOW (not at initialize) so the
        # build-then-load_parameters checkpoint-restore flow works exactly
        # as for ordinary blocks (reference gluon semantics)
        from ..gluon.parameter import Parameter
        S = self._num_stages
        for name, tp in stage._collect_params_with_prefix().items():
            if tp.shape is None or any(not s for s in tp.shape):
                raise MXNetError(
                    f"GPipe: template parameter {name!r} has unknown shape "
                    f"{tp.shape}; give the stage explicit in_units/"
                    f"in_channels (or forward data through it once) before "
                    f"wrapping it in GPipe")
            p = Parameter(name.replace(".", "_"), grad_req=tp.grad_req,
                          shape=(S,) + tuple(tp.shape), dtype=tp.dtype,
                          init=_StackedInit(tp.init, S))
            p.lr_mult, p.wd_mult = tp.lr_mult, tp.wd_mult
            self._stacked[name] = p
            self._reg_params[name.replace(".", "_")] = p

    # -- parameter lifecycle ------------------------------------------------
    def _materialize_params(self, init=None, ctx=None, force_reinit=False):
        # parameters already exist; just resolve which base initializer each
        # stacked draw should use: the template param's own init wins,
        # else the init the user passed (gluon precedence), else Xavier.
        tmpl = self._stage_template._collect_params_with_prefix()
        for name, p in self._stacked.items():
            p.init.base = tmpl[name].init or init

    def pipe_sharding_rules(self):
        """shard_params rules putting every stacked param on the pipe axis."""
        return [(".*", (self._axis,))]

    # -- forward ------------------------------------------------------------
    def _stage_apply(self, param_raws, mb_raw):
        """Run the template stage functionally on raw jax values."""
        from ..gluon.block import Block
        st = self._stage_template
        ps = list(st._collect_params_with_prefix().values())
        olds = [p._nd for p in ps]
        try:
            for p, r in zip(ps, param_raws):
                p._nd = NDArray(r)
            out = Block.__call__(st, NDArray(mb_raw))
            if isinstance(out, (tuple, list)):
                raise MXNetError("GPipe stages must return a single array")
            return unwrap(out)
        finally:
            for p, o in zip(ps, olds):
                p._nd = o

    def forward(self, x):
        import jax
        from ..ndarray.ndarray import apply_op
        if any(p._nd is None for p in self._stacked.values()):
            raise MXNetError("GPipe: parameters not initialized — call "
                             "initialize() or load_parameters() first")
        mesh = self._mesh
        if mesh is None:
            raise MXNetError("GPipe needs a mesh (pass mesh= at construction)")
        if mesh.shape[self._axis] != self._num_stages:
            raise MXNetError(
                f"GPipe: num_stages={self._num_stages} != mesh "
                f"{self._axis}={mesh.shape[self._axis]}")
        M = self._mb
        names = list(self._stacked.keys())
        param_nds = [self._stacked[n].data() for n in names]

        def fn(x_raw, *param_raws):
            B = x_raw.shape[0]
            if B % M:
                raise MXNetError(f"GPipe: batch {B} not divisible by "
                                 f"num_microbatches {M}")
            xm = x_raw.reshape((M, B // M) + x_raw.shape[1:])
            stage = lambda params, mb: self._stage_apply(params, mb)
            if self._remat:
                stage = jax.checkpoint(stage)
            out = spmd_pipeline(stage, list(param_raws), xm, mesh,
                                axis=self._axis, data_axis=self._data_axis)
            return out.reshape((B,) + out.shape[2:])

        return apply_op(fn, x, *param_nds, op_name="gpipe")
