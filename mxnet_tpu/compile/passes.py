"""mxnet_tpu.compile.passes — deterministic rewrite passes over captured
programs.

The repo captures whole serving buckets / generation prefills as single
programs (``jax.make_jaxpr``), but until now treated the captured jaxpr
as opaque: capture -> lower -> AOT compile -> ProgramCache.  This module
is the Relay-style pass layer in between (PAPERS.md: "A New IR for
Machine Learning Frameworks"; "Operator Fusion in XLA"): a small,
deterministic pipeline that inspects and rewrites the captured program
BEFORE lowering/persistence, under the repo's standing referee
discipline — every pass's output is validated against the unrewritten
program on example inputs, a failed validation discards the rewrite
(serve correct > serve fast), and an **empty pipeline is bit-identical**
because no capture-replay happens at all (callers jit the original
function).

* :class:`CapturedProgram` — a ClosedJaxpr + arg/result trees, with
  ``as_callable()`` (re-traceable replay) and a bytes/FLOPs estimate.
* :class:`GraphPass` — ``run(prog) -> rewritten | None``; declares a
  ``tolerance`` (0.0 = validation must be bit-exact).
* :class:`PassPipeline` — runs passes in order, validates each against
  its input program, records a per-pass before->after bytes/FLOPs ledger
  entry in ``mxnet_tpu.costs`` (``record_pass``), and exposes a
  ``fingerprint()`` that callers fold into the ProgramCache key so a
  rewritten program can NEVER stale-hit its unrewritten twin.
* Built-in passes: ``dce`` (drop dead equations; exact) and
  ``int8_residency`` (fold dequantize -> glue -> quantize bridges
  between quantized layers into one int8-resident rescale, so
  layer-to-layer activations stay int8 and dequantization happens only
  at graph outputs — the PTQ serving mode, docs/COMPILE_PASSES.md).

Selection: the ``MXNET_COMPILE_PASSES`` env knob (comma-separated pass
names) is the process default; ``InferenceEngine(compile_passes=...)``,
``GenerationEngine(compile_passes=...)`` and
``ReplicaSpec(compile_passes=...)`` override per model.  Telemetry:
``compile/passes_*`` counters ride the compile collector
(docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import hashlib
import logging
import threading
import time

import numpy as onp

from .. import util
from ..base import MXNetError

__all__ = ["CapturedProgram", "GraphPass", "PassPipeline", "DCEPass",
           "Int8ResidencyPass", "register_pass", "available_passes",
           "resolve_pipeline", "telemetry_stats", "reset_stats",
           "candidate_specs", "QUANTIZE_MARKER", "DEQUANTIZE_MARKER"]

_LOG = logging.getLogger("mxnet_tpu.compile.passes")

#: jit'd marker-function names the quantized layers stage as ``jit``
#: equations (contrib/quantization.py) — the int8_residency pass's
#: pattern anchors.
QUANTIZE_MARKER = "_mx_quantize_act"
DEQUANTIZE_MARKER = "_mx_dequantize_act"

# -- pipeline counters for the compile/* telemetry collector ----------------
_stats_lock = threading.Lock()
_stats = {
    "runs": 0,                  # pipeline invocations
    "rewrites": 0,              # passes that changed + validated clean
    "unchanged": 0,             # passes that matched nothing
    "validation_failures": 0,   # rewrites discarded by the referee
    "errors": 0,                # passes that raised (rewrite discarded)
    "bytes_saved": 0,           # estimated glue bytes removed (sum)
}


def telemetry_stats():
    """The ``compile/passes_*`` counter dict (compile collector)."""
    with _stats_lock:
        return {"compile/passes_" + k: v for k, v in _stats.items()}


def reset_stats():
    """Zero the pipeline counters (tests)."""
    with _stats_lock:
        for k in _stats:
            _stats[k] = 0


def _bump(key, n=1):
    with _stats_lock:
        _stats[key] += n


# ---------------------------------------------------------------------------
# captured programs
# ---------------------------------------------------------------------------
def _aval_bytes(aval):
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * onp.dtype(aval.dtype).itemsize
    except Exception:               # noqa: BLE001 — odd aval
        return 0


#: primitives treated as materialization barriers by the byte estimator:
#: their operands/results cross a fusion boundary in practice (dot/conv
#: epilogues, opaque calls), so glue tensors feeding them count as HBM
#: traffic.  A documented MODEL, not a measurement — XLA's own
#: ``bytes accessed`` lands in the cost ledger at compile time and stays
#: the authoritative figure (docs/COMPILE_PASSES.md).
_BARRIER_PRIMS = frozenset((
    "dot_general", "conv_general_dilated", "jit", "custom_jvp_call",
    "custom_vjp_call", "while", "scan", "cond",
))


class CapturedProgram:
    """A captured program: ClosedJaxpr + the arg/result pytree structure
    needed to call it again.

    ``capture()`` traces ``fn`` at example arguments (concrete arrays
    and/or ``jax.ShapeDtypeStruct`` specs); ``as_callable()`` returns a
    function with the original signature that replays the (possibly
    rewritten) jaxpr — hand it to ``jax.jit`` exactly where the original
    ``fn`` would have gone.
    """

    def __init__(self, closed, in_tree, out_tree, label=""):
        self.closed = closed
        self.in_tree = in_tree
        self.out_tree = out_tree
        self.label = label

    @classmethod
    def capture(cls, fn, example_args, label=""):
        import jax
        from jax import tree_util
        _flat, in_tree = tree_util.tree_flatten(tuple(example_args))
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
            *example_args)
        out_tree = tree_util.tree_structure(out_shape)
        return cls(closed, in_tree, out_tree, label=label)

    @property
    def jaxpr(self):
        return self.closed.jaxpr

    def eval_flat(self, flat_args):
        """Evaluate on already-flattened leaf arrays -> flat outputs
        (eager, op by op — the validation path)."""
        import jax
        return jax.core.eval_jaxpr(self.closed.jaxpr, self.closed.consts,
                                   *flat_args)

    def as_callable(self):
        """A function with the capture-time signature replaying this
        program — jit it like the original."""
        import jax
        from jax import tree_util
        closed, in_tree, out_tree = self.closed, self.in_tree, self.out_tree

        def replay(*args):
            flat, tree = tree_util.tree_flatten(tuple(args))
            if tree != in_tree:
                raise MXNetError(
                    f"captured program {self.label or '?'} called with a "
                    f"different argument structure than it was captured "
                    f"at")
            out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *flat)
            return tree_util.tree_unflatten(out_tree, out)

        return replay

    def rewrite(self, plan):
        """Re-trace this program with ``plan`` applied and return the
        rewritten twin (same arg/result trees).

        ``plan``: ``{eqn_index: ("skip",) | ("replace", fn)}`` — skipped
        equations are never bound (their outputs must be unused or
        re-provided), replacements receive a ``read(var)`` accessor and
        return the equation's output values.
        """
        import jax
        in_avals = list(self.closed.in_avals)
        sds = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in in_avals]

        def replayed(*flat):
            return _replay_with_plan(self.closed, plan, flat)

        closed2, _shape = jax.make_jaxpr(replayed, return_shape=True)(*sds)
        return CapturedProgram(closed2, self.in_tree, self.out_tree,
                               label=self.label)

    def cost_estimate(self):
        """``{"flops", "bytes"}`` estimate: FLOPs from the shared jaxpr
        walk (``costs.jaxpr_cost``), bytes from program I/O plus tensors
        crossing :data:`_BARRIER_PRIMS` boundaries."""
        from .. import costs as _costs
        jaxpr = self.closed.jaxpr
        flops, transc = _costs.jaxpr_cost(jaxpr)
        byts = sum(_aval_bytes(v.aval) for v in jaxpr.invars)
        byts += sum(_aval_bytes(v.aval) for v in jaxpr.outvars
                    if hasattr(v, "aval"))
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _BARRIER_PRIMS:
                byts += sum(_aval_bytes(v.aval) for v in eqn.invars
                            if hasattr(v, "aval"))
                byts += sum(_aval_bytes(v.aval) for v in eqn.outvars)
        return {"flops": float(flops + transc), "bytes": float(byts)}

    def eqn_summary(self):
        """Primitive names in order, jit markers resolved — the
        structural assertion handle for tests."""
        out = []
        for eqn in self.closed.jaxpr.eqns:
            name = eqn.primitive.name
            if name == "jit":
                inner = eqn.params.get("name")
                if inner:
                    name = f"jit:{inner}"
            out.append(name)
        return out


def _read_env_factory(env):
    from jax._src.core import Literal

    def read(v):
        if isinstance(v, Literal):
            return v.val
        return env[v]

    return read


def _replay_with_plan(closed, plan, flat_args):
    """Replay a ClosedJaxpr equation by equation under a rewrite plan
    (the canonical ``eval_jaxpr`` loop with skip/replace hooks)."""
    jaxpr = closed.jaxpr
    env = {}
    read = _read_env_factory(env)
    for v, val in zip(jaxpr.constvars, closed.consts):
        env[v] = val
    if len(jaxpr.invars) != len(flat_args):
        raise MXNetError(
            f"replay got {len(flat_args)} args for {len(jaxpr.invars)} "
            "program inputs")
    for v, val in zip(jaxpr.invars, flat_args):
        env[v] = val
    for i, eqn in enumerate(jaxpr.eqns):
        action = plan.get(i)
        if action is not None and action[0] == "skip":
            continue
        if action is not None and action[0] == "replace":
            outs = action[1](read)
        else:
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            invals = [read(v) for v in eqn.invars]
            outs = eqn.primitive.bind(*subfuns, *invals, **bind_params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
        for v, val in zip(eqn.outvars, outs):
            env[v] = val
    return [read(v) for v in jaxpr.outvars]


# ---------------------------------------------------------------------------
# pass base + registry
# ---------------------------------------------------------------------------
class GraphPass:
    """One rewrite over a :class:`CapturedProgram`.

    ``run(prog)`` returns the rewritten program, or None when nothing
    matched (the pipeline records it unchanged and skips validation).
    ``tolerance`` is the validation contract: 0.0 demands bit-exact
    replay on the example inputs; a pass that legitimately changes
    arithmetic (requantization) declares the relative tolerance its
    rewrite is allowed to move outputs by.  ``version`` feeds the
    pipeline fingerprint — bump it when the rewrite's semantics change
    so stale ProgramCache entries cannot be warm-loaded.
    """

    name = "?"
    tolerance = 0.0
    version = 1

    def run(self, prog):
        raise NotImplementedError


_REGISTRY: dict = {}


def register_pass(cls):
    """Register a :class:`GraphPass` subclass under ``cls.name`` (also a
    class decorator).  Last registration wins — tests may shadow."""
    if not getattr(cls, "name", None) or cls.name == "?":
        raise MXNetError(f"pass {cls!r} needs a name")
    _REGISTRY[cls.name] = cls
    return cls


def available_passes():
    return sorted(_REGISTRY)


def resolve_pipeline(spec=None):
    """Build a :class:`PassPipeline` from a comma-separated spec string.

    ``spec=None`` reads ``MXNET_COMPILE_PASSES`` (the process default);
    an empty spec returns None — the no-pipeline fast path, under which
    callers jit the ORIGINAL function (bit-identical by construction).
    Unknown names raise at resolution time, not mid-serving.
    """
    if isinstance(spec, PassPipeline):
        return spec
    if spec is None:
        spec = str(util.getenv("MXNET_COMPILE_PASSES") or "")
    names = [s.strip() for s in str(spec).split(",") if s.strip()]
    if not names:
        return None
    passes = []
    for n in names:
        cls = _REGISTRY.get(n)
        if cls is None:
            raise MXNetError(f"unknown compile pass {n!r} "
                             f"(available: {available_passes()})")
        passes.append(cls())
    return PassPipeline(passes)


def candidate_specs(candidates):
    """Turn ``tools/cost_report.py``'s machine-readable
    ``rewrite_candidates`` rows into resolvable pipeline specs:
    ``{program_key: spec_string}`` — only suggestions naming passes this
    process actually has survive (forward-compatible with reports from
    newer builds)."""
    out = {}
    for c in candidates or ():
        key = c.get("key")
        names = [n for n in (c.get("suggested_passes") or ())
                 if n in _REGISTRY]
        if key and names:
            out[str(key)] = ",".join(names)
    return out


# ---------------------------------------------------------------------------
# pipeline: run + validate + ledger
# ---------------------------------------------------------------------------
def _synth_flat_inputs(prog, example_args=None):
    """Concrete validation inputs for every program input: caller-given
    concrete leaves (e.g. real weights) are used as-is, spec leaves and
    missing args are synthesized deterministically per position."""
    import jax
    from jax import tree_util
    leaves = []
    if example_args is not None:
        leaves = tree_util.tree_flatten(tuple(example_args))[0]
    flat = []
    for i, aval in enumerate(prog.closed.in_avals):
        given = leaves[i] if i < len(leaves) else None
        if given is not None and not isinstance(given,
                                                jax.ShapeDtypeStruct):
            flat.append(onp.asarray(given))
            continue
        rng = onp.random.RandomState(0xC0DE + i)
        dt = onp.dtype(aval.dtype)
        if dt.kind == "f" or dt.kind == "V":    # floats incl. bfloat16
            a = rng.standard_normal(aval.shape).astype("float32")
            flat.append(a.astype(dt) if dt.kind == "f"
                        else onp.asarray(a, dtype=aval.dtype))
        elif dt.kind in "iu":
            flat.append(rng.randint(0, 4, size=aval.shape).astype(dt))
        elif dt.kind == "b":
            flat.append(onp.zeros(aval.shape, dtype=dt))
        else:
            flat.append(onp.zeros(aval.shape, dtype=dt))
    return flat


def _outputs_match(ref, new, tolerance):
    """The referee: dtype/shape must match exactly; values bit-exact at
    tolerance 0, else within the declared relative band."""
    if len(ref) != len(new):
        return False, "output arity changed"
    for i, (r, n) in enumerate(zip(ref, new)):
        r = onp.asarray(r)
        n = onp.asarray(n)
        if r.shape != n.shape or r.dtype != n.dtype:
            return False, (f"output {i}: {r.shape}/{r.dtype} -> "
                           f"{n.shape}/{n.dtype}")
        rf = r.astype("float32") if r.dtype.kind in "fV" else r
        nf = n.astype("float32") if n.dtype.kind in "fV" else n
        if tolerance == 0.0:
            if not onp.array_equal(onp.asarray(rf), onp.asarray(nf)):
                return False, f"output {i}: not bit-identical"
        else:
            rf = onp.asarray(rf, dtype="float64")
            nf = onp.asarray(nf, dtype="float64")
            denom = max(float(onp.max(onp.abs(rf))) if rf.size else 0.0,
                        1.0)
            err = float(onp.max(onp.abs(rf - nf))) / denom if rf.size \
                else 0.0
            if not onp.isfinite(err) or err > tolerance:
                return False, (f"output {i}: max rel err {err:.3e} > "
                               f"tolerance {tolerance:g}")
    return True, ""


class PassPipeline:
    """An ordered list of :class:`GraphPass` instances with the
    validation + ledger + fingerprint contract."""

    def __init__(self, passes):
        self.passes = list(passes)
        if not self.passes:
            raise MXNetError("empty PassPipeline — use no pipeline at all "
                             "(resolve_pipeline returns None) so the "
                             "unrewritten program is served bit-identical")
        self.spec = ",".join(p.name for p in self.passes)

    def __repr__(self):
        return f"PassPipeline({self.spec!r})"

    def has_pass(self, name):
        return any(p.name == name for p in self.passes)

    def fingerprint(self):
        """Stable hash over pass names x versions — callers fold it into
        the ProgramCache key (``aot_compile_lowered(extra_key=...)``) so
        rewritten and unrewritten twins can never collide, including
        across ``MXNET_COMPILE_PASSES`` changes and pickled
        ``ReplicaSpec`` warm starts."""
        h = hashlib.sha256()
        for p in self.passes:
            h.update(f"{p.name}@{p.version};".encode())
        return "passes:" + h.hexdigest()[:16]

    def run(self, prog, example_args=None, label="", validate=True):
        """Run every pass over ``prog``; returns ``(program, reports)``.

        Each pass's output is validated against ITS input program on
        deterministic example inputs (concrete ``example_args`` leaves —
        real weights — are used where given); a mismatch beyond the
        pass's declared tolerance discards that rewrite and keeps going
        with the unrewritten program.  Per-pass before->after
        bytes/FLOPs land in the ``mxnet_tpu.costs`` pass ledger.
        """
        from .. import costs as _costs
        _bump("runs")
        reports = []
        cur = prog
        flat_inputs = None
        for p in self.passes:
            t0 = time.perf_counter()
            rep = {"pass": p.name, "label": label, "changed": False,
                   "validated": None, "tolerance": p.tolerance}
            try:
                out = p.run(cur)
            except Exception as e:      # noqa: BLE001 — rewrite discarded
                _bump("errors")
                rep.update(error=repr(e))
                _LOG.warning("pass %s raised on %s — rewrite discarded: "
                             "%r", p.name, label or "?", e)
                reports.append(rep)
                continue
            if out is None:
                _bump("unchanged")
                reports.append(rep)
                continue
            rep["changed"] = True
            if validate:
                if flat_inputs is None:
                    flat_inputs = _synth_flat_inputs(prog, example_args)
                ok, why = True, ""
                try:
                    ref = cur.eval_flat(flat_inputs)
                    new = out.eval_flat(flat_inputs)
                    ok, why = _outputs_match(ref, new, p.tolerance)
                except Exception as e:  # noqa: BLE001 — treat as mismatch
                    ok, why = False, repr(e)
                rep["validated"] = ok
                if not ok:
                    _bump("validation_failures")
                    rep["why"] = why
                    _LOG.warning(
                        "pass %s failed validation on %s (%s) — rewrite "
                        "discarded", p.name, label or "?", why)
                    reports.append(rep)
                    continue
            before = cur.cost_estimate()
            after = out.cost_estimate()
            seconds = time.perf_counter() - t0
            rep.update(flops_before=before["flops"],
                       flops_after=after["flops"],
                       bytes_before=before["bytes"],
                       bytes_after=after["bytes"],
                       seconds=round(seconds, 4))
            _bump("rewrites")
            _bump("bytes_saved",
                  max(0, int(before["bytes"] - after["bytes"])))
            try:
                _costs.record_pass(
                    p.name, label=label,
                    flops_before=before["flops"],
                    flops_after=after["flops"],
                    bytes_before=before["bytes"],
                    bytes_after=after["bytes"],
                    seconds=seconds, validated=rep["validated"],
                    tolerance=p.tolerance)
            except Exception:           # noqa: BLE001 — ledger best-effort
                pass
            reports.append(rep)
            cur = out
        return cur, reports


# ---------------------------------------------------------------------------
# built-in pass: dead-code elimination
# ---------------------------------------------------------------------------
@register_pass
class DCEPass(GraphPass):
    """Drop equations whose outputs feed nothing (backward liveness from
    the program outputs; effectful equations are kept).  Exact: the
    referee demands bit-identical replay."""

    name = "dce"
    tolerance = 0.0
    version = 1

    def run(self, prog):
        jaxpr = prog.closed.jaxpr
        from jax._src.core import Literal
        live = {v for v in jaxpr.outvars if not isinstance(v, Literal)}
        keep = [False] * len(jaxpr.eqns)
        for i in range(len(jaxpr.eqns) - 1, -1, -1):
            eqn = jaxpr.eqns[i]
            if getattr(eqn, "effects", None) or \
                    any(v in live for v in eqn.outvars):
                keep[i] = True
                for v in eqn.invars:
                    if not isinstance(v, Literal):
                        live.add(v)
        if all(keep):
            return None
        plan = {i: ("skip",) for i, k in enumerate(keep) if not k}
        return prog.rewrite(plan)


# ---------------------------------------------------------------------------
# built-in pass: int8 residency
# ---------------------------------------------------------------------------
def _marker_name(eqn):
    if eqn.primitive.name == "jit":
        return eqn.params.get("name")
    return None


def _is_relu(eqn):
    """jax.nn.relu stages as custom_jvp_call whose call_jaxpr is a jit
    named 'relu'."""
    if eqn.primitive.name != "custom_jvp_call" or len(eqn.invars) != 1:
        return False
    inner = eqn.params.get("call_jaxpr")
    if inner is None:
        return False
    inner = getattr(inner, "jaxpr", inner)
    for e in inner.eqns:
        nm = e.primitive.name
        if nm == "jit" and e.params.get("name") == "relu":
            return True
    return False


@register_pass
class Int8ResidencyPass(GraphPass):
    """Keep layer-to-layer activations int8.

    The PTQ layers (contrib/quantization.py) stage their scale handling
    as named ``jit`` markers, so a two-quantized-layer program contains
    the bridge::

        ... dot_general(int8) -> jit:_mx_dequantize_act -> [glue]
            -> jit:_mx_quantize_act -> dot_general(int8) ...

    where the glue (bias add, relu, reshapes, bf16 round-trips) runs in
    float and costs an HBM round-trip per layer boundary.  This pass
    folds each single-consumer dequantize->glue->quantize chain into one
    requantize epilogue computed in the OUTPUT scale's domain — the
    invariant is ``t = value / s_out``::

        t = acc.astype(f32) * (s_in / s_out)       # dequant + requant
        add b      -> t += b / s_out               # linear glue rescaled
        mul/div m  -> t *= m  /  t /= m            # scale-invariant
        relu       -> max(t, 0)                    # commutes (s_out > 0)
        max/min c  -> max/min(t, c / s_out)
        reshape / transpose / squeeze / broadcast  -> replayed on t
        f->f convert (bf16 round-trip)             -> dropped (stay f32)
        quantize   -> clip(round(t), -127, 127).astype(int8)

    Bridges whose value escapes to a program output (or fans out) are
    left alone — dequantization survives only at graph outputs.  Not
    bit-exact (the bf16 round-trip is deliberately removed), so the
    declared tolerance admits rounding-level drift and the referee
    rejects anything larger.
    """

    name = "int8_residency"
    tolerance = 5e-2
    version = 1

    # glue classification result: (kind, payload)
    _BINARY = {"add": "add", "sub": "sub", "mul": "mul", "div": "div",
               "max": "max", "min": "min"}
    _SHAPE = frozenset(("reshape", "transpose", "squeeze",
                        "broadcast_in_dim", "expand_dims"))

    def run(self, prog):
        jaxpr = prog.closed.jaxpr
        from jax._src.core import Literal
        uses: dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    uses.setdefault(v, []).append(i)
        outvars = {v for v in jaxpr.outvars if not isinstance(v, Literal)}

        plan: dict = {}
        folded = 0
        for d_idx, d_eqn in enumerate(jaxpr.eqns):
            if _marker_name(d_eqn) != DEQUANTIZE_MARKER:
                continue
            chain = self._walk_bridge(jaxpr, uses, outvars, d_idx)
            if chain is None:
                continue
            glue_idxs, glue_steps, q_idx = chain
            q_eqn = jaxpr.eqns[q_idx]
            plan[d_idx] = ("skip",)
            for gi in glue_idxs:
                plan[gi] = ("skip",)
            plan[q_idx] = ("replace",
                           self._make_fold(d_eqn, glue_steps, q_eqn))
            folded += 1
        if not folded:
            return None
        return prog.rewrite(plan)

    # -- bridge discovery ---------------------------------------------------
    def _walk_bridge(self, jaxpr, uses, outvars, d_idx):
        """Follow the dequantize output through single-consumer glue to a
        quantize marker.  Returns ``(glue_idxs, glue_steps, q_idx)`` or
        None when the bridge is unfoldable (fan-out, escape to a program
        output, unsupported glue)."""
        d_eqn = jaxpr.eqns[d_idx]
        cur = d_eqn.outvars[0]
        glue_idxs, glue_steps = [], []
        for _ in range(64):             # defensive bound
            if cur in outvars:
                return None             # value escapes: keep the dequant
            consumers = uses.get(cur, [])
            if len(consumers) != 1:
                return None
            ci = consumers[0]
            eqn = jaxpr.eqns[ci]
            if len(eqn.outvars) != 1:
                return None
            name = _marker_name(eqn)
            if name == QUANTIZE_MARKER:
                if eqn.invars[0] is not cur:
                    return None         # chain feeds the SCALE slot: bail
                return glue_idxs, glue_steps, ci
            step = self._classify_glue(eqn, cur)
            if step is None:
                return None
            glue_idxs.append(ci)
            glue_steps.append(step)
            cur = eqn.outvars[0]
        return None

    def _classify_glue(self, eqn, cur):
        prim = eqn.primitive.name
        if _is_relu(eqn):
            return ("relu", None, None)
        if prim in self._BINARY and len(eqn.invars) == 2:
            pos = 0 if eqn.invars[0] is cur else 1
            other = eqn.invars[1 - pos]
            if other is cur:
                return None             # x op x: not independent
            if prim == "div" and pos == 1:
                return None             # other / chain: not linear in t
            return (self._BINARY[prim], other, pos)
        if prim in self._SHAPE:
            if any(v is cur for v in eqn.invars[1:]):
                return None
            return ("prim", eqn.primitive, dict(eqn.params))
        if prim == "convert_element_type":
            new = onp.dtype(eqn.params.get("new_dtype", "float32"))
            if new.kind in "fV":        # float->float round-trip: drop
                return ("noop", None, None)
            return None
        return None

    # -- fold emission ------------------------------------------------------
    @staticmethod
    def _make_fold(d_eqn, glue_steps, q_eqn):
        def fold(read):
            import jax.numpy as jnp
            acc = read(d_eqn.invars[0])
            s_in = read(d_eqn.invars[1])
            s_out = read(q_eqn.invars[1])
            t = acc.astype(jnp.float32) * (
                jnp.asarray(s_in, jnp.float32) / s_out)
            for step in glue_steps:
                kind = step[0]
                if kind == "relu":
                    t = jnp.maximum(t, jnp.float32(0))
                elif kind == "noop":
                    pass
                elif kind == "prim":
                    _k, primitive, params = step
                    subfuns, bind_params = primitive.get_bind_params(params)
                    t = primitive.bind(*subfuns, t, **bind_params)
                elif kind in ("add", "sub", "max", "min"):
                    _k, other, pos = step
                    o = jnp.asarray(read(other), jnp.float32) / s_out
                    if kind == "add":
                        t = t + o
                    elif kind == "sub":
                        t = t - o if pos == 0 else o - t
                    elif kind == "max":
                        t = jnp.maximum(t, o)
                    else:
                        t = jnp.minimum(t, o)
                else:                   # mul / div by an independent value
                    _k, other, pos = step
                    o = jnp.asarray(read(other), jnp.float32)
                    t = t * o if kind == "mul" else t / o
            q = jnp.clip(jnp.round(t), -127, 127).astype(jnp.int8)
            return [q]

        return fold
