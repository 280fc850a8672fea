"""INT8 post-training quantization for inference.

Reference: ``src/operator/quantization/`` (quantize/dequantize ops, minmax and
KL-entropy calibration) and ``python/mxnet/contrib/quantization.py``
(``quantize_net``).  TPU-native design: the MXU multiplies int8 natively
(``lax.dot_general(..., preferred_element_type=int32)`` — v5e runs int8 at 2x
bf16 throughput), so quantized Dense/Convolution layers carry symmetric
per-output-channel int8 weights plus a calibrated per-tensor input scale, and
the whole dequantize epilogue fuses into the matmul under jit.  There is no
cuDNN-style quantized-op registry: the quantized layers are ordinary
HybridBlocks swapped into the Gluon tree, so ``hybridize()``/``export`` work
unchanged.

Modes (reference parity):
- ``calib_mode='naive'``  — per-layer input absmax over the calibration set.
- ``calib_mode='entropy'`` — KL-divergence-optimal clipping threshold from a
  histogram of calibration activations (reference ``_get_optimal_threshold``).
- ``quantized_dtype``: 'int8' or 'auto' (alias).  'uint8' is mapped to int8
  with a warning — the MXU path is symmetric-signed.
"""
from __future__ import annotations

import logging
import re as _re

import numpy as onp

from ..base import MXNetError
from ..gluon.block import Block, HybridBlock
from ..gluon import nn as _nn
from ..gluon.parameter import Parameter
from ..ndarray.ndarray import NDArray, apply_op, unwrap

__all__ = ["quantize_net", "calib_thresholds", "QuantizedDense",
           "QuantizedConv", "optimal_threshold_kl"]

_LOG = logging.getLogger("mxnet_tpu.quantization")


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------
def optimal_threshold_kl(hist, hist_edges, num_quantized_bins=255):
    """KL-divergence-optimal |x| clipping threshold from an abs-value
    histogram (reference ``_get_optimal_threshold`` in
    python/mxnet/contrib/quantization.py, itself from TensorRT's entropy
    calibration)."""
    num_bins = len(hist)
    assert num_bins >= num_quantized_bins
    best_div, best_t = None, float(hist_edges[-1])
    hist = hist.astype("float64")

    def smooth(d, eps=1e-4):
        """Blend in eps uniform mass so every bin is positive (the additive
        scheme in reference _smooth_distribution can go negative on sparse
        histograms)."""
        return (1.0 - eps) * d + eps / d.size

    for i in range(num_quantized_bins, num_bins + 1):
        ref = hist[:i].copy()
        ref[-1] += hist[i:].sum()              # clip outlier mass in
        # quantize the i bins down to num_quantized_bins
        idx = (onp.arange(i) * num_quantized_bins // i)
        q = onp.zeros(num_quantized_bins)
        onp.add.at(q, idx, hist[:i])
        # expand q back to i bins, spreading uniformly over nonzero support
        counts = onp.zeros(num_quantized_bins)
        onp.add.at(counts, idx, (hist[:i] > 0).astype("float64"))
        qe = onp.where(counts[idx] > 0, q[idx] / onp.maximum(counts[idx], 1),
                       0.0)
        qe = onp.where(hist[:i] > 0, qe, 0.0)
        if ref.sum() <= 0 or qe.sum() <= 0:
            continue
        pn = smooth(ref / ref.sum())
        qn = smooth(qe / qe.sum())
        mask = pn > 0
        div = float((pn[mask] * onp.log(pn[mask] / qn[mask])).sum())
        # <= : on ties (sparse calibration histograms) prefer the larger,
        # safer threshold
        if best_div is None or div <= best_div:
            best_div = div
            best_t = float(hist_edges[i])
    return best_t


class _Observer(HybridBlock):
    """Transparent wrapper that records input activation statistics during
    eager calibration forwards."""

    NUM_BINS = 2048

    def __init__(self, inner, mode):
        super().__init__()
        self.inner = inner
        self._mode = mode
        self.absmax = 0.0
        self._hist = None
        self._edges = None

    def __call__(self, x, *args):
        raw = onp.abs(unwrap(x.wait_to_read()).__array__()
                      if isinstance(x, NDArray) else onp.asarray(x))
        amax = float(raw.max()) if raw.size else 0.0
        self.absmax = max(self.absmax, amax)
        if self._mode == "entropy":
            if self._hist is None:
                self._edges = onp.linspace(0, max(amax, 1e-8), self.NUM_BINS + 1)
                self._hist = onp.histogram(raw, bins=self._edges)[0].astype("float64")
            else:
                if amax > self._edges[-1]:      # re-bin to the wider range
                    old_centers = (self._edges[:-1] + self._edges[1:]) / 2
                    self._edges = onp.linspace(0, amax, self.NUM_BINS + 1)
                    newh = onp.histogram(old_centers, bins=self._edges,
                                         weights=self._hist)[0]
                    self._hist = newh
                self._hist += onp.histogram(raw, bins=self._edges)[0]
        return self.inner(x, *args)

    # below ~4 samples per quantized bin the KL estimate is noise and tends
    # to pick destructively small thresholds; fall back to absmax
    MIN_KL_SAMPLES = 4 * 255

    def threshold(self):
        if self._mode == "entropy" and self._hist is not None and \
                self._hist.sum() >= self.MIN_KL_SAMPLES:
            return optimal_threshold_kl(self._hist, self._edges)
        return self.absmax


# ---------------------------------------------------------------------------
# quantized layers
# ---------------------------------------------------------------------------
_MARKERS = None


def _marker_fns():
    """The jit'd quantize/dequantize helpers shared by every quantized
    layer.  Calling a module-level ``jax.jit`` function inside an outer
    trace stages ONE named ``jit`` equation per call, so the captured
    program carries ``jit:_mx_quantize_act`` / ``jit:_mx_dequantize_act``
    markers the ``int8_residency`` compile pass
    (``mxnet_tpu.compile.passes``) pattern-matches to fold layer-to-layer
    dequantize->glue->quantize bridges into int8-resident requantizes.
    The numerics are EXACTLY the former inline epilogue: symmetric
    clip-round quantize, fp32 multiply dequantize.  Built lazily so
    importing this module never imports jax."""
    global _MARKERS
    if _MARKERS is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _mx_quantize_act(x, scale):
            return jnp.clip(jnp.round(x.astype("float32") / scale),
                            -127, 127).astype(jnp.int8)

        @jax.jit
        def _mx_dequantize_act(acc, scale):
            return acc.astype("float32") * scale

        _MARKERS = (_mx_quantize_act, _mx_dequantize_act)
    return _MARKERS


def _quantize_weight(w, channel_axis):
    """Symmetric per-output-channel int8 quantization of a weight array."""
    red = tuple(i for i in range(w.ndim) if i != channel_axis)
    scale = onp.abs(w).max(axis=red) / 127.0
    scale = onp.maximum(scale, 1e-12).astype("float32")
    bshape = tuple(-1 if i == channel_axis else 1 for i in range(w.ndim))
    wq = onp.clip(onp.round(w / scale.reshape(bshape)), -127, 127) \
        .astype("int8")
    return wq, scale


class _QuantizedBase(HybridBlock):
    def __init__(self, input_scale, act=None):
        super().__init__()
        self._input_scale = float(input_scale)
        self._act = act

    def _quantize_input(self, jnp, x):
        s = jnp.asarray(self._input_scale, "float32")
        quantize, _dequantize = _marker_fns()
        return quantize(x, s), s

    def _init_quantized_params(self, weight, bias, channel_axis):
        """Freeze the fp weight into int8 qweight + per-channel scale (and a
        fp32 bias copy) as grad_req='null' Parameters."""
        w = weight.data().astype("float32").asnumpy()
        wq, wscale = _quantize_weight(w, channel_axis)
        self.qweight = Parameter("qweight", shape=wq.shape, dtype="int8",
                                 grad_req="null")
        self.qweight.set_data(NDArray(wq))
        self.wscale = Parameter("wscale", shape=wscale.shape, dtype="float32",
                                grad_req="null")
        self.wscale.set_data(NDArray(wscale))
        if bias is not None:
            b = bias.data().astype("float32").asnumpy()
            self.bias = Parameter("bias", shape=b.shape, dtype="float32",
                                  grad_req="null")
            self.bias.set_data(NDArray(b))
        else:
            self.bias = None


class QuantizedDense(_QuantizedBase):
    """int8 x @ int8 W^T on the MXU, fp32 dequantize epilogue.

    Reference: quantized_fully_connected (src/operator/quantization/)."""

    def __init__(self, dense, input_scale):
        super().__init__(input_scale, dense._act)
        self._units = dense._units
        self._flatten = dense._flatten
        self._init_quantized_params(dense.weight, dense.bias, channel_axis=0)

    def hybrid_forward(self, F, x, qweight, wscale, bias=None):
        import jax.numpy as jnp
        from jax import lax

        def f(x, wq, ws, *b):
            xq, s = self._quantize_input(jnp, x)
            if self._flatten:
                xq = xq.reshape((xq.shape[0], -1))
            y = lax.dot_general(xq, wq, (((xq.ndim - 1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
            _quantize, dequantize = _marker_fns()
            y = dequantize(y, s * ws)
            if b:
                y = y + b[0]
            # dequantize into the activation dtype: a bf16-fed net keeps
            # bf16 inter-layer traffic (fp32 epilogues doubled the
            # HBM-bound serving path's bytes and lost to plain bf16)
            return y.astype(x.dtype)

        args = (x, qweight, wscale) + ((bias,) if bias is not None else ())
        out = apply_op(f, *args, op_name="QuantizedDense")
        if self._act:
            from .. import ndarray as FF
            out = FF.Activation(out, act_type=self._act)
        return out


class QuantizedConv(_QuantizedBase):
    """int8 convolution on the MXU, fp32 dequantize epilogue.

    Reference: quantized_conv (src/operator/quantization/quantized_conv.cu)."""

    def __init__(self, conv, input_scale):
        super().__init__(input_scale, conv._act)
        self._kwargs = dict(conv._kwargs)
        self._init_quantized_params(conv.weight, conv.bias, channel_axis=0)

    def hybrid_forward(self, F, x, qweight, wscale, bias=None):
        import jax.numpy as jnp
        from jax import lax
        kw = self._kwargs
        nsp = len(kw["kernel"])
        layout = kw["layout"] or "NC" + "DHW"[3 - nsp:]
        if not layout.startswith("NC"):
            raise MXNetError("QuantizedConv supports NC* layouts only")
        l = "NC" + "DHW"[3 - nsp:]
        dn = (l, "OI" + "DHW"[3 - nsp:], l)
        ch_axis = 1

        def f(x, wq, ws, *b):
            xq, s = self._quantize_input(jnp, x)
            y = lax.conv_general_dilated(
                xq, wq, window_strides=tuple(kw["stride"]),
                padding=[(p, p) for p in kw["pad"]],
                rhs_dilation=tuple(kw["dilate"]), dimension_numbers=dn,
                feature_group_count=kw["num_group"],
                preferred_element_type=jnp.int32)
            bshape = tuple(-1 if i == ch_axis else 1 for i in range(y.ndim))
            _quantize, dequantize = _marker_fns()
            y = dequantize(y, s * ws.reshape(bshape))
            if b:
                y = y + b[0].reshape(bshape)
            return y.astype(x.dtype)

        args = (x, qweight, wscale) + ((bias,) if bias is not None else ())
        out = apply_op(f, *args, op_name="QuantizedConv")
        if self._act:
            from .. import ndarray as FF
            out = FF.Activation(out, act_type=self._act)
        return out


# ---------------------------------------------------------------------------
# net transformation
# ---------------------------------------------------------------------------
_QUANTIZABLE = None


def _quantizable_types():
    global _QUANTIZABLE
    if _QUANTIZABLE is None:
        from ..gluon.nn.conv_layers import _Conv
        _QUANTIZABLE = (_nn.Dense, _Conv)
    return _QUANTIZABLE


def _all_blocks(block):
    yield block
    for child in block._children.values():
        yield from _all_blocks(child)


def _walk(block, prefix=""):
    """Yield (parent, child_key, attr_name_or_None, child, path)."""
    for key, child in list(block._children.items()):
        attr = None
        for aname, aval in block.__dict__.items():
            if aval is child:
                attr = aname
                break
        path = f"{prefix}.{key}" if prefix else key
        yield block, key, attr, child, path
        yield from _walk(child, path)


def _replace(parent, key, attr, new):
    parent._children[key] = new
    if attr is not None:
        object.__setattr__(parent, attr, new)


def _clear_jit_caches(net):
    """Drop every HybridBlock's compiled-program cache: cached fns close over
    the pre-swap parameter list and would misbind after a layer replacement."""
    for blk in _all_blocks(net):
        if isinstance(blk, HybridBlock):
            blk._cached_fns = {}


def _excluded(path, child, exclude_layers, exclude_layers_match):
    if exclude_layers and path in exclude_layers:
        return True
    if exclude_layers_match:
        for pat in exclude_layers_match:
            if _re.search(pat, path):
                return True
    return False


def calib_thresholds(net, calib_data, calib_mode="naive", num_calib_batches=None,
                     exclude_layers=None, exclude_layers_match=None):
    """Run calibration forwards and return {layer_path: threshold}."""
    targets = []
    for parent, key, attr, child, path in _walk(net):
        if isinstance(child, _quantizable_types()) and \
                not _excluded(path, child, exclude_layers,
                              exclude_layers_match):
            obs = _Observer(child, calib_mode)
            _replace(parent, key, attr, obs)
            targets.append((parent, key, attr, obs, path))
    # calibration must run eagerly: observers read concrete activations, so
    # temporarily de-hybridize (restored below)
    actives = []
    for blk in _all_blocks(net):
        if isinstance(blk, HybridBlock) and getattr(blk, "_active", False):
            actives.append(blk)
            blk._active = False
    try:
        from .. import autograd
        n = 0
        for batch in calib_data:
            x = batch[0] if isinstance(batch, (tuple, list)) else batch
            with autograd._Scope(recording=False, training=False):
                net(x if isinstance(x, NDArray) else NDArray(unwrap(x)))
            n += 1
            if num_calib_batches is not None and n >= num_calib_batches:
                break
        if n == 0:
            raise MXNetError("calib_data yielded no batches")
        return {path: obs.threshold()
                for _, _, _, obs, path in targets}
    finally:
        for parent, key, attr, obs, _ in targets:
            _replace(parent, key, attr, obs.inner)
        for blk in actives:
            blk._active = True
        _clear_jit_caches(net)


def quantize_net(net, calib_data=None, calib_mode="naive",
                 quantized_dtype="int8", num_calib_batches=None,
                 exclude_layers=None, exclude_layers_match=None,
                 thresholds=None):
    """Post-training-quantize a Gluon net's Dense/Convolution layers to int8.

    Reference API: ``mx.contrib.quantization.quantize_net``.  Mutates and
    returns ``net``; the swapped-in quantized layers are HybridBlocks, so the
    result hybridizes/exports normally.  Inference only (weights frozen).
    """
    if quantized_dtype not in ("int8", "auto", "uint8"):
        raise MXNetError(f"unsupported quantized_dtype {quantized_dtype!r}")
    if quantized_dtype == "uint8":
        _LOG.warning("uint8 requested; the TPU MXU path is symmetric signed "
                     "int8 — using int8")
    if calib_mode not in ("naive", "entropy", "none"):
        raise MXNetError(f"unknown calib_mode {calib_mode!r}")
    if thresholds is None:
        if calib_mode == "none" or calib_data is None:
            raise MXNetError(
                "quantize_net needs calib_data (calib_mode naive/entropy) "
                "or explicit thresholds")
        thresholds = calib_thresholds(
            net, calib_data, calib_mode, num_calib_batches,
            exclude_layers, exclude_layers_match)

    from ..gluon.nn.conv_layers import _Conv
    n_replaced = 0
    for parent, key, attr, child, path in _walk(net):
        if path not in thresholds:
            continue
        t = max(float(thresholds[path]), 1e-12)
        scale = t / 127.0
        if isinstance(child, _nn.Dense):
            q = QuantizedDense(child, scale)
        elif isinstance(child, _Conv) and \
                child._op_name == "Convolution":
            layout = child._kwargs.get("layout")
            if layout is not None and not layout.startswith("NC"):
                _LOG.warning("skipping %s: QuantizedConv supports NC* "
                             "layouts only (got %s)", path, layout)
                continue
            q = QuantizedConv(child, scale)
        else:
            continue
        _replace(parent, key, attr, q)
        n_replaced += 1
    _clear_jit_caches(net)
    _LOG.info("quantized %d layers", n_replaced)
    return net
