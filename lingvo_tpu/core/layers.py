"""Core NN layers: projections, convs, embeddings, softmax, norms, dropout.

TPU-native re-design of the reference's `lingvo/core/layers.py` (7.3k LoC) and
`bn_layers.py`. Same capability surface — ProjectionLayer/FCLayer (`layers.py:845,1586`),
FeedForwardNet (`:1597`), Conv2D family (`:182-844`), embeddings (`:2679,3018`),
positional embeddings incl. rotary (`:3143-3558`), SimpleFullSoftmax (`:3697`),
deterministic dropout (`:4842-4926`), LayerNorm (`:4927`), BatchNorm
(`bn_layers.py:114`) — but computation is pure jnp/lax, weights are theta
pytrees, and sharding is expressed as mesh-axis names on WeightParams.

Matmul-heavy ops keep bf16-friendly shapes and rely on XLA fusion; no
hand-scheduling.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from lingvo_tpu.core import activations
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import py_utils
from lingvo_tpu.core import quant_utils
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.core.py_utils import WeightInit, WeightParams


class IdentityLayer(base_layer.BaseLayer):

  def FProp(self, theta, x, *args):
    return x


# ---------------------------------------------------------------------------
# Projections / feed-forward.
# ---------------------------------------------------------------------------


class ProjectionLayer(base_layer.BaseLayer):
  """y = act(norm(x @ w + b)). Ref: layers.ProjectionLayer (`layers.py:845`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Input depth.")
    p.Define("output_dim", 0, "Output depth.")
    p.Define("activation", "NONE", "Activation name.")
    p.Define("has_bias", True, "Whether to add a bias.")
    p.Define("bias_init", 0.0, "Constant bias initialization.")
    p.Define("batch_norm", False, "Apply BatchNorm before activation.")
    p.Define("ln_tpl", None, "Optional LayerNorm params applied pre-activation.")
    p.Define("weight_norm", False, "Reparameterize w = g * v/||v||.")
    p.Define("qdomain", None,
             "Optional quant_utils.QDomain params: fake-quantize the weight "
             "and the output activation (ref QuantizableLayer wiring).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.output_dim > 0, p.name
    wsdm = p.weight_split_dims_mapping
    self.CreateVariable(
        "w",
        WeightParams(
            shape=(p.input_dim, p.output_dim),
            init=p.params_init,
            dtype=p.dtype,
            tensor_split_dims_mapping=wsdm))
    if p.weight_norm:
      self.CreateVariable(
          "g", WeightParams((p.output_dim,), WeightInit.Constant(0.0), p.dtype))
    if p.has_bias:
      bias_sharding = (wsdm[-1],) if wsdm else None
      self.CreateVariable(
          "b",
          WeightParams((p.output_dim,), WeightInit.Constant(p.bias_init),
                       p.dtype, tensor_split_dims_mapping=bias_sharding))
    if p.batch_norm:
      self.CreateChild("bn", BatchNormLayer.Params().Set(dim=p.output_dim))
    if p.qdomain is not None:
      self.CreateChild("qdomain", p.qdomain.Copy())

  def FProp(self, theta, inputs, paddings=None):
    p = self.p
    th = self.CastTheta(theta)
    x = self.ToFPropDtype(inputs)
    w = th.w
    if isinstance(w, quant_utils.Int8Weight):
      # int8-serving theta: the matmul runs in int8 on the MXU. Weight-norm
      # and fake-quant domains rewrite the float weight and cannot compose
      # with the frozen integer grid.
      assert not p.weight_norm and p.qdomain is None
      out = w.Einsum(x)
    else:
      if p.weight_norm:
        w = jnp.reshape((1.0 + th.g) / jnp.linalg.norm(w, axis=0),
                        (1, -1)) * w
      if p.qdomain is not None:
        # quantize the EFFECTIVE matmul weight (post weight-norm) — QAT must
        # simulate the weight the int8 deployment actually uses
        w = self.qdomain.QuantizeWeight(self.ChildTheta(theta, "qdomain"), w)
      out = jnp.einsum("...i,io->...o", x, w)
    if p.has_bias:
      out = out + th.b
    if p.batch_norm:
      out = self.bn.FProp(theta.bn, out, paddings)
    if p.activation != "NONE":
      out = activations.GetFn(p.activation)(out)
    if p.qdomain is not None:
      out = self.qdomain.QuantizeAct(
          self.ChildTheta(theta, "qdomain"), "act", out)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out


class FCLayer(ProjectionLayer):
  """Fully-connected layer with RELU default (`layers.py:1586`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.activation = "RELU"
    return p


class FeedForwardNet(base_layer.BaseLayer):
  """MLP over hidden_layer_dims with dropout (`layers.py:1597`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Input depth.")
    p.Define("hidden_layer_dims", [], "Output dim of each layer.")
    p.Define("activation", "RELU", "Single name or list per layer.")
    p.Define("dropout_prob", 0.0, "Single prob or list per layer.")
    p.Define("has_bias", True, "Bias in each projection.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    dims = [p.input_dim] + list(p.hidden_layer_dims)
    num = len(p.hidden_layer_dims)
    acts = p.activation if isinstance(p.activation, (list, tuple)) else [
        p.activation
    ] * num
    drops = p.dropout_prob if isinstance(p.dropout_prob, (list, tuple)) else [
        p.dropout_prob
    ] * num
    self._dropout_probs = list(drops)
    projs = []
    for i in range(num):
      projs.append(ProjectionLayer.Params().Set(
          input_dim=dims[i], output_dim=dims[i + 1], activation=acts[i],
          has_bias=p.has_bias))
    self.CreateChildren("fc", projs)
    self.CreateChild("dropout", DeterministicDropoutLayer.Params())

  def FProp(self, theta, inputs, paddings=None):
    x = inputs
    for i, layer in enumerate(self.fc):
      x = layer.FProp(theta.fc[i], x, paddings)
      if self._dropout_probs[i] > 0.0:
        x = self.dropout.FProp(
            self.ChildTheta(theta, "dropout"), x,
            keep_prob=1.0 - self._dropout_probs[i], name_suffix=f"l{i}")
    return x


# ---------------------------------------------------------------------------
# Dropout.
# ---------------------------------------------------------------------------


class DeterministicDropoutLayer(base_layer.BaseLayer):
  """Dropout seeded from the step-seed context (`layers.py:4916`).

  Identity when eval-mode or no step seed is active, so eval FProps need no
  key plumbing.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("keep_prob", 1.0, "Keep probability (may be overridden per call).")
    p.Define("noise_shape_broadcast_dims", None,
             "Dims over which the dropout mask broadcasts (memory saving).")
    return p

  def _NameIsRequired(self):
    return False

  def FProp(self, theta, inputs, keep_prob=None, name_suffix="",
            extra_seed=None):
    p = self.p
    kp = p.keep_prob if keep_prob is None else keep_prob
    if kp >= 1.0 or py_utils.DoEval() or not py_utils.HasStepSeed():
      return inputs
    key = py_utils.StepSeed(f"{self.path}/{name_suffix}", extra_seed)
    shape = list(inputs.shape)
    if p.noise_shape_broadcast_dims:
      for d in p.noise_shape_broadcast_dims:
        shape[d] = 1
    mask = jax.random.bernoulli(key, kp, shape)
    return jnp.where(mask, inputs / jnp.asarray(kp, inputs.dtype),
                     jnp.zeros((), inputs.dtype))


DropoutLayer = DeterministicDropoutLayer


# ---------------------------------------------------------------------------
# Normalization.
# ---------------------------------------------------------------------------


class LayerNorm(base_layer.BaseLayer):
  """Layer normalization over the trailing dim (`layers.py:4927`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Depth of the input.")
    p.Define("epsilon", 1e-6, "Variance floor.")
    p.Define("use_fused_layernorm", False, "Hint only; XLA fuses anyway.")
    p.Define("direct_scale", False,
             "If True scale is applied as-is; else (1+scale).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0, p.name
    self.CreateVariable(
        "scale", WeightParams((p.input_dim,), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable(
        "bias", WeightParams((p.input_dim,), WeightInit.Constant(0.0), p.dtype))

  def FProp(self, theta, inputs):
    p = self.p
    th = self.CastTheta(theta)
    x = self.ToFPropDtype(inputs)
    # Always compute moments in f32 for stability under bf16 activations.
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = (x32 - mean) * jax.lax.rsqrt(var + p.epsilon)
    normed = normed.astype(x.dtype)
    scale = th.scale if p.direct_scale else (1.0 + th.scale)
    return normed * scale + th.bias


class RmsNorm(base_layer.BaseLayer):
  """RMS normalization (no centering), common in large LMs."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Depth of the input.")
    p.Define("epsilon", 1e-6, "Variance floor.")
    return p

  def __init__(self, params):
    super().__init__(params)
    self.CreateVariable(
        "scale",
        WeightParams((self.p.input_dim,), WeightInit.Constant(0.0), self.p.dtype))

  def FProp(self, theta, inputs):
    th = self.CastTheta(theta)
    x32 = inputs.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(ms + self.p.epsilon)).astype(inputs.dtype)
    return normed * (1.0 + th.scale)


class BatchNormLayer(base_layer.BaseLayer):
  """Batch norm with functional moving-average updates (`bn_layers.py:114`).

  Train mode: uses batch moments, emits moving-stat updates through
  `py_utils.AddForwardStateUpdate` (collected by the train program); if a mesh
  axis name is given in `cross_replica_axes`, moments are all-reduced with
  psum — the TPU-native form of the reference's tpu_cross_replica BN.
  Eval mode: uses moving stats from theta.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("dim", 0, "Depth of the activation.")
    p.Define("decay", 0.999, "Moving-average decay.")
    p.Define("epsilon", 1e-3, "Variance floor.")
    p.Define("cross_replica_axes", None,
             "Mesh axis name(s) to all-reduce moments over (shard_map only).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.dim > 0, p.name
    self.CreateVariable(
        "beta", WeightParams((p.dim,), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable(
        "gamma", WeightParams((p.dim,), WeightInit.Constant(0.0), p.dtype))
    # Moving stats live in theta but are non-trainable (collections tag).
    self.CreateVariable(
        "moving_mean",
        WeightParams((p.dim,), WeightInit.Constant(0.0), jnp.float32,
                     collections=("non_trainable", "moving_stats")))
    self.CreateVariable(
        "moving_variance",
        WeightParams((p.dim,), WeightInit.Constant(1.0), jnp.float32,
                     collections=("non_trainable", "moving_stats")))

  def _Moments(self, x32, paddings):
    p = self.p
    reduce_dims = tuple(range(x32.ndim - 1))
    if paddings is None:
      count = jnp.asarray(
          float(math.prod(x32.shape[:-1])), jnp.float32)
      mean_sum = jnp.sum(x32, axis=reduce_dims)
      sq_sum = jnp.sum(jnp.square(x32), axis=reduce_dims)
    else:
      mask = py_utils.SequenceMask(paddings)
      while mask.ndim < x32.ndim:
        mask = mask[..., None]
      # Count of valid positions across ALL reduced dims (broadcast the mask
      # over spatial dims it doesn't cover, excluding the channel dim).
      bmask = jnp.broadcast_to(mask, x32.shape[:-1] + (1,))
      count = jnp.maximum(jnp.sum(bmask), 1.0)
      mean_sum = jnp.sum(x32 * mask, axis=reduce_dims)
      sq_sum = jnp.sum(jnp.square(x32) * mask, axis=reduce_dims)
    if p.cross_replica_axes:
      mean_sum = jax.lax.psum(mean_sum, p.cross_replica_axes)
      sq_sum = jax.lax.psum(sq_sum, p.cross_replica_axes)
      count = jax.lax.psum(count, p.cross_replica_axes)
    mean = mean_sum / count
    var = jnp.maximum(sq_sum / count - jnp.square(mean), 0.0)
    return mean, var

  def FProp(self, theta, inputs, paddings=None):
    p = self.p
    th = self.CastTheta(theta)
    x = self.ToFPropDtype(inputs)
    x32 = x.astype(jnp.float32)
    if py_utils.DoEval():
      mean, var = theta.moving_mean, theta.moving_variance
    else:
      mean, var = self._Moments(x32, paddings)
      new_mean = theta.moving_mean * p.decay + mean * (1.0 - p.decay)
      new_var = theta.moving_variance * p.decay + var * (1.0 - p.decay)
      py_utils.AddForwardStateUpdate(f"{self.path}/moving_mean", new_mean)
      py_utils.AddForwardStateUpdate(f"{self.path}/moving_variance", new_var)
    normed = (x32 - mean) * jax.lax.rsqrt(var + p.epsilon)
    out = (normed.astype(x.dtype) * (1.0 + th.gamma) + th.beta)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out


class GroupNormLayer(base_layer.BaseLayer):
  """Group normalization (`bn_layers.py` GroupNorm), used by Conformer."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("dim", 0, "Channel depth.")
    p.Define("num_groups", 32, "Number of groups.")
    p.Define("epsilon", 1e-3, "Variance floor.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.dim % p.num_groups == 0, (p.dim, p.num_groups)
    self.CreateVariable(
        "beta", WeightParams((p.dim,), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable(
        "gamma", WeightParams((p.dim,), WeightInit.Constant(0.0), p.dtype))

  def FProp(self, theta, inputs):
    p = self.p
    th = self.CastTheta(theta)
    x32 = inputs.astype(jnp.float32)
    shape = x32.shape
    grouped = x32.reshape(shape[:-1] + (p.num_groups, p.dim // p.num_groups))
    axes = tuple(range(1, grouped.ndim - 2)) + (grouped.ndim - 1,)
    mean = jnp.mean(grouped, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(grouped - mean), axis=axes, keepdims=True)
    normed = ((grouped - mean) * jax.lax.rsqrt(var + p.epsilon)).reshape(shape)
    return normed.astype(inputs.dtype) * (1.0 + th.gamma) + th.beta


# ---------------------------------------------------------------------------
# Convolutions (NHWC; lowered straight onto the MXU by XLA).
# ---------------------------------------------------------------------------


class Conv2DLayer(base_layer.BaseLayer):
  """2D convolution + optional BN/activation (`layers.py:182`).

  Input: [batch, height, width, in_channels] (NHWC; time-major ASR uses
  height=time). filter_shape = [fh, fw, in, out], filter_stride = [sh, sw].
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("filter_shape", (0, 0, 0, 0), "[fh, fw, cin, cout].")
    p.Define("filter_stride", (1, 1), "[stride_h, stride_w].")
    p.Define("dilation_rate", (1, 1), "[dil_h, dil_w].")
    p.Define("padding", "SAME", "SAME|VALID.")
    p.Define("activation", "NONE", "Activation name.")
    p.Define("batch_norm", True, "Apply BN after conv (ref default).")
    p.Define("has_bias", False, "Bias (only when no BN).")
    p.Define("causal_convolution", False,
             "Left-pad height (time) so output depends only on the past.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert all(d > 0 for d in p.filter_shape), p.filter_shape
    self.CreateVariable(
        "w", WeightParams(p.filter_shape, p.params_init, p.dtype))
    if p.has_bias:
      self.CreateVariable(
          "b",
          WeightParams((p.filter_shape[-1],), WeightInit.Constant(0.0), p.dtype))
    if p.batch_norm:
      self.CreateChild("bn", BatchNormLayer.Params().Set(dim=p.filter_shape[-1]))

  def _PadForCausal(self, x):
    """Left-pads time (height) so outputs depend only on the past.

    Returns (x, padding_spec) shared by all conv variants.
    """
    p = self.p
    if not p.causal_convolution:
      return x, p.padding
    fh = p.filter_shape[0]
    pad_h = (fh - 1) * p.dilation_rate[0]
    x = jnp.pad(x, ((0, 0), (pad_h, 0), (0, 0), (0, 0)))
    if p.padding == "VALID":
      return x, [(0, 0), (0, 0)]
    # SAME on width, explicit VALID on (already left-padded) time.
    return x, [(0, 0), ((p.filter_shape[1] - 1) // 2, p.filter_shape[1] // 2)]

  def _Conv(self, x, w):
    p = self.p
    x, padding = self._PadForCausal(x)
    return jax.lax.conv_general_dilated(
        x, w,
        window_strides=tuple(p.filter_stride),
        padding=padding,
        rhs_dilation=tuple(p.dilation_rate),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))

  def FProp(self, theta, inputs, paddings=None):
    """paddings: optional [b, t] time paddings (t = height dim)."""
    p = self.p
    th = self.CastTheta(theta)
    x = self.ToFPropDtype(inputs)
    if paddings is not None:
      x = x * py_utils.SequenceMask(paddings, x.dtype)[:, :, None, None]
    out = self._Conv(x, th.w)
    out_paddings = None
    if paddings is not None:
      # Derive from ACTUAL output length (VALID is shorter than t/stride).
      out_paddings = _StridedPaddings(paddings, p.filter_stride[0],
                                      out.shape[1])
    if p.has_bias:
      out = out + th.b
    if p.batch_norm:
      out = self.bn.FProp(theta.bn, out, out_paddings)
    if p.activation != "NONE":
      out = activations.GetFn(p.activation)(out)
    if out_paddings is not None:
      return out * py_utils.SequenceMask(out_paddings, out.dtype)[:, :, None,
                                                                  None], out_paddings
    return out


def _StridedPaddings(paddings, stride, out_len=None):
  """Paddings for a strided (conv/pool) output: window-start positions,
  trimmed to the op's actual output length (VALID < SAME)."""
  out = paddings if stride == 1 else paddings[:, ::stride]
  if out_len is not None:
    assert out.shape[1] >= out_len, (out.shape, out_len)
    out = out[:, :out_len]
  return out


class DepthwiseConv2DLayer(Conv2DLayer):
  """Depthwise conv: filter_shape=[fh, fw, cin, multiplier]."""

  def _Conv(self, x, w):
    p = self.p
    fh, fw, cin, mult = p.filter_shape
    w = jnp.reshape(w, (fh, fw, 1, cin * mult))
    x, padding = self._PadForCausal(x)
    return jax.lax.conv_general_dilated(
        x, w,
        window_strides=tuple(p.filter_stride),
        padding=padding,
        rhs_dilation=tuple(p.dilation_rate),
        feature_group_count=cin,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


class MaxPoolLayer(base_layer.BaseLayer):
  """Max pooling (`layers.py:2285`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("window_shape", (2, 2), "[h, w] window.")
    p.Define("window_stride", (2, 2), "[h, w] stride.")
    p.Define("padding", "SAME", "SAME|VALID.")
    return p

  def FProp(self, theta, inputs, paddings=None):
    p = self.p
    if paddings is not None:
      # Padded frames must not win the max over real negative activations.
      big_neg = jnp.asarray(jnp.finfo(inputs.dtype).min / 2, inputs.dtype)
      inputs = py_utils.ApplyPadding(paddings, inputs, pad_value=big_neg)
    out = jax.lax.reduce_window(
        inputs, -jnp.inf, jax.lax.max,
        (1,) + tuple(p.window_shape) + (1,),
        (1,) + tuple(p.window_stride) + (1,), p.padding)
    if paddings is not None:
      out_paddings = _StridedPaddings(paddings, p.window_stride[0],
                                      out.shape[1])
      out = py_utils.ApplyPadding(out_paddings, out)
      return out, out_paddings
    return out


# ---------------------------------------------------------------------------
# Embeddings & positional embeddings.
# ---------------------------------------------------------------------------


class SimpleEmbeddingLayer(base_layer.BaseLayer):
  """Token embedding lookup (`layers.py:2679`).

  On TPU, gather of a sharded table is fine under GSPMD; optionally use
  one-hot matmul (`use_matmul`) which maps better onto the MXU for small
  vocabularies.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("vocab_size", 0, "Vocabulary size.")
    p.Define("embedding_dim", 0, "Depth of the embedding.")
    p.Define("use_matmul", False, "One-hot matmul instead of gather.")
    p.Define("scale_sqrt_depth", False, "Scale outputs by sqrt(dim).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.vocab_size > 0 and p.embedding_dim > 0
    self.CreateVariable(
        "emb",
        WeightParams(
            shape=(p.vocab_size, p.embedding_dim),
            init=p.params_init if p.params_init != WeightInit.Xavier() else
            WeightInit.Gaussian(1.0 / math.sqrt(p.embedding_dim)),
            dtype=p.dtype,
            tensor_split_dims_mapping=p.weight_split_dims_mapping))

  def EmbLookup(self, theta, ids):
    p = self.p
    th = self.CastTheta(theta)
    if p.use_matmul:
      one_hot = jax.nn.one_hot(ids, p.vocab_size, dtype=th.emb.dtype)
      # Selection matmul: full precision so lookup == gather bit-for-bit-ish.
      out = jnp.einsum("...v,vd->...d", one_hot, th.emb,
                       precision=jax.lax.Precision.HIGHEST)
    else:
      out = jnp.take(th.emb, ids, axis=0)
    if p.scale_sqrt_depth:
      out = out * math.sqrt(p.embedding_dim)
    return out

  def FProp(self, theta, ids):
    return self.EmbLookup(theta, ids)


class PositionalEmbeddingLayer(base_layer.BaseLayer):
  """Sinusoidal positional embedding (`layers.py:3143`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("embedding_dim", 0, "Depth.")
    p.Define("min_timescale", 1, "Min timescale.")
    p.Define("max_timescale", 1e4, "Max timescale.")
    return p

  def _NameIsRequired(self):
    return False

  def FProp(self, theta, seq_length=None, position=None):
    """Returns [seq_length, dim] or per-position embeddings for `position`."""
    p = self.p
    assert p.embedding_dim % 2 == 0
    if position is None:
      position = jnp.arange(seq_length, dtype=jnp.float32)
    position = position.astype(jnp.float32)
    num_timescales = p.embedding_dim // 2
    log_inc = math.log(p.max_timescale / p.min_timescale) / max(
        1, num_timescales - 1)
    inv_timescales = p.min_timescale * jnp.exp(
        jnp.arange(num_timescales, dtype=jnp.float32) * -log_inc)
    scaled = position[..., None] * inv_timescales
    signal = jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)
    return self.ToFPropDtype(signal)


class RotaryPositionalEmbeddingLayer(base_layer.BaseLayer):
  """Rotary position embedding (`layers.py:3466` RoPE)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("embedding_dim", 0, "Per-head dim to rotate (must be even).")
    p.Define("min_timescale", 1, "Min timescale.")
    p.Define("max_timescale", 1e4, "Max timescale.")
    return p

  def _NameIsRequired(self):
    return False

  def FProp(self, theta, inputs, position=None):
    """inputs: [..., t, n, h]; rotates the first embedding_dim of h.

    When embedding_dim < h, the remaining h - embedding_dim features pass
    through unrotated (partial-rotary).
    """
    p = self.p
    dim = p.embedding_dim or inputs.shape[-1]
    assert dim % 2 == 0 and dim <= inputs.shape[-1], (dim, inputs.shape)
    x_rot, x_pass = inputs[..., :dim], inputs[..., dim:]
    half = dim // 2
    fraction = jnp.arange(half, dtype=jnp.float32) / half
    timescale = p.min_timescale * (p.max_timescale / p.min_timescale)**fraction
    t_ax = inputs.ndim - 3
    if position is None:
      position = jnp.arange(inputs.shape[t_ax], dtype=jnp.float32)
      shape = [1] * inputs.ndim
      shape[t_ax] = inputs.shape[t_ax]
      position = position.reshape(shape)
    else:
      while position.ndim < inputs.ndim:
        position = position[..., None]
    sinusoid = position / timescale
    sin, cos = jnp.sin(sinusoid), jnp.cos(sinusoid)
    first, second = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1)
    rotated = rotated.astype(inputs.dtype)
    if x_pass.shape[-1]:
      return jnp.concatenate([rotated, x_pass], axis=-1)
    return rotated


# ---------------------------------------------------------------------------
# Softmax / output layers.
# ---------------------------------------------------------------------------


class SimpleFullSoftmax(base_layer.BaseLayer):
  """Full softmax with xent helpers (`layers.py:3697`).

  Logits in fprop dtype, log-softmax/xent in float32 (TPU numerics policy).
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Input depth.")
    p.Define("num_classes", 0, "Output classes.")
    p.Define("has_bias", True, "Bias on logits.")
    p.Define("logits_soft_max", 0.0, "If >0, cap logits with tanh.")
    p.Define("xent_block_size", 0,
             "If >0, FProp with class_ids computes the fused blockwise "
             "xent (ops/fused_xent.py) this many vocab entries at a time "
             "and never materializes [..., V] logits (out.logits and "
             "out.log_probs are None; out.argmax/label_log_probs are "
             "provided instead). 0 = exact legacy dense path.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    self.CreateVariable(
        "linear",
        WeightParams(
            shape=(p.input_dim, p.num_classes),
            init=p.params_init,
            dtype=p.dtype,
            tensor_split_dims_mapping=p.weight_split_dims_mapping))
    if p.has_bias:
      self.CreateVariable(
          "bias",
          WeightParams((p.num_classes,), WeightInit.Constant(0.0), p.dtype))

  def Logits(self, theta, inputs):
    p = self.p
    th = self.CastTheta(theta)
    logits = jnp.einsum("...i,io->...o", self.ToFPropDtype(inputs), th.linear)
    if p.has_bias:
      logits = logits + th.bias
    if p.logits_soft_max > 0:
      logits = p.logits_soft_max * jnp.tanh(logits / p.logits_soft_max)
    return logits

  def XentLossFromLogits(self, logits, class_ids=None, class_probabilities=None,
                         label_smoothing=0.0):
    """Returns NestedMap(per_example_xent, log_probs) in float32."""
    return XentLossFromLogits(logits, self.p.num_classes, class_ids,
                              class_probabilities, label_smoothing)

  def FProp(self, theta, inputs, class_ids=None, class_probabilities=None,
            label_smoothing=0.0):
    p = self.p
    if FusedXentEligible(p, class_ids, class_probabilities):
      th = self.CastTheta(theta)
      return _FusedXentFProp(
          self, self.ToFPropDtype(inputs), th.linear, class_ids,
          label_smoothing, weight_layout="dv",
          bias=th.bias if p.has_bias else None)
    logits = self.Logits(theta, inputs)
    out = self.XentLossFromLogits(
        logits, class_ids, class_probabilities, label_smoothing)
    out.logits = logits
    return out


def FusedXentEligible(p, class_ids, class_probabilities) -> bool:
  """Gate for the blockwise fused LM-head xent: opted in via
  p.xent_block_size, needs integer labels (dense class_probabilities would
  re-materialize [..., V] anyway — fall back to the legacy path)."""
  return (getattr(p, "xent_block_size", 0) > 0 and class_ids is not None
          and class_probabilities is None)


def _FusedXentFProp(layer, inputs, weight, class_ids, label_smoothing,
                    weight_layout, bias=None):
  """Shared fused-path FProp for the softmax layers: same NestedMap shape
  as the dense path minus the [..., V] tensors, plus the per-block argmax
  (so `fraction_of_correct_next_step_preds` needn't re-materialize
  logits) and the label log-probs (the scoring path)."""
  from lingvo_tpu.ops import fused_xent
  p = layer.p
  out = fused_xent.FusedXent(
      inputs, weight, class_ids, block_size=p.xent_block_size,
      bias=bias, logits_soft_max=p.logits_soft_max,
      label_smoothing=label_smoothing, weight_layout=weight_layout)
  return NestedMap(per_example_xent=out.per_example_xent,
                   log_probs=None, logits=None,
                   label_log_probs=out.label_log_prob,
                   argmax=out.argmax)


class SingleShardFullSoftmax(SimpleFullSoftmax):
  """Full softmax for huge vocabularies (ref `layers.py:4494`).

  Two memory levers, composable:
  - vocab-dim sharding: set `weight_split_dims_mapping=(None, 'model')` and
    the [D, V] table plus each logits chunk shard over the model axis
    (GSPMD inserts the collectives) — the reference's SingleShard* family;
  - `chunk_size`: computes per-example xent `chunk_size` rows at a time
    with `lax.map`, never materializing the full [B*T, V] logits
    (ref `layers.py:3991-4040` chunked xent).
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("chunk_size", 0,
             "If >0, rows per xent chunk (memory over one big matmul).")
    return p

  def FProp(self, theta, inputs, class_ids=None, class_probabilities=None,
            label_smoothing=0.0):
    p = self.p
    if p.chunk_size <= 0 or class_ids is None:
      return super().FProp(theta, inputs, class_ids, class_probabilities,
                           label_smoothing)
    assert class_probabilities is None, "chunked path needs class_ids"
    lead_shape = class_ids.shape
    m = int(math.prod(lead_shape))
    x = inputs.reshape(m, inputs.shape[-1])
    ids = class_ids.reshape(m)
    pad = (-m) % p.chunk_size
    if pad:
      x = jnp.pad(x, ((0, pad), (0, 0)))
      ids = jnp.pad(ids, (0, pad))
    xc = x.reshape(-1, p.chunk_size, x.shape[-1])
    idc = ids.reshape(-1, p.chunk_size)

    def _Chunk(args):
      xi, idi = args
      logits = self.Logits(theta, xi)
      out = XentLossFromLogits(logits, p.num_classes, class_ids=idi,
                               label_smoothing=label_smoothing)
      return out.per_example_xent

    xent = jax.lax.map(_Chunk, (xc, idc)).reshape(-1)[:m]
    return NestedMap(per_example_xent=xent.reshape(lead_shape),
                     log_probs=None, logits=None)


class SharedEmbeddingSoftmaxLayer(base_layer.BaseLayer):
  """Ties input embedding and softmax weights (common LM configuration)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("vocab_size", 0, "Vocab.")
    p.Define("embedding_dim", 0, "Depth.")
    p.Define("scale_sqrt_depth", True, "Scale embeddings by sqrt(dim).")
    p.Define("embedding_multiplier", 1.0,
             "A constant the looked-up embeddings are multiplied by (beside "
             "scale_sqrt_depth; 1: none, and no op).")
    p.Define("logits_divisor", 1.0,
             "A constant the logits are divided by, before any cap (1: none, "
             "and no op).")
    p.Define("logits_soft_max", 0.0, "If >0, cap logits with tanh.")
    p.Define("xent_block_size", 0,
             "If >0, FProp with class_ids computes the fused blockwise "
             "xent (ops/fused_xent.py) over the tied table and never "
             "materializes [..., V] logits. 0 = exact legacy dense path.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    self.CreateVariable(
        "emb",
        WeightParams(
            shape=(p.vocab_size, p.embedding_dim),
            init=WeightInit.Gaussian(1.0 / math.sqrt(p.embedding_dim)),
            dtype=p.dtype,
            tensor_split_dims_mapping=p.weight_split_dims_mapping))

  def EmbLookup(self, theta, ids):
    p = self.p
    th = self.CastTheta(theta)
    emb = th.emb
    if isinstance(emb, quant_utils.Int8Weight):
      # gather int8 rows and dequantize by the per-row ('vd') scale — a
      # lookup has no matmul to run in int8, so this is exact w.r.t. the
      # frozen grid.
      rows = jnp.take(emb.w_int8, ids, axis=0).astype(jnp.float32)
      out = (rows * jnp.take(emb.scale.astype(jnp.float32), ids,
                             axis=0)).astype(self.fprop_dtype)
    else:
      out = jnp.take(emb, ids, axis=0)
    if p.scale_sqrt_depth:
      out = out * math.sqrt(p.embedding_dim)
    if p.embedding_multiplier != 1.0:
      out = out * p.embedding_multiplier
    return out

  def Logits(self, theta, inputs):
    th = self.CastTheta(theta)
    if isinstance(th.emb, quant_utils.Int8Weight):
      # tied softmax over the int8 table: [..., D] x int8 [V, D] ('vd').
      logits = th.emb.Einsum(self.ToFPropDtype(inputs))
    else:
      logits = jnp.einsum("...d,vd->...v", self.ToFPropDtype(inputs), th.emb)
    if self.p.logits_divisor != 1.0:
      logits = logits / self.p.logits_divisor
    if self.p.logits_soft_max > 0:
      logits = self.p.logits_soft_max * jnp.tanh(logits / self.p.logits_soft_max)
    return logits

  def XentLossFromLogits(self, logits, class_ids=None, class_probabilities=None,
                         label_smoothing=0.0):
    return XentLossFromLogits(logits, self.p.vocab_size, class_ids,
                              class_probabilities, label_smoothing)

  def FProp(self, theta, inputs, class_ids=None, class_probabilities=None,
            label_smoothing=0.0):
    if (FusedXentEligible(self.p, class_ids, class_probabilities)
        and not isinstance(theta.emb, quant_utils.Int8Weight)):
      # the fused blockwise kernel slices the float table; int8-serving
      # thetas take the dense Logits path below (scoring, not training).
      th = self.CastTheta(theta)
      return _FusedXentFProp(
          self, self.ToFPropDtype(inputs), th.emb, class_ids,
          label_smoothing, weight_layout="vd")
    logits = self.Logits(theta, inputs)
    out = self.XentLossFromLogits(
        logits, class_ids, class_probabilities, label_smoothing)
    out.logits = logits
    return out

  @property
  def num_classes(self):
    return self.p.vocab_size


def XentLossFromLogits(logits, num_classes, class_ids=None,
                       class_probabilities=None, label_smoothing=0.0):
  """Softmax cross-entropy in float32; returns NestedMap(per_example_xent,
  log_probs)."""
  logits32 = logits.astype(jnp.float32)
  log_probs = jax.nn.log_softmax(logits32)
  if class_probabilities is None:
    assert class_ids is not None
    class_probabilities = jax.nn.one_hot(
        class_ids, num_classes, dtype=jnp.float32)
  if label_smoothing > 0.0:
    class_probabilities = ((1.0 - label_smoothing) * class_probabilities +
                           label_smoothing / num_classes)
  per_example_xent = -jnp.sum(class_probabilities * log_probs, axis=-1)
  return NestedMap(per_example_xent=per_example_xent, log_probs=log_probs)


# ---------------------------------------------------------------------------
# Label smoothing (standalone, for seq2seq targets).
# ---------------------------------------------------------------------------


class UniformLabelSmoother(base_layer.BaseLayer):
  """Uniform label smoothing (`layers.py` UniformLabelSmoother)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("num_classes", 0, "Classes.")
    p.Define("uncertainty", 0.1, "Smoothing mass.")
    return p

  def _NameIsRequired(self):
    return False

  def FProp(self, theta, target_ids):
    p = self.p
    one_hot = jax.nn.one_hot(target_ids, p.num_classes, dtype=jnp.float32)
    return (1.0 - p.uncertainty) * one_hot + p.uncertainty / p.num_classes


class EinsumEmbeddingLayer(SimpleEmbeddingLayer):
  """Embedding as a pure einsum over one-hot ids (ref
  `layers.py:3018` EinsumEmbeddingLayer): SimpleEmbeddingLayer with the
  matmul formulation forced on — the MXU-native choice, and the one GSPMD
  partitions cleanly when the table is sharded (gather would all-gather
  the table)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.use_matmul = True
    return p


class SampledSoftmax(base_layer.BaseLayer):
  """Sampled softmax for huge vocabularies (ref `SimpleFullSoftmax`'s
  num_sampled path, `layers.py:3697+` — what the word-level 793k-vocab
  1B-words configs need).

  Training computes logits only over the true class + num_sampled
  log-uniform (Zipfian) negatives with the standard expected-count
  correction; eval uses the full softmax. Sampling draws from the step-seed
  context so it is deterministic per step.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Input depth.")
    p.Define("num_classes", 0, "Full vocabulary size.")
    p.Define("num_sampled", 4096, "Negatives sampled per batch.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.num_classes > 0
    self.CreateVariable(
        "w", WeightParams((p.num_classes, p.input_dim), p.params_init,
                          p.dtype,
                          tensor_split_dims_mapping=(
                              p.weight_split_dims_mapping)))
    self.CreateVariable(
        "b", WeightParams((p.num_classes,), WeightInit.Constant(0.0),
                          p.dtype))

  def _LogExpectedCount(self, ids):
    """log E[count(id)] under num_sampled draws of the log-uniform (Zipf)
    sampler (ref TF's log_uniform_candidate_sampler + sampled-softmax
    correction logit - log Q): E[count] = num_sampled * P(id)."""
    ids = ids.astype(jnp.float32)
    log_p = jnp.log(
        jnp.log((ids + 2.0) / (ids + 1.0)) /
        math.log(self.p.num_classes + 1.0))
    return log_p + math.log(self.p.num_sampled)

  def _SampleNegatives(self, key):
    """Log-uniform sampling via inverse CDF: id = floor(exp(u*log(V+1)))-1."""
    p = self.p
    u = jax.random.uniform(key, (p.num_sampled,))
    ids = jnp.exp(u * math.log(p.num_classes + 1.0)) - 1.0
    return jnp.clip(ids.astype(jnp.int32), 0, p.num_classes - 1)

  def Logits(self, theta, inputs):
    """Full logits (eval / decode path)."""
    th = self.CastTheta(theta)
    return jnp.einsum("...d,vd->...v", self.ToFPropDtype(inputs),
                      th.w) + th.b

  def XentLossFromInputs(self, theta, inputs, class_ids):
    """Sampled-softmax xent: inputs [..., D], class_ids [...] -> xent [...].

    Falls back to the full softmax outside training (no step seed).
    """
    p = self.p
    th = self.CastTheta(theta)
    if py_utils.DoEval() or not py_utils.HasStepSeed():
      logits = self.Logits(theta, inputs).astype(jnp.float32)
      return XentLossFromLogits(logits, p.num_classes,
                                class_ids=class_ids).per_example_xent
    key = py_utils.StepSeed(f"{self.path}/sampled_softmax")
    neg_ids = self._SampleNegatives(key)                   # [S]
    x = self.ToFPropDtype(inputs)
    # true-class logit with its correction
    w_true = jnp.take(th.w, class_ids, axis=0)             # [..., D]
    b_true = jnp.take(th.b, class_ids, axis=0)
    true_logit = jnp.sum(x * w_true, -1) + b_true
    true_logit = true_logit.astype(jnp.float32) - self._LogExpectedCount(
        class_ids)
    # negative logits with their corrections
    w_neg = jnp.take(th.w, neg_ids, axis=0)                # [S, D]
    b_neg = jnp.take(th.b, neg_ids, axis=0)
    neg_logits = jnp.einsum("...d,sd->...s", x, w_neg) + b_neg
    neg_logits = neg_logits.astype(jnp.float32) - self._LogExpectedCount(
        neg_ids)
    # mask accidental hits of the true class among negatives
    hit = (neg_ids == class_ids[..., None])
    neg_logits = jnp.where(hit, -1e9, neg_logits)
    all_logits = jnp.concatenate([true_logit[..., None], neg_logits], -1)
    return -jax.nn.log_softmax(all_logits, axis=-1)[..., 0]


class StackingOverTime(base_layer.BaseLayer):
  """Stacks adjacent frames and subsamples time (ref
  `layers.py:2006` StackingOverTime — the classic ASR encoder front):
  [b, t, d] -> [b, ceil(t/stride), d*(left+1+right)]."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("left_context", 0, "Past frames stacked per output frame.")
    p.Define("right_context", 2, "Future frames stacked.")
    p.Define("stride", 3, "Output frame subsampling.")
    return p

  @property
  def window_size(self):
    return self.p.left_context + 1 + self.p.right_context

  def FProp(self, theta, inputs, paddings=None):
    """Returns (stacked [b, t_out, d*window], out_paddings [b, t_out])."""
    p = self.p
    b, t, d = inputs.shape
    if paddings is None:
      paddings = jnp.zeros((b, t), inputs.dtype)
    x = jnp.pad(inputs, ((0, 0), (p.left_context, p.right_context), (0, 0)))
    pad = jnp.pad(paddings, ((0, 0), (p.left_context, p.right_context)),
                  constant_values=1.0)
    frames = [x[:, i:i + t] for i in range(self.window_size)]
    stacked = jnp.concatenate(frames, axis=-1)             # [b, t, d*w]
    stacked = stacked[:, ::p.stride]
    # an output frame is padding iff its CENTER frame was padding (ref)
    out_paddings = pad[:, p.left_context:p.left_context + t][:, ::p.stride]
    return stacked, out_paddings
