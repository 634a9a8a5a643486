"""A dropless top-k expert layer: every token reaches every expert it chose.

`parallel/gshard.py` trains expert-parallel: tokens are dispatched into
`[E, C]` capacity slots over an 'expert' mesh axis and a token that finds its
expert full is dropped. A served token cannot be dropped, and on one chip
there is no exchange to shape the dispatch for, so this layer sorts instead:

  route     router logits `[T, E]` from the LAYER's input, handed in by
            `TransformerLayer` ("router before attention"), top-k,
            softmax over the chosen logits (softmax then top-k then
            renormalise gives the same numbers);
  dispatch  the `T x k` (token, expert) pairs sorted by expert: a gather of
            `[T * k, D]` rows and the experts' run lengths `[E]`;
  experts   three grouped matmuls over the runs, ReGLU:
            (relu(x W_gate) * (x W_up)) W_down, widths D -> F -> D;
  combine   each row weighted, unsorted, and a token's k rows summed.

No capacity, no `[T, E, C]` tensor, no dropped token; an expert with no token
is a run of length 0. The layer holds all its experts and runs no collective:
an expert-parallel form would be told which experts it holds (ROADMAP R1).

The grouped matmul is `GroupedMatmul`: megablox's Pallas kernel on a TPU
where the shapes tile, `jax.lax.ragged_dot` elsewhere (PERF.md section 6,
PR 35, has the chip's reading of both). Padding tokens of a packed step
(`paddings` 1) are routed nowhere: they sort behind every run, cost the
experts nothing and count for nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lingvo_tpu import observe
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.core.py_utils import WeightInit, WeightParams

_GMM_TILE = 128   # megablox tiles m, k and n; a shape it cannot tile takes
#                   ragged_dot

def GroupedMatmul(lhs, rhs, group_sizes):
  """lhs [M, K] rows in runs by group, rhs [G, K, N], group_sizes [G] int32
  -> [M, N]: row r of run g times rhs[g]. Rows past the last run come out
  zero or unspecified by the lowering; callers mask them."""
  m, k = lhs.shape
  n = rhs.shape[-1]
  if (jax.default_backend() == "tpu" and m % _GMM_TILE == 0
      and k % _GMM_TILE == 0 and n % _GMM_TILE == 0):
    import importlib
    gmm_lib = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    return gmm_lib.gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                       tiling=_GmmTiling(m, k, n))
  return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _GmmTiling(m: int, k: int, n: int) -> tuple[int, int, int]:
  """Tiles of 128 rows by the whole contraction by as many columns as keep
  the weight tile within 4 MiB: the fastest of the eight tilings measured
  on a v5e at the serve step's 6,528 rows by 2,560 by 768 (PERF.md section
  6, PR 35). Each divides its dimension."""
  def _Largest(dim, cap):
    return max(t for t in range(_GMM_TILE, dim + 1, _GMM_TILE)
               if dim % t == 0 and (t <= cap or t == _GMM_TILE))
  tk = _Largest(k, 2560)
  tn = _Largest(n, max(_GMM_TILE, (4 * 2**20 // 2) // tk))
  return _GMM_TILE, tk, tn


class DroplessMoELayer(base_layer.BaseLayer):
  """Pre-norm expert feed-forward with residual; a `tr_fflayer_tpl`."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim.")
    p.Define("hidden_dim", 0, "Width of one expert.")
    p.Define("num_experts", 0, "Experts held (all of them).")
    p.Define("num_experts_per_token", 2, "k: experts a token reaches.")
    p.Define("norm_tpl", layers_lib.RmsNorm.Params(), "Norm template.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.hidden_dim > 0
    assert 0 < p.num_experts_per_token <= p.num_experts
    d, f, e = p.input_dim, p.hidden_dim, p.num_experts
    self.CreateChild("ln", p.norm_tpl.Copy().Set(input_dim=d))
    self.CreateVariable(
        "w_router", WeightParams((d, e), WeightInit.Gaussian(
            1.0 / math.sqrt(d)), p.dtype))
    # fans are an expert's own, not the stack's
    for name, shape, fan_in in (("w_gate", (e, d, f), d),
                                ("w_up", (e, d, f), d),
                                ("w_down", (e, f, d), f)):
      self.CreateVariable(
          name, WeightParams(shape, WeightInit.Gaussian(
              1.0 / math.sqrt(fan_in)), p.dtype))

  def StackAddressed(self) -> tuple[str, ...]:
    """The variables a scan over layers hands this layer WHOLE,
    [layers, E, ...], beside the layer's index (`layer`), instead of a
    layer's slice: the experts' matrices. Sliced out of the stack a layer
    at a time they would be copied a layer at a time (a kernel's operand is
    a buffer: 0.25 GB a matrix, 2 ms each on a v5e, PERF.md section 6,
    PR 35), so the layer addresses its run of groups in the stack, as an
    attention layer addresses its pages."""
    return ("w_gate", "w_up", "w_down")

  def RouterLogits(self, theta, x):
    """x [..., D], the transformer layer's un-normed input -> f32 [..., E].
    `TransformerLayer` calls it before its attention block and hands the
    logits to FProp / RaggedStep."""
    th = self.CastTheta(theta)
    return jnp.einsum("...d,de->...e", self.ToFPropDtype(x), th.w_router,
                      preferred_element_type=jnp.float32)

  def _Experts(self, theta, x, logits, valid, layer=None):
    """x [T, D] normed tokens, logits f32 [T, E], valid bool [T] or None ->
    (f32-weighted sum of each token's k experts [T, D], tokens by expert
    [E] int32). layer: set where the experts' matrices arrive stacked over
    layers (StackAddressed): this layer's experts are groups
    [layer * E, (layer + 1) * E) of the stack seen as one run of groups."""
    p = self.p
    th = self.CastTheta(theta)
    t, d = x.shape
    e, k = p.num_experts, p.num_experts_per_token
    with observe.Scope("moe_route"):
      top_logits, top_idx = jax.lax.top_k(logits, k)               # [T, k]
      weights = jax.nn.softmax(top_logits, axis=-1)
      if valid is not None:
        # a padding token's pairs sort behind every expert's run
        top_idx = jnp.where(valid[:, None], top_idx, e)
    with observe.Scope("moe_dispatch"):
      flat = top_idx.reshape(-1)                                    # [T * k]
      order = jnp.argsort(flat, stable=True)
      counts = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
      xs = x[order // k]                                            # [T*k, D]
    sizes, flat = counts, lambda w: w
    if th.w_gate.ndim == 4:
      layers = th.w_gate.shape[0]
      sizes = jax.lax.dynamic_update_slice(
          jnp.zeros((layers * e,), jnp.int32), counts,
          (jnp.asarray(layer, jnp.int32) * e,))
      flat = lambda w: w.reshape((-1,) + w.shape[2:])
    with observe.Scope("moe_experts"):
      h = jax.nn.relu(GroupedMatmul(xs, flat(th.w_gate), sizes))
      h = h * GroupedMatmul(xs, flat(th.w_up), sizes)
      ys = GroupedMatmul(h.astype(xs.dtype), flat(th.w_down), sizes)
    with observe.Scope("moe_combine"):
      w_sorted = weights.reshape(-1)[order]
      live = jnp.arange(t * k) < jnp.sum(counts)
      ys = jnp.where(live[:, None], ys.astype(jnp.float32)
                     * w_sorted[:, None], 0.0)
      # unsort by a gather through the inverse permutation
      out = ys[jnp.argsort(order)].reshape(t, k, d).sum(axis=1)
    return out.astype(x.dtype), counts

  def FPropWithCounts(self, theta, inputs, router_logits, paddings=None,
                      layer=None):
    """inputs [..., D]; router_logits f32 [..., E] (RouterLogits of the
    transformer layer's input); paddings [...] (1 = padding) or None.
    Returns (inputs + experts [..., D], tokens by expert [E] int32)."""
    p = self.p
    with observe.Scope("norm"):
      x = self.ln.FProp(theta.ln, inputs)
    with observe.Scope("ffn"):
      d = x.shape[-1]
      valid = None if paddings is None else paddings.reshape(-1) < 0.5
      out, counts = self._Experts(
          theta, x.reshape(-1, d),
          router_logits.reshape(-1, p.num_experts), valid, layer)
      out = inputs + out.reshape(inputs.shape)
    return out, counts

  def FProp(self, theta, inputs, paddings=None, *, router_logits):
    return self.FPropWithCounts(theta, inputs, router_logits, paddings)[0]

  # -- the serving step ------------------------------------------------------

  def InitPagedStates(self, theta) -> NestedMap:
    """`routed` [E] int32: tokens each expert got in the newest step. It is
    the engine's to read (Stats: moe_*), overwritten every step."""
    del theta
    return NestedMap(routed=jnp.zeros((self.p.num_experts,), jnp.int32))

  def RaggedStep(self, theta, inputs, cached_states, rows, *, router_logits,
                 layer=None):
    """inputs [1, T, D] packed tokens (core/ragged.py RaggedRows); the
    step's padding tokens are routed nowhere. layer: as in
    MultiHeadedAttention.RaggedStep, the index of this layer's `routed` in
    a stack carried by RepeatedTransformerLayer's scan."""
    paddings = 1.0 - rows.valid.astype(jnp.float32)[None]
    out, counts = self.FPropWithCounts(
        theta, inputs, router_logits, paddings,
        layer=layer if theta.w_gate.ndim == 4 else None)
    if layer is None:
      return out, NestedMap(routed=counts)
    return out, NestedMap(
        routed=cached_states.routed.at[layer].set(counts))
