"""A dropless top-k expert layer: every token reaches every expert it chose.

`parallel/gshard.py` trains expert-parallel: tokens are dispatched into
`[E, C]` capacity slots over an 'expert' mesh axis and a token that finds its
expert full is dropped. A served token cannot be dropped, and on one chip
there is no exchange to shape the dispatch for, so this layer sorts instead:

  route     router logits `[T, E]` from the LAYER's input, handed in by
            `TransformerLayer` ("router before attention"), or from the
            layer's own normed input (`router_reads`); top-k; `scoring`
            'softmax': softmax over the chosen logits (softmax then top-k
            then renormalise gives the same numbers); 'sigmoid': scores
            sigmoid(logits), chosen by score + `router_bias` (a bias that
            chooses and does not weigh), weights the chosen scores over
            their sum times `routed_scale`;
  dispatch  the `T x k` (token, expert) pairs sorted by expert: a gather of
            `[T * k, D]` rows and the experts' run lengths `[E]`;
  experts   grouped matmuls over the runs, widths D -> F -> D; `activation`
            'reglu': three, (relu(x W_gate) * (x W_up)) W_down; 'swiglu':
            three, (silu(x W_gate) * (x W_up)) W_down; 'relu2': two,
            relu(x W_up)^2 W_down, and no W_gate exists;
  combine   the rows un-sorted by ONE gather in the matmuls' dtype, `k` the
            major axis `[k, T, D]`; weighted and a token's k summed in f32;
  shared    where `shared_hidden_dim` > 0, one more expert of that width
            that every token goes through, added to the routed sum.

No capacity, no `[T, E, C]` tensor, no dropped token; an expert with no token
is a run of length 0. The layer runs no collective. By default it holds all
the experts its router scores; as one of several chips that share the layer
it is TOLD which it holds (`first_expert`, `num_experts_held`: a contiguous
run): the router keeps its whole width and chooses among all `num_experts`,
the weights of a token's k are normalised over all k, and a (token, expert)
pair whose expert lives elsewhere sorts behind every run as a padding token's
does: it costs the grouped matmuls nothing and adds nothing here. The shared
expert is computed by every share. Nothing stands in for the other chips or
for the exchange that would sum their parts (ROADMAP R1).

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
# `activation`s with a gate matrix -> what the gate's product goes through
_GATE_ACTIVATION = {"reglu": jax.nn.relu, "swiglu": jax.nn.silu}


def _StoredWidth(d: int, f: int) -> int:
  """The width the experts' [d, f] / [f, d] matrices are STORED at. Where the
  kernel tiles the model dim and not the experts' width (1856 = 14.5 x 128),
  the width is padded up to whole tiles: zero columns of W_up (and W_gate),
  zero rows of W_down, so every product is the same to the bit (relu(0) = 0)
  and the kernel runs (PERF.md section 6, PR 45: 4.9 ms against ragged_dot's
  43). A layout, like the page pool's: `hidden_dim` stays the model's width.
  A model dim the kernel cannot tile takes ragged_dot whatever the width, and
  nothing is padded."""
  return -(-f // _GMM_TILE) * _GMM_TILE if d % _GMM_TILE == 0 else f


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
    p.Define("num_experts", 0,
             "Experts the router scores and chooses among; all of them are "
             "held here unless num_experts_held says fewer.")
    p.Define("first_expert", 0,
             "The first expert of the contiguous run this layer holds.")
    p.Define("num_experts_held", 0,
             "Experts held: [first_expert, first_expert + num_experts_held) "
             "of num_experts; 0 = all of them. The experts' matrices have "
             "this many, the router num_experts.")
    p.Define("num_experts_per_token", 2, "k: experts a token reaches.")
    p.Define("norm_tpl", layers_lib.RmsNorm.Params(), "Norm template.")
    p.Define("scoring", "softmax",
             "'softmax': weights are the softmax over the chosen logits. "
             "'sigmoid': scores sigmoid(logits) in f32, the k chosen by "
             "score + router_bias, weights routed_scale * score / (sum of "
             "the chosen scores).")
    p.Define("routed_scale", 1.0, "Factor on the weights ('sigmoid').")
    p.Define("activation", "reglu",
             "'reglu': relu(x W_gate) * (x W_up). 'swiglu': silu(x W_gate) "
             "* (x W_up). 'relu2': relu(x W_up)^2, two matrices an expert.")
    p.Define("shared_hidden_dim", 0,
             "Width of the shared expert every token goes through (same "
             "activation); 0 = none.")
    p.Define("residual_scale", 1.0,
             "Factor on the layer's output (routed and shared) before it is "
             "added to its input (1: none, and no op).")
    p.Define("post_norm_tpl", None,
             "A norm on the layer's output (routed and shared), before the "
             "residual add; None: none, no variable and no op.")
    p.Define("router_reads", "layer_input",
             "'layer_input': the logits are handed in (RouterLogits of the "
             "transformer layer's un-normed input). 'normed_input': the "
             "router reads this layer's own normed input.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.hidden_dim > 0
    assert 0 < p.num_experts_per_token <= p.num_experts
    assert p.scoring in ("softmax", "sigmoid"), p.scoring
    assert p.activation in _GATE_ACTIVATION or p.activation == "relu2", (
        p.activation)
    assert p.router_reads in ("layer_input", "normed_input"), p.router_reads
    d, e = p.input_dim, p.num_experts
    held = self.num_held
    assert 0 <= p.first_expert and p.first_expert + held <= e, (
        p.first_expert, held, e)
    f = _StoredWidth(d, p.hidden_dim)
    self.CreateChild("ln", p.norm_tpl.Copy().Set(input_dim=d))
    if p.post_norm_tpl is not None:
      self.CreateChild("post_ln", p.post_norm_tpl.Copy().Set(input_dim=d))
    self.CreateVariable(
        "w_router", WeightParams((d, e), WeightInit.Gaussian(
            1.0 / math.sqrt(d)), p.dtype))
    if p.scoring == "sigmoid":
      self.CreateVariable("router_bias", WeightParams(
          (e,), WeightInit.Constant(0.0), p.dtype))
    # fans are an expert's own, not the stack's
    fs = p.shared_hidden_dim
    gated = p.activation in _GATE_ACTIVATION
    for name, shape, fan_in in (
        [("w_gate", (held, d, f), d)] * gated
        + [("w_up", (held, d, f), d), ("w_down", (held, f, d), f)]
        + ([("w_shared_gate", (d, fs), d)] * gated
           + [("w_shared_up", (d, fs), d), ("w_shared_down", (fs, d), fs)]
           ) * (fs > 0)):
      self.CreateVariable(
          name, WeightParams(shape, WeightInit.Gaussian(
              1.0 / math.sqrt(fan_in)), p.dtype))

  @property
  def num_held(self) -> int:
    """Experts this layer holds: the length of `routed` and of the experts'
    matrices."""
    return self.p.num_experts_held or self.p.num_experts

  def InstantiateVariables(self, key):
    theta = super().InstantiateVariables(key)
    p = self.p
    if _StoredWidth(p.input_dim, p.hidden_dim) != p.hidden_dim:
      # the padded layout is made an expert at a time, each with its zeros
      # where it is made: a second pass over a scanned block's matrices
      # ([repeats, 128, 2688, 1920]) would hold them twice
      for i, name in enumerate(self.StackAddressed()):
        e, rows, cols = theta[name].shape
        down = name == "w_down"
        live = jnp.arange(rows if down else cols) < p.hidden_dim
        live = live[:, None] if down else live[None, :]
        scale = (p.hidden_dim if down else rows) ** -0.5

        def _One(k, live=live, scale=scale, shape=(rows, cols)):
          return jnp.where(live, scale * jax.random.normal(k, shape, p.dtype),
                           0).astype(p.dtype)

        theta[name] = jax.lax.map(_One, jax.random.split(
            jax.random.fold_in(key, i + 1), e))
    return theta

  def StackAddressed(self) -> tuple[str, ...]:
    """The variables a scan over layers hands this layer WHOLE,
    [layers, E, ...], beside the layer's index (`layer`), instead of a
    layer's slice: the experts' matrices. Sliced out of the stack a layer
    at a time they would be copied a layer at a time (a kernel's operand is
    a buffer: 0.25 GB a matrix, 2 ms each on a v5e, PERF.md section 6,
    PR 35), so the layer addresses its run of groups in the stack, as an
    attention layer addresses its pages."""
    return (("w_gate",) if self.p.activation in _GATE_ACTIVATION else ()) + (
        "w_up", "w_down")

  def RouterLogits(self, theta, x):
    """x [..., D], the transformer layer's un-normed input -> f32 [..., E].
    `TransformerLayer` calls it before its attention block and hands the
    logits to FProp / RaggedStep ('layer_input'; a layer whose router reads
    its own normed input takes its logits itself, in f32)."""
    th = self.CastTheta(theta)
    return jnp.einsum("...d,de->...e", self.ToFPropDtype(x), th.w_router,
                      preferred_element_type=jnp.float32)

  def _Route(self, th, logits):
    """logits f32 [T, E] -> (the k experts a token reaches [T, k] int32,
    their weights f32 [T, k])."""
    p = self.p
    k = p.num_experts_per_token
    if p.scoring == "softmax":
      top_logits, top_idx = jax.lax.top_k(logits, k)
      return top_idx, jax.nn.softmax(top_logits, axis=-1)
    scores = jax.nn.sigmoid(logits)
    _, top_idx = jax.lax.top_k(scores + th.router_bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, top_idx, axis=-1)
    return top_idx, p.routed_scale * chosen / jnp.sum(chosen, -1,
                                                      keepdims=True)

  def _Experts(self, theta, x, logits, valid, layer=None):
    """x [T, D] normed tokens, logits f32 [T, E], valid bool [T] or None ->
    (f32-weighted sum of each token's experts held here [T, D], tokens by
    held expert [Eh] int32). layer: set where the experts' matrices arrive
    stacked over layers (StackAddressed): this layer's experts are groups
    [layer * Eh, (layer + 1) * Eh) of the stack seen as one run of groups."""
    p = self.p
    th = self.CastTheta(theta)
    t, e, k = x.shape[0], self.num_held, p.num_experts_per_token
    with observe.Scope("moe_route"):
      top_idx, weights = self._Route(th, logits)                   # [T, k]
      if e != p.num_experts:
        # a pair whose expert lives elsewhere sorts behind every run, as a
        # padding token's; its weight stays in the sum the k were
        # normalised over
        top_idx = top_idx - p.first_expert
        top_idx = jnp.where((top_idx >= 0) & (top_idx < e), top_idx, e)
      if valid is not None:
        # a padding token's pairs sort behind every expert's run
        top_idx = jnp.where(valid[:, None], top_idx, e)
    with observe.Scope("moe_dispatch"):
      flat = top_idx.reshape(-1)                                    # [T * k]
      order = jnp.argsort(flat, stable=True)
      counts = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
      xs = x[order // k]                                            # [T*k, D]
    sizes, flat = counts, lambda w: w
    if th.w_up.ndim == 4:
      layers = th.w_up.shape[0]
      sizes = jax.lax.dynamic_update_slice(
          jnp.zeros((layers * e,), jnp.int32), counts,
          (jnp.asarray(layer, jnp.int32) * e,))
      flat = lambda w: w.reshape((-1,) + w.shape[2:])
    gate = _GATE_ACTIVATION.get(p.activation)   # None: 'relu2', no gate matrix
    with observe.Scope("moe_experts"):
      if gate is not None:
        h = gate(GroupedMatmul(xs, flat(th.w_gate), sizes))
        h = h * GroupedMatmul(xs, flat(th.w_up), sizes)
      else:
        h = jnp.square(jax.nn.relu(GroupedMatmul(xs, flat(th.w_up), sizes)))
      ys = GroupedMatmul(h.astype(xs.dtype), flat(th.w_down), sizes)
    with observe.Scope("moe_combine"):
      # each pair's place in sorted order, k MAJOR: ONE gather of the matmul's
      # rows; row AND weight masked by value (either may be NaN: 0 x NaN)
      live = (top_idx < e).T[..., None]                          # [k, T, 1]
      pos = jnp.argsort(order).reshape(t, k).T                      # [k, T]
      ys = jnp.where(live, ys[pos], 0).astype(jnp.float32)       # [k, T, D]
      out = jnp.sum(ys * jnp.where(live, weights.T[..., None], 0.0), axis=0)
    if p.shared_hidden_dim:
      with observe.Scope("moe_shared"):
        up = jnp.einsum("td,df->tf", x, th.w_shared_up)
        if gate is not None:
          h = gate(jnp.einsum("td,df->tf", x, th.w_shared_gate)) * up
        else:
          h = jnp.square(jax.nn.relu(up))
        out = out + jnp.einsum("tf,fd->td", h, th.w_shared_down)
    return out.astype(x.dtype), counts

  def FPropWithCounts(self, theta, inputs, router_logits=None, paddings=None,
                      layer=None):
    """inputs [..., D]; router_logits f32 [..., E] (RouterLogits of the
    transformer layer's input; None where the router reads this layer's own
    normed input); paddings [...] (1 = padding) or None.
    Returns (inputs + experts held here [..., D], tokens by held expert
    [Eh] int32)."""
    p = self.p
    theta = base_layer.TakeSlices(theta)
    with observe.Scope("norm"):
      x = self.ln.FProp(theta.ln, inputs)
    with observe.Scope("ffn"):
      d = x.shape[-1]
      if p.router_reads == "normed_input":
        assert router_logits is None
        with observe.Scope("moe_route"):
          # the norm and the product in f32: a router is a discrete cut, and
          # a score rounded to the stream's precision moves it
          router_logits = jnp.einsum(
              "...d,de->...e",
              self.ln.FProp(theta.ln, inputs.astype(jnp.float32)),
              theta.w_router.astype(jnp.float32),
              precision=jax.lax.Precision.HIGHEST)
      valid = None if paddings is None else paddings.reshape(-1) < 0.5
      out, counts = self._Experts(
          theta, x.reshape(-1, d),
          router_logits.reshape(-1, p.num_experts), valid, layer)
      out = out.reshape(inputs.shape)
      if p.post_norm_tpl is not None:
        with observe.Scope("post_norm"):
          out = self.post_ln.FProp(theta.post_ln, out)
      out = inputs + (out if p.residual_scale == 1.0
                      else p.residual_scale * out)
    return out, counts

  def FProp(self, theta, inputs, paddings=None, *, router_logits=None):
    return self.FPropWithCounts(theta, inputs, router_logits, paddings)[0]

  # -- the serving step ------------------------------------------------------

  def InitPagedStates(self, theta) -> NestedMap:
    """`routed` [Eh] int32: tokens each held expert got in the newest step.
    It is the engine's to read (Stats: moe_*), overwritten every step. A
    layer that holds a share of its experts adds `elsewhere` [] int32: the
    step's (token, expert) pairs whose expert lives on another chip
    (moe_pairs_elsewhere)."""
    del theta
    states = NestedMap(routed=jnp.zeros((self.num_held,), jnp.int32))
    if self.num_held != self.p.num_experts:
      states.elsewhere = jnp.zeros((), jnp.int32)
    return states

  def CountLeaves(self, counts, valid_tokens) -> NestedMap:
    """A step's count leaves, as `InitPagedStates` lays them out, from its
    tokens by held expert `counts` [Eh] and the number of its tokens that
    were routed: `elsewhere` is what is left of their k pairs."""
    leaves = NestedMap(routed=counts)
    if self.num_held != self.p.num_experts:
      leaves.elsewhere = (self.p.num_experts_per_token * valid_tokens
                          - jnp.sum(counts))
    return leaves

  def RaggedStep(self, theta, inputs, cached_states, rows, *,
                 router_logits=None, layer=None):
    """inputs [1, T, D] packed tokens (core/ragged.py RaggedRows); the
    step's padding tokens are routed nowhere. layer: as in
    MultiHeadedAttention.RaggedStep, the index of this layer's `routed` in
    a stack carried by RepeatedTransformerLayer's scan."""
    paddings = 1.0 - rows.valid.astype(jnp.float32)[None]
    out, counts = self.FPropWithCounts(
        theta, inputs, router_logits, paddings,
        layer=layer if theta.w_up.ndim == 4 else None)
    new_states = self.CountLeaves(
        counts, jnp.sum(rows.valid.astype(jnp.int32)))
    if layer is None:
      return out, new_states
    return out, jax.tree_util.tree_map(
        lambda old, new: old.at[layer].set(new), cached_states, new_states)
