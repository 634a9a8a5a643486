"""Learner: the per-loss optimization pipeline.

Re-implements `lingvo/core/learner.py` (`Learner:31`, `Apply:177`,
`ScaleGradients:434`) functionally: gradient computation happens in the train
program with `jax.grad`; the Learner takes (theta, grads, step, opt_state) and
produces (new_theta, new_opt_state, stats), handling loss-weight scaling,
global-norm clipping, per-value capping, NaN/Inf global skip (ref
`_GetGlobalGradScale:395`), Lp regularization, and the LR schedule.

Under data parallelism the gradients arriving here are already mean-reduced by
GSPMD (batch-dim sharding + jax.grad emits the psum) — the TPU-native form of
the reference's `cross_replica_sum` aggregation (`py_utils.py:3059-3079`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lingvo_tpu import observe
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import optimizer as optimizer_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core import schedule as schedule_lib
from lingvo_tpu.core.nested_map import NestedMap


class Learner(base_layer.BaseLayer):

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("learning_rate", 1e-3, "Base learning rate.")
    p.Define("lr_schedule", schedule_lib.Constant.Params(),
             "Multiplier schedule on learning_rate.")
    p.Define("optimizer", optimizer_lib.Adam.Params(), "Optimizer template.")
    p.Define("loss_name", "loss",
             "Which entry of the task's metrics dict to optimize.")
    p.Define("clip_gradient_norm_to_value", 0.0,
             "If >0, clip global grad norm to this.")
    p.Define("clip_gradient_single_norm_to_value", 0.0,
             "If >0, clip each tensor's norm to this.")
    p.Define("grad_norm_to_clip_to_zero", 0.0,
             "If >0 and global norm exceeds this, skip the step (outlier "
             "batch rejection).")
    p.Define("skip_nan_gradients", True,
             "Skip updates whose global grad norm is NaN/Inf.")
    p.Define("l2_regularizer_weight", None, "Optional L2 on trainable theta.")
    p.Define("l1_regularizer_weight", None, "Optional L1 on trainable theta.")
    p.Define("grad_aggregation_fn", None,
             "Optional fn(grads)->grads before clipping (e.g. custom psum).")
    p.Define("bprop_variable_filter", None,
             "Regex: only vars whose path matches are trained.")
    p.Define("bprop_variable_exclusion", None,
             "Regex: vars whose path matches are NOT trained.")
    return p

  def __init__(self, params):
    super().__init__(params)
    self.CreateChild("lr_sched", self.p.lr_schedule)
    self.CreateChild("opt", self.p.optimizer)

  # -- variable filtering ----------------------------------------------------

  def TrainableFilter(self, path: str, wp=None) -> bool:
    """Whether the variable at `path` is trained by this learner."""
    import re
    p = self.p
    if wp is not None and "non_trainable" in tuple(wp.collections or ()):
      return False
    if p.bprop_variable_filter and not re.search(p.bprop_variable_filter, path):
      return False
    if p.bprop_variable_exclusion and re.search(p.bprop_variable_exclusion,
                                                path):
      return False
    return True

  # -- regularization (added to the loss by the task's train program) --------

  def RegularizationLoss(self, theta: NestedMap) -> jax.Array:
    p = self.p
    loss = jnp.zeros((), jnp.float32)
    if p.l2_regularizer_weight:
      loss += 0.5 * p.l2_regularizer_weight * sum(
          jnp.sum(jnp.square(w.astype(jnp.float32)))
          for w in jax.tree_util.tree_leaves(theta))
    if p.l1_regularizer_weight:
      loss += p.l1_regularizer_weight * sum(
          jnp.sum(jnp.abs(w.astype(jnp.float32)))
          for w in jax.tree_util.tree_leaves(theta))
    return loss

  # -- state -----------------------------------------------------------------

  def InitState(self, theta: NestedMap) -> NestedMap:
    return self.opt.InitState(theta)

  # -- apply -----------------------------------------------------------------

  def LearningRate(self, step) -> jax.Array:
    return self.p.learning_rate * self.lr_sched.Value(step)

  def Apply(self, theta: NestedMap, grads: NestedMap, step,
            opt_state: NestedMap) -> tuple[NestedMap, NestedMap, NestedMap]:
    """Returns (new_theta, new_opt_state, stats NestedMap)."""
    with observe.Scope("optimizer_update"):
      return self._Apply(theta, grads, step, opt_state)

  def _Apply(self, theta, grads, step, opt_state):
    p = self.p
    if p.grad_aggregation_fn is not None:
      grads = p.grad_aggregation_fn(grads)
    grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)

    grad_norm = py_utils.GlobalNorm(grads)
    stats = NestedMap(grad_norm=grad_norm)

    # Global scale: 0 when non-finite or above clip-to-zero; else optional
    # global-norm clip (ref ScaleGradients:434). NaN norms must be sanitized
    # BEFORE entering any arithmetic: 0 * NaN = NaN would defeat the skip.
    finite = jnp.isfinite(grad_norm)
    safe_norm = jnp.where(finite, grad_norm, 1.0)
    keep = finite if p.skip_nan_gradients else jnp.asarray(True)
    if p.grad_norm_to_clip_to_zero > 0:
      keep = jnp.logical_and(keep, safe_norm <= p.grad_norm_to_clip_to_zero)
    grad_scale = keep.astype(jnp.float32)
    if p.clip_gradient_norm_to_value > 0:
      clip = jnp.minimum(
          1.0, p.clip_gradient_norm_to_value / jnp.maximum(safe_norm, 1e-30))
      grad_scale = grad_scale * clip
    # Zero (not NaN-scale) grads on skipped steps so optimizer slots stay
    # finite; theta/state are additionally rolled back below.
    grads = jax.tree_util.tree_map(
        lambda g: jnp.where(keep, g * grad_scale, jnp.zeros_like(g)), grads)
    if p.clip_gradient_single_norm_to_value > 0:

      def _ClipSingle(g):
        n = jnp.sqrt(jnp.sum(jnp.square(g)) + 1e-30)
        return g * jnp.minimum(1.0, p.clip_gradient_single_norm_to_value / n)

      grads = jax.tree_util.tree_map(_ClipSingle, grads)

    lr = self.LearningRate(step)
    stats.learning_rate = lr
    stats.grad_scale = grad_scale

    new_theta, new_state = self.opt.Update(opt_state, grads, theta, lr, step)
    # Skip = keep everything unchanged when scale hit 0 (NaN or outlier).
    skipped = grad_scale == 0.0
    stats.skipped_step = skipped.astype(jnp.float32)
    new_theta = jax.tree_util.tree_map(
        lambda n, o: jnp.where(skipped, o, n), new_theta, theta)
    new_state = jax.tree_util.tree_map(
        lambda n, o: jnp.where(skipped, o, n), new_state, opt_state)
    return new_theta, new_state, stats
