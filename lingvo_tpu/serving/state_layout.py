"""What the decode state is made of: which leaves are pages, which are a
slot's, on which axes, and the one gather / scatter over them.

`task.InitPagedDecodeState(theta, num_pages, page_size, num_slots,
kv_cache_dtype)` returns one tree whose leaves are of three sorts, and nothing
in the tree says which is which: page pools (`[..., pages, ..., page_size,
...]`: K and V, int8 scale sidecars, a retention layer's gates), slot states
(`[..., slots, ...]`: an O(1) mixer's recurrent state) and leaves of neither
sort (an expert layer's token counts). `Detect` tells them apart by STRUCTURE:
it abstract-evaluates the function at a second value of each of its three
geometry parameters, and the axis of a leaf that moved with the pool's size is
its page axis, with the page's size its token-offset axis, with the slots its
slot axis. That handles every layout the same way: a flat stack's pool pages
on axis 0, a repeated stack's on axis 1 (behind the layers' axis), a sidecar
`[pages, heads, page_size]` keeps its offsets on another axis than the pool
beside it `[pages, page_size, heads, head]`, and a stack that keeps one
`kv_pool` for all its layers is one more leaf.

Everything that moves decode state by page, by (page, offset) or by slot goes
through `StateLayout.Gather` / `Scatter` (copy-on-write, the fleet's page
handoff, preemption's spill and restore, tree speculation's KV repair): a new
leaf a mixer declares rides along without any of them knowing it.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax

# what an index addresses -> the leaf axes it runs over, in the index's order
_KIND_AXES = {"page": ("page",), "slot": ("slot",),
              "token": ("page", "offset")}


class LeafAxes(NamedTuple):
  """One leaf's axes; None where the leaf has no such axis."""
  page: Optional[int]
  offset: Optional[int]
  slot: Optional[int]


class StateLayout:
  """`leaves`: a LeafAxes a leaf of the decode state, in tree_leaves order.

  `Gather`, `Scatter` and `Copy` are pure functions of the states, for use
  inside a caller's jit; `gather`, `scatter` and `copy` are their jits (kind
  static, indices traced, so one compile a kind and index shape; `scatter` and
  `copy` donate the states off-CPU, as the step program does).

  kind 'page': idx an int32 scalar or [n] of pages; 'slot': a scalar or [n]
  of slots; 'token': a pair (pages, offsets) of equal shape. A block is the
  leaf with the index's shape in place of the indexed axes."""

  def __init__(self, leaves):
    self.leaves = tuple(leaves)
    donate = (0,) if jax.default_backend() != "cpu" else ()
    self.gather = jax.jit(self.Gather, static_argnums=(1,))
    self.scatter = jax.jit(self.Scatter, static_argnums=(1,),
                           donate_argnums=donate)
    self.copy = jax.jit(self.Copy, static_argnums=(1,),
                        donate_argnums=donate)

  def _Indexers(self, kind, idx, n_leaves):
    """Per leaf: the index tuple that addresses `idx` on the kind's axes, or
    None for a leaf without them."""
    assert n_leaves == len(self.leaves), (n_leaves, len(self.leaves))
    idx = idx if isinstance(idx, tuple) else (idx,)
    names = _KIND_AXES[kind]
    assert len(idx) == len(names), (kind, len(idx))
    out = []
    for leaf in self.leaves:
      axes = [getattr(leaf, name) for name in names]
      if axes[0] is None:
        out.append(None)
        continue
      at = [slice(None)] * (max(axes) + 1)
      for axis, i in zip(axes, idx):
        at[axis] = i
      out.append(tuple(at))
    return out

  def Gather(self, states, kind: str, idx) -> list:
    """-> [block] for the leaves that have the kind's axes, in leaf order."""
    leaves = jax.tree_util.tree_leaves(states)
    return [leaf[at] for leaf, at in zip(
        leaves, self._Indexers(kind, idx, len(leaves))) if at is not None]

  def Scatter(self, states, kind: str, idx, blocks):
    """`Gather`'s blocks into (other) indices -> states."""
    leaves, treedef = jax.tree_util.tree_flatten(states)
    blocks = iter(blocks)
    out = [leaf if at is None else leaf.at[at].set(next(blocks))
           for leaf, at in zip(leaves,
                               self._Indexers(kind, idx, len(leaves)))]
    return jax.tree_util.tree_unflatten(treedef, out)

  def Copy(self, states, kind: str, src, dst):
    """What `src` addresses, written where `dst` does."""
    return self.Scatter(states, kind, dst, self.Gather(states, kind, src))


def Detect(task, theta, num_pages: int, page_size: int, num_slots: int,
           kv_cache_dtype=None) -> StateLayout:
  """The layout of `task.InitPagedDecodeState(theta, num_pages, page_size,
  num_slots, kv_cache_dtype)` (module docstring). Four abstract evaluations
  and no device work; callers build it on first use."""
  def _Leaves(np_total, ps, slots):
    return jax.tree_util.tree_leaves(jax.eval_shape(
        lambda th: task.InitPagedDecodeState(th, np_total, ps, slots,
                                             kv_cache_dtype), theta))

  def _Moved(a, b):
    diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
    assert len(diff) <= 1, (a.shape, b.shape)
    return diff[0] if diff else None

  base = _Leaves(num_pages, page_size, num_slots)
  # twice the page, not one token more: a kernel's page is a multiple of its
  # tile, and a mixer may refuse at set-up a page that is not
  leaves = [
      LeafAxes(_Moved(a, p), _Moved(a, o), _Moved(a, s))
      for a, p, o, s in zip(base,
                            _Leaves(num_pages + 1, page_size, num_slots),
                            _Leaves(num_pages, 2 * page_size, num_slots),
                            _Leaves(num_pages, page_size, num_slots + 1))]
  for leaf, a in zip(leaves, base):
    assert (leaf.page is None) == (leaf.offset is None), (a.shape, leaf)
  return StateLayout(leaves)
