"""Mamba-1 selective scan over the serving engine's packed token axis.

The recurrence of one Mamba-1 layer, per channel e of E and state index n of
N, with a step size `delta` and an input `x` per (token, channel), `b` and
`c` per (token, state index), and `a = -exp(A_log)` per (state index,
channel):

    s_t = exp(delta_t * a) * s_{t-1} + (delta_t * x_t) * b_t       [N, E]
    y_t = sum_n s_t[n] * c_t[n] + d_skip * x_t                     [E]

Unlike `ops/ssd_scan.py` the decay differs along the state axis (a matrix
`a`, a step size a channel), so no scalar-decay chunked form expresses it.

The tokens are the PACKED axis of a serving step (`core/ragged.RaggedRows`):
a slot's tokens are one contiguous run, the valid tokens lead the axis, and a
run starts from its slot's state `[N, E]` (zero where the row is a request's
first token, `fresh`) and leaves its last state there. A decode row is a run
of one token and a prefill chunk one of several; nothing is re-laid out by
row, so a step of 64 one-token rows and a 512-token chunk costs its 576
tokens.

State is kept `[slots, N, E]`, the channels on the lanes: a `[.., E, 16]`
array pads its minor dimension eightfold on the chip.

Two lowerings of the same float operations:

- `_PallasSelectiveScan`: grid over blocks of `Eb` channels (independent, so
  `parallel`); inside a block a loop over the valid tokens in packed order,
  eight to a trip, with the running state `[N, Eb]` in registers. A run's
  first token takes the slot's state from the output block (which starts as
  a copy of the input block), its last token puts it back. Per-token scalars
  (slot, first / last / fresh flags) ride scalar prefetch.
- `_XlaSelectiveScan`: the CPU serving path and the twin the kernel is held
  to: the row view `[slots, wmax]` of the pack scanned over columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu import observe
from lingvo_tpu.ops.flash_attention import LANES, SUBLANES

_FIRST, _LAST, _FRESH = 1, 2, 4     # bits of a token's flags
_VMEM_LIMIT = 48 * 2**20


def ChannelBlock(e: int) -> int:
  """Channels a program of the kernel holds: 512 lanes (a `[16, 512]` f32
  state is 8 vregs) where E divides, else all of them."""
  return 512 if e % 512 == 0 else e


def _XlaSelectiveScan(delta, x, b, c, a, d_skip, state, row_of, col_of,
                      valid, row_len, fresh, row_cols):
  """delta, x: [T, E]; b, c: [T, N]; a: [N, E]; d_skip: [E];
  state: [B, N, E]; row_cols: [B, wmax] packed index of a row's columns.
  -> (y [T, E], new state [B, N, E]), f32."""
  wmax = row_cols.shape[1]
  s0 = jnp.where(fresh[:, None, None], 0.0, state)
  cols = jnp.clip(row_cols, 0, delta.shape[0] - 1)

  def _Col(s, j):
    tok = cols[:, j]                                          # [B]
    d, xx, bb, cc = delta[tok], x[tok], b[tok], c[tok]
    s_new = (jnp.exp(d[:, None, :] * a[None]) * s
             + (d * xx)[:, None, :] * bb[:, :, None])
    y = jnp.sum(s_new * cc[:, :, None], axis=1) + d_skip[None] * xx
    live = (j < row_len)[:, None, None]
    return jnp.where(live, s_new, s), y

  s, ys = jax.lax.scan(_Col, s0, jnp.arange(wmax))            # ys [wmax, B, E]
  rows = jnp.clip(row_of, 0, state.shape[0] - 1)
  y = ys[jnp.clip(col_of, 0, wmax - 1), rows]
  return jnp.where(valid[:, None], y, 0.0), s


def _ScanKernel(row_ref, flag_ref, n_ref, delta_ref, x_ref, b_ref, c_ref,
                a_ref, d_ref, state_ref, y_ref, out_ref):
  """One block of channels: every valid token in packed order."""
  n_valid = n_ref[0]
  out_ref[...] = state_ref[...]
  y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)
  a = a_ref[...]                                              # [N, Eb]
  d_skip = d_ref[...]                                         # [1, Eb]
  eb = a.shape[1]
  sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, eb), 0)

  def _Group(g, s):
    base = pl.multiple_of(g * SUBLANES, SUBLANES)
    d8 = delta_ref[pl.ds(base, SUBLANES), :]                  # [8, Eb]
    x8 = x_ref[pl.ds(base, SUBLANES), :]
    b8, c8 = b_ref[g], c_ref[g]                               # [N, 8]
    y8 = jnp.zeros((SUBLANES, eb), jnp.float32)
    for k in range(SUBLANES):
      t = base + k
      live = t < n_valid
      row, flag = row_ref[t], flag_ref[t]
      first = (flag & _FIRST) != 0
      fresh = (flag & _FRESH) != 0
      held = out_ref[row]                                     # [N, Eb]
      s_in = jnp.where(first, jnp.where(fresh, 0.0, held), s)
      d, xx = d8[k:k + 1], x8[k:k + 1]                        # [1, Eb]
      s_new = (jnp.exp(d * a) * s_in + (d * xx) * b8[:, k:k + 1])
      y = jnp.sum(s_new * c8[:, k:k + 1], axis=0, keepdims=True) + d_skip * xx
      y8 = jnp.where((sub == k) & live, y, y8)
      s = jnp.where(live, s_new, s)

      @pl.when(live & ((flag & _LAST) != 0))
      def _Put(row=row, s_new=s_new):
        out_ref[row] = s_new

    y_ref[pl.ds(base, SUBLANES), :] = y8
    return s

  groups = (n_valid + SUBLANES - 1) // SUBLANES
  jax.lax.fori_loop(0, groups, _Group,
                    jnp.zeros(a.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ScanCall(row_of, flags, n_valid, delta, x, b8, c8, a, d_skip, state, *,
              interpret: bool):
  """The kernel over its grid. A `jit` of its own, as
  ragged_block_attend._GroupedCall: the layers of a stack that call at the
  same shapes share one trace, and the scope keeps the kernel's name."""
  t, e = delta.shape
  slots, n, _ = state.shape
  eb = ChannelBlock(e)
  by_block = lambda i, *_: (0, i)
  whole = lambda i, *_: (0, 0, 0)
  with observe.Scope("ssm_scan"):
    return pl.pallas_call(
        _ScanKernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(e // eb,),
            in_specs=[
                pl.BlockSpec((t, eb), by_block),
                pl.BlockSpec((t, eb), by_block),
                pl.BlockSpec(b8.shape, whole),
                pl.BlockSpec(c8.shape, whole),
                pl.BlockSpec((n, eb), by_block),
                pl.BlockSpec((1, eb), by_block),
                pl.BlockSpec((slots, n, eb), lambda i, *_: (0, 0, i)),
            ],
            out_specs=[
                pl.BlockSpec((t, eb), by_block),
                pl.BlockSpec((slots, n, eb), lambda i, *_: (0, 0, i)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((t, e), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(row_of, flags, n_valid, delta, x, b8, c8, a, d_skip, state)


def _PallasSelectiveScan(delta, x, b, c, a, d_skip, state, row_of, col_of,
                         valid, row_len, fresh, interpret: bool):
  t, e = delta.shape
  n = b.shape[1]
  pad = -t % SUBLANES
  rows = jnp.clip(row_of.astype(jnp.int32), 0, state.shape[0] - 1)
  flags = ((col_of == 0) * _FIRST + (col_of == row_len[rows] - 1) * _LAST
           + fresh[rows] * _FRESH).astype(jnp.int32)
  n_valid = jnp.sum(valid.astype(jnp.int32)).reshape(1)

  def _Tokens(v):
    return jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))

  def _ByEight(v):
    # [T, N] -> [T / 8, N, 8]: a trip's eight tokens beside each state index
    return _Tokens(v).reshape(-1, SUBLANES, n).swapaxes(1, 2)

  y, new_state = _ScanCall(
      _Tokens(rows), _Tokens(flags), n_valid, _Tokens(delta), _Tokens(x),
      _ByEight(b), _ByEight(c), a, d_skip[None], state, interpret=interpret)
  return y[:t], new_state


def SupportedOnTpu(e: int, n: int) -> bool:
  """Mosaic's tiling: channels on whole lanes, state indices on whole
  sublanes."""
  return e % LANES == 0 and n % SUBLANES == 0


def SelectiveScan(delta, x, b, c, a, d_skip, state, rows, *,
                  lowering: str = "auto", interpret: bool | None = None):
  """The packed step's scan (module docstring). delta, x: [T, E]; b, c:
  [T, N]; a: [N, E]; d_skip: [E]; state: [B, N, E]; rows: the step's
  `core/ragged.RaggedRows` (chains only). All f32 inside, whatever arrives.
  -> (y [T, E] f32, zeros at padding tokens; new state [B, N, E] f32).
  lowering: 'auto' (the kernel on a TPU where `SupportedOnTpu`, the twin
  elsewhere) | 'pallas' | 'xla'."""
  assert lowering in ("auto", "pallas", "xla"), lowering
  f32 = lambda v: v.astype(jnp.float32)
  delta, x, b, c, a, d_skip, state = map(
      f32, (delta, x, b, c, a, d_skip, state))
  on_tpu = jax.default_backend() == "tpu"
  if lowering == "auto":
    lowering = ("pallas" if on_tpu and SupportedOnTpu(*a.shape[::-1])
                else "xla")
  row_of = rows.row_of.astype(jnp.int32)
  col_of = rows.col_of.astype(jnp.int32)
  row_len = rows.row_len.astype(jnp.int32)
  fresh = rows.row_q_pos == 0
  if lowering == "xla":
    with observe.Scope("ssm_scan"):
      return _XlaSelectiveScan(delta, x, b, c, a, d_skip, state, row_of,
                               col_of, rows.valid, row_len, fresh,
                               rows.row_cols)
  if interpret is None:
    interpret = not on_tpu
  return _PallasSelectiveScan(delta, x, b, c, a, d_skip, state, row_of,
                              col_of, rows.valid, row_len, fresh, interpret)
