#!/usr/bin/env python3
"""An expert layer's two grouped matmuls at a width that megablox cannot tile
(F = 1856 = 14.5 x 128): `jax.lax.ragged_dot` over the matrices as the file
states them against megablox over the same matrices stored padded to the next
multiple of 128 (zero columns of W_up, zero rows of W_down; relu(0)^2 = 0, so
the product is the same to the bit). One reading of each, at a full step's
rows and at a decode-only step's (PERF.md section 6, PR 45).

  python3 benchmarks/tools/gmm_width.py [--tokens 1088,64] [--experts 128]
      [--model_dim 2688] [--width 1856] [--per_token 6] [--iters 20]

Prints one JSON line a row count: milliseconds of up, relu^2 and down through
each lowering, and whether the two agree.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--tokens", default="1088,64")
  ap.add_argument("--experts", type=int, default=128)
  ap.add_argument("--model_dim", type=int, default=2688)
  ap.add_argument("--width", type=int, default=1856)
  ap.add_argument("--per_token", type=int, default=6)
  ap.add_argument("--iters", type=int, default=20)
  args = ap.parse_args(argv)

  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import moe

  e, d, f, k = args.experts, args.model_dim, args.width, args.per_token
  fp = -(-f // moe._GMM_TILE) * moe._GMM_TILE
  key = jax.random.PRNGKey(0)
  ku, kd, kx = jax.random.split(key, 3)
  w_up = (jax.random.normal(ku, (e, d, f), jnp.float32) * d ** -0.5
          ).astype(jnp.bfloat16)
  w_down = (jax.random.normal(kd, (e, f, d), jnp.float32) * f ** -0.5
            ).astype(jnp.bfloat16)
  w_up_p = jnp.pad(w_up, ((0, 0), (0, 0), (0, fp - f)))
  w_down_p = jnp.pad(w_down, ((0, 0), (0, fp - f), (0, 0)))

  def _Experts(xs, up, down, sizes):
    h = jnp.square(jax.nn.relu(moe.GroupedMatmul(xs, up, sizes)))
    return moe.GroupedMatmul(h.astype(xs.dtype), down, sizes)

  fn = jax.jit(_Experts)
  rng = np.random.RandomState(0)
  for t in (int(x) for x in args.tokens.split(",")):
    m = t * k
    choice = np.stack([rng.permutation(e)[:k] for _ in range(t)]).reshape(-1)
    sizes = jnp.asarray(np.bincount(choice, minlength=e), jnp.int32)
    xs = jax.random.normal(kx, (m, d), jnp.float32).astype(jnp.bfloat16)
    out = {}
    ms = {}
    for name, up, down in (("ragged_dot", w_up, w_down),
                           ("padded_megablox", w_up_p, w_down_p)):
      out[name] = jax.block_until_ready(fn(xs, up, down, sizes))
      t0 = time.perf_counter()
      for _ in range(args.iters):
        y = fn(xs, up, down, sizes)
      jax.block_until_ready(y)
      ms[name] = 1e3 * (time.perf_counter() - t0) / args.iters
    a, b = (np.asarray(out[n], np.float32) for n in ms)
    print(json.dumps({
        "tokens": t, "rows": m, "experts": e, "active": int((sizes > 0).sum()),
        "model_dim": d, "width": f, "padded": fp, "ms": ms,
        "max_abs_diff": float(np.abs(a - b).max()),
        "backend": jax.default_backend()}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
