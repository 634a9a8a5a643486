"""KV-cache quantization: per-token-per-head int8 with f32 scale sidecars.

Numerics contract (docs/quantized_serving.md):

  - Quantization happens exactly once, at WRITE time, in the same scatter
    that places a token's K/V into its page (`PagedStep`) or cache row
    (`ExtendStep`/`Prefill`). Each written token row [N, H] gets one
    symmetric max-abs scale PER HEAD — `scale[n] = max(|x[n, :]|) / 127`.
    Writes touch only the written slots, so quantization is purely local:
    no page-level re-quantization ever revisits (and re-rounds) already
    written tokens. That is why the sidecar is per-slot-per-head rather
    than the coarser per-page granularity — a page-level max grows as
    tokens stream in, and rescaling in place would be lossy.
  - Dequantization happens at READ time, inside the decode kernel (both
    the Pallas and XLA lowerings share `ops.block_decode._DequantPages`,
    which is what makes the twins bitwise-identical) or just before the
    dense `_Atten` fallback.
  - Scale sidecars for the paged pool are stored TRANSPOSED as
    [num_pages, N, page_size] f32 so the Pallas block's minor dimension is
    page_size (already gated to a multiple of 128 lanes by
    `SupportedOnTpu`). The dense cache keeps the natural [B, L, N] layout
    (it is XLA-only).

fp8 (float8_e4m3) storage reuses this exact plumbing — the registry below
reserves the name — but is a follow-on until the CI toolchain can
round-trip fp8 scatters.
"""

from __future__ import annotations

import jax.numpy as jnp

# Storage dtypes the KV pools understand. "" / None means "fprop dtype" —
# the bit-exact legacy cache. Only int8 carries scale sidecars.
KV_CACHE_DTYPES = ("float32", "bfloat16", "int8")


def ResolveKvCacheDtype(kv_cache_dtype, fprop_dtype):
  """-> (pool storage dtype, quantized?: bool).

  None/'' keeps the legacy behavior: the pool is allocated in the layer's
  fprop dtype and every read/write is a plain cast-free copy (bit-exact
  with the pre-quantization engine). 'float32'/'bfloat16' change only the
  storage dtype; 'int8' additionally switches on the scale sidecars and
  quantize-on-write.
  """
  if not kv_cache_dtype:
    return jnp.dtype(fprop_dtype), False
  if kv_cache_dtype not in KV_CACHE_DTYPES:
    raise ValueError(
        f"kv_cache_dtype={kv_cache_dtype!r} not in {KV_CACHE_DTYPES}")
  if kv_cache_dtype == "int8":
    return jnp.dtype(jnp.int8), True
  return jnp.dtype(kv_cache_dtype), False


def QuantizeKv(x):
  """[..., N, H] float K/V rows -> ([..., N, H] int8, [..., N] f32 scale).

  Symmetric per-head max-abs over H. The scale floor (1e-8) keeps all-zero
  rows well-defined: they quantize to zeros and dequantize to zeros.
  """
  x32 = x.astype(jnp.float32)
  amax = jnp.max(jnp.abs(x32), axis=-1)
  scale = jnp.maximum(amax / 127.0, 1e-8)
  q = jnp.clip(jnp.round(x32 / scale[..., None]), -128, 127).astype(jnp.int8)
  return q, scale


def DequantKv(q, scale):
  """([..., N, H] int8, [..., N] f32) -> [..., N, H] f32."""
  return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def KvBytesPerToken(num_heads: int, dim_per_head: int, kv_cache_dtype,
                    fprop_dtype) -> int:
  """K + V bytes one cached token costs in one attention layer, sidecars
  included (int8 adds 2 * N f32 scales per token)."""
  dtype, quantized = ResolveKvCacheDtype(kv_cache_dtype, fprop_dtype)
  per = 2 * num_heads * dim_per_head * dtype.itemsize
  if quantized:
    per += 2 * num_heads * 4
  return per
