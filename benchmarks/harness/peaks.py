"""Published peaks of the chips the benchmark may run on, keyed by
`jax.devices()[0].device_kind`. One table, here and nowhere else.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect. The bf16 figure
was copied from `bench._PEAK_FLOPS`; 819 GB/s is new here. A device that is
not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
  flops_bf16: float      # FLOP/s
  hbm_bytes_s: float     # bytes/s
  source: str


_V5E = Peak(197e12, 819e9, "cloud.google.com/tpu/docs/v5e")

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def PeakOf(device_kind: str) -> Peak:
  try:
    return PEAKS[device_kind]
  except KeyError:
    raise KeyError(
        f"no published peak for device_kind {device_kind!r}; add it to "
        f"benchmarks/harness/peaks.py with its source (known: "
        f"{sorted(PEAKS)})") from None
