"""paged_attn_roofline_pct.saturated (%): least time for the KV bytes and FLOPs the live slots need (the larger of bytes/819 GB/s and FLOPs/197 TFLOP/s) over the paged kernel's device time."""

from chipbench.metrics import _lib as L


def read(obs):
    return L.paged_attn_roofline_pct(obs)
