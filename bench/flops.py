"""Arithmetic every architecture family shares: causal pair counts and
the roofline share of a measured time.

What one family's model needs (its FLOPs per token and the work of
each kernel) is counted in its own module beside its reference,
``bench/configs/<family>.py``, which ``loader.family`` finds by the
``family`` named in the configuration file.
"""

from __future__ import annotations

BF16 = 2


def attn_pairs(start: int, n: int) -> int:
    """Causal (query, key) pairs of ``n`` queries at positions
    ``start .. start+n-1``: query ``p`` sees keys ``0..p``."""
    return n * start + n * (n + 1) // 2


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(share in %, bound) of the least time the work allows over the
    measured ``seconds``; bound is "compute" or "memory"."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
