"""The chip's side of a roofline share: the least time its peaks
(``peaks.json``, by ``device_kind``) allow for a call's needs.  What a
call NEEDS is its family's to say (``families/<family>/needs.py``).
"""

from __future__ import annotations


def least_seconds(needs: dict, peak: dict) -> tuple:
    """(seconds, which bound) the chip could do it in at its peaks."""
    by_bytes = needs["bytes"] / peak["hbm_bytes_per_s"]
    by_flops = needs["flops"] / peak["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
