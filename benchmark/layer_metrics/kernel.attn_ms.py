"""Summed device duration per step of the Pallas attention kernels
(`dwt_fa_*`: forward, fused backward, split dq / dkv), device 0.
Under recomputation the second forward call is in it."""

from benchmark import xtrace

NAME, UNIT, SOURCE = "kernel.attn_ms", "ms", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


PREFIXES = ("dwt_fa_",)


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    ms = xtrace.per_step_ms(trace, PREFIXES)
    return ms if ms else None
