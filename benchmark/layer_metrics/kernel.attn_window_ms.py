"""Summed device duration per step of the WINDOWED attention layers'
Pallas kernels (`dwt_fa_win_*`: forward, split dq / dkv, fused backward),
device 0: the part of `kernel.attn_ms` a sliding-window layer's calls
take (`ops/flash_attention.py` names a windowed call's kernels so).
Under recomputation the second forward call is in it.  A program whose
kernels know no window has no such op and reports nothing."""

from benchmark import xtrace

NAME, UNIT, SOURCE = "kernel.attn_window_ms", "ms", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


PREFIXES = ("dwt_fa_win_",)


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    ms = xtrace.per_step_ms(trace, PREFIXES)
    return ms if ms else None
