"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip, whole
process (set-up included), in GiB."""

from benchmark import readers

NAME, UNIT, SOURCE = "device.peak_hbm_gib", "GiB", "program_counter"
LAYER, MOVES = "device", "tokens_per_s"


def read(trace, events, ledgers, cell):
    peak = readers.measured(ledgers).get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
